"""Counter-based pseudo-random bits for collision chirality and forcing.

The FHP update needs cheap random bits per node per step -- one for
FHP-II (chirality of two-/four-body rotations), two for FHP-III (the
member of a collision class) -- and one uniform per node per step
(forcing with probability p).  A stateful PRNG array would double the
memory traffic of a memory-bound algorithm, so we hash the (position,
time, salt) counter instead - the TPU analogue of the paper's implicit
per-thread RNG, with bitwise ops only (VPU-native).

The mix is a 32-bit xorshift/multiply hash (splitmix-style).  Statistical
quality is far above what FHP chirality needs (a coin flip per node).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLD = np.uint32(0x9E3779B9)


def hash_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Final-avalanche mix of a uint32 array (murmur3 finalizer)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def counter_u32(shape, t, salt: int, y0: int = 0, x0: int = 0) -> jnp.ndarray:
    """Uniform uint32 words for a (H, W) grid of counters.

    ``t`` may be a traced scalar (step index).  ``y0/x0`` offset the counters
    so that distributed shards draw from disjoint streams that exactly match
    the single-device stream (shard-invariance).
    """
    h, w = shape
    ys = (jnp.arange(h, dtype=jnp.uint32) + np.uint32(y0))[:, None]
    xs = (jnp.arange(w, dtype=jnp.uint32) + np.uint32(x0))[None, :]
    ctr = ys * np.uint32(0x01000193) + xs
    salted = np.uint32((salt * int(_M2)) & 0xFFFFFFFF)
    tt = jnp.asarray(t, dtype=jnp.uint32) * _GOLD + salted
    return hash_u32(ctr ^ tt)


# Salt of the k-th random word a collision draws per site-step: 0x11 is
# the chirality word (FHP-II's only one, FHP-III's c0), 0x33 FHP-III's c1.
# The forcing comparator uses 0x2200 + i (``bernoulli_words``).
RANDOM_SALTS = (0x11, 0x33)


def random_bits(shape, t, n: int, y0: int = 0, x0: int = 0) -> jnp.ndarray:
    """``n`` random bits per node packed as ``r = sum(bit_k << k)``, uint8
    in [0, 2**n): bit k from the k-th of ``RANDOM_SALTS`` (byte path)."""
    r = jnp.zeros(shape, jnp.uint8)
    for k, salt in enumerate(RANDOM_SALTS[:n]):
        bit = counter_u32(shape, t, salt=salt, y0=y0, x0=x0) >> 31
        r = r | (bit.astype(jnp.uint8) << k)
    return r


def bernoulli(shape, t, p: float, salt: int = 0x22, y0: int = 0, x0: int = 0):
    """Per-node Bernoulli(p) mask as bool."""
    thresh = np.uint32(min(max(p, 0.0), 1.0) * 4294967295.0)
    return counter_u32(shape, t, salt=salt, y0=y0, x0=x0) < thresh


# ---------------------------------------------------------------------------
# Word-level (bit-plane) random sources.
#
# In the bit-plane representation one uint32 word holds 32 lattice nodes, so
# the natural "SIMD random" primitive is a whole word of independent random
# bits from a single hash -- the paper's 32-nodes-per-AVX-register idea
# applied to the RNG itself.  One hash yields 32 chirality coins, versus 32
# per-node hashes in the naive scheme.
# ---------------------------------------------------------------------------

BERNOULLI_BITS = 16  # Bernoulli(p) resolution: p is quantised to 1/65536.


def word_u32(shape_words, t, salt: int, y0: int = 0, xw0: int = 0) -> jnp.ndarray:
    """One uint32 of 32 independent random bits per (row, word) counter.

    ``shape_words`` is the packed shape (H, W//32); ``xw0`` offsets the word
    counter (global word index of the first local word) so distributed shards
    reproduce the single-device stream exactly.
    """
    h, wd = shape_words
    ys = (jnp.arange(h, dtype=jnp.uint32) + jnp.asarray(y0, jnp.uint32))[:, None]
    xs = (jnp.arange(wd, dtype=jnp.uint32) + jnp.asarray(xw0, jnp.uint32))[None, :]
    return word_u32_at(ys, xs, t, salt)


def word_u32_at(rows: jnp.ndarray, cols: jnp.ndarray, t, salt: int) -> jnp.ndarray:
    """Random words for explicit (row, word) coordinate arrays.

    ``rows``/``cols`` broadcast against each other; the distributed stepper
    passes mod-H / mod-Wd global coordinates so halo regions reproduce the
    owning shard's stream exactly.
    """
    ctr = rows.astype(jnp.uint32) * np.uint32(0x01000193) + cols.astype(jnp.uint32)
    salted = np.uint32((salt * int(_M2)) & 0xFFFFFFFF)
    tt = jnp.asarray(t, dtype=jnp.uint32) * _GOLD + salted
    return hash_u32(ctr ^ tt)


def quantize_p(p: float) -> int:
    """Round p to the BERNOULLI_BITS grid; returns the integer threshold."""
    return int(round(min(max(p, 0.0), 1.0) * (1 << BERNOULLI_BITS)))


def bernoulli_words(shape_words, t, p: float, salt: int = 0x22,
                    y0: int = 0, xw0: int = 0) -> jnp.ndarray:
    """Per-bit Bernoulli(p) over packed uint32 words (bit-serial comparator).

    Emits, for every one of the 32 bit lanes of every word, an independent
    Bernoulli(round(p * 2^16)/2^16) bit.  Implemented as an MSB-first
    comparison R < P between a random bit stream R (one random plane per
    round) and the fixed binary expansion of P, using only AND/OR/NOT --
    the VPU-native way to draw 32 biased coins per word.  Rounds after the
    last set bit of P cannot change the result and are skipped, so p = 0.5
    costs a single hash per word.
    """
    h, wd = shape_words
    ys = (jnp.arange(h, dtype=jnp.uint32) + jnp.asarray(y0, jnp.uint32))[:, None]
    xs = (jnp.arange(wd, dtype=jnp.uint32) + jnp.asarray(xw0, jnp.uint32))[None, :]
    return bernoulli_words_at(ys, xs, t, p, salt=salt)


def bernoulli_words_at(rows, cols, t, p: float, salt: int = 0x22) -> jnp.ndarray:
    """``bernoulli_words`` for explicit (broadcastable) coordinate arrays."""
    shape = jnp.broadcast_shapes(rows.shape, cols.shape)
    pq = quantize_p(p)
    if pq <= 0:
        return jnp.zeros(shape, dtype=jnp.uint32)
    if pq >= (1 << BERNOULLI_BITS):
        return jnp.full(shape, 0xFFFFFFFF, dtype=jnp.uint32)
    res = jnp.zeros(shape, dtype=jnp.uint32)
    eq = jnp.full(shape, 0xFFFFFFFF, dtype=jnp.uint32)
    # Position of the last set bit of P (LSB side) -- rounds below it are moot.
    last = (pq & -pq).bit_length() - 1
    for i in range(BERNOULLI_BITS - 1, last - 1, -1):
        r = word_u32_at(rows, cols, t, salt=salt * 0x100 + i)
        if (pq >> i) & 1:
            res = res | (eq & ~r)
            eq = eq & r
        else:
            eq = eq & ~r
    return res


def chirality_words(shape_words, t, y0: int = 0, xw0: int = 0) -> jnp.ndarray:
    """One random chirality bit per node, packed 32 nodes per uint32 word."""
    return word_u32(shape_words, t, salt=0x11, y0=y0, xw0=xw0)


def random_words(shape_words, t, n: int, y0: int = 0, xw0: int = 0):
    """The ``n`` random words a collision draws per site-step (a tuple;
    word k under the k-th of ``RANDOM_SALTS``)."""
    return tuple(word_u32(shape_words, t, salt=s, y0=y0, xw0=xw0)
                 for s in RANDOM_SALTS[:n])


def random_words_at(rows, cols, t, n: int):
    """``random_words`` for explicit (broadcastable) coordinate arrays."""
    return tuple(word_u32_at(rows, cols, t, salt=s) for s in RANDOM_SALTS[:n])
