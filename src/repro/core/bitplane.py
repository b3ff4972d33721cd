"""Multi-spin-coded (bit-plane) FHP stepper: 32 nodes per uint32 word.

This is the beyond-paper optimized path.  The byte representation moves one
byte per node per pass; packing each of the 8 state bits into its own plane
of uint32 words moves 8 bits/node/step *and* turns the collision LUT gather
into pure vector boolean algebra (see ``boolean.py``).  On the TPU VPU one
(8, 128) vector register then carries 8 * 128 * 32 = 32768 lattice nodes of
one plane -- the paper's AVX insight (32 nodes/register) scaled to the TPU
register file.

Layout: ``planes`` is ``(8, H, W // 32)`` uint32; bit ``b`` of word ``w`` in
row ``y`` is node ``(y, 32 * w + b)`` (little-endian bit order along x).
Plane order matches the byte bits: 0..5 moving, 6 rest, 7 solid.

Every stepper and observable also accepts leading batch axes
(``(B, 8, H, W // 32)`` ensemble lanes): the update is per-lane, and the
RNG counters do not include the lane index, so each lane is bit-identical
to the unbatched reference at the same ``(t, y0, xw0)`` (common random
numbers across the ensemble).
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boolean, prng, rules

WORD = 32
_U32 = jnp.uint32


def pack(state: jnp.ndarray, n_planes: int = 8) -> jnp.ndarray:
    """(..., H, W) uint8 bytes -> (..., n_planes, H, W//32) uint32 planes.
    W % 32 == 0; leading axes are ensemble lanes.  ``n_planes`` is the
    rule's plane count (8 for FHP, 2 for BML; see ``core.rulespec``)."""
    *lead, h, w = state.shape
    assert w % WORD == 0, f"W={w} must be a multiple of {WORD}"
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=_U32))
    planes = []
    for i in range(n_planes):
        bits = ((state >> i) & 1).astype(_U32).reshape(
            *lead, h, w // WORD, WORD)
        planes.append((bits * weights).sum(axis=-1, dtype=_U32))
    return jnp.stack(planes, axis=-3)


def unpack(planes: jnp.ndarray) -> jnp.ndarray:
    """(..., n_planes, H, W//32) uint32 planes -> (..., H, W) uint8 bytes."""
    *lead, np_, h, wd = planes.shape
    shifts = jnp.arange(WORD, dtype=_U32)
    state = jnp.zeros((*lead, h, wd * WORD), dtype=jnp.uint8)
    for i in range(np_):
        bits = ((planes[..., i, :, :, None] >> shifts) & 1).astype(jnp.uint8)
        state = state | (bits.reshape(*lead, h, wd * WORD) << i)
    return state


def shift_x(p: jnp.ndarray, dx: int) -> jnp.ndarray:
    """Shift a packed plane by dx nodes along x (periodic), dx in {-1, 0, 1}.

    Cross-word carry: the bit leaving one word enters the next, exactly the
    paper's inter-register boundary handled with an extra load -- here a
    word-rotate plus shift/or, all VPU ops.
    """
    if dx == 0:
        return p
    if dx == 1:
        return (p << 1) | (jnp.roll(p, 1, axis=-1) >> (WORD - 1))
    if dx == -1:
        return (p >> 1) | (jnp.roll(p, -1, axis=-1) << (WORD - 1))
    raise ValueError(dx)


def stream_planes(planes: jnp.ndarray, row0=0) -> jnp.ndarray:
    """Motion step on packed planes (periodic both axes; walls via collide).

    ``row0`` is the global row index of local row 0 (may be traced): the
    triangular-lattice x-offsets depend on the *global* row parity, so a
    shard of a larger lattice must pass its offset.
    """
    h = planes.shape[-2]
    parity = ((jnp.arange(h, dtype=_U32)
               + jnp.asarray(row0, _U32)) & 1)[:, None]  # (H, 1) source parity
    even = parity == 0
    out = [None] * 8
    for k in range(rules.N_DIR):
        p = planes[..., k, :, :]
        (dx0, dy), (dx1, _) = rules.OFFSETS[k]
        if dx0 == dx1:
            moved = shift_x(p, dx0)
        else:
            moved = jnp.where(even, shift_x(p, dx0), shift_x(p, dx1))
        out[k] = jnp.roll(moved, dy, axis=-2) if dy else moved
    out[rules.REST_BIT] = planes[..., rules.REST_BIT, :, :]
    out[rules.SOLID_BIT] = planes[..., rules.SOLID_BIT, :, :]
    return jnp.stack(out, axis=-3)


def _as_plane_list(planes: jnp.ndarray) -> List[jnp.ndarray]:
    """Split the plane axis (-3) into a list, preserving batch axes."""
    return [planes[..., k, :, :] for k in range(8)]


def collide(planes: jnp.ndarray, chi,
            variant: str = "fhp2") -> jnp.ndarray:
    return jnp.stack(boolean.collide_planes(_as_plane_list(planes), chi,
                                            variant), axis=-3)


def step_planes(planes: jnp.ndarray, t, p_force: float = 0.0,
                y0: int = 0, xw0: int = 0, *, chi=None, accel=None,
                variant: str = "fhp2") -> jnp.ndarray:
    """One fused FHP step (stream -> collide -> force) on packed planes.

    ``y0``/``xw0`` are the global coordinates of local element (0, 0); they
    offset both the RNG counters and the row parity, so a shard reproduces
    the global lattice bit-for-bit.  ``chi``/``accel`` override the
    counter-based RNG (used by equivalence tests to drive byte and
    bit-plane paths with identical randomness); ``chi`` is the chirality
    word for FHP-II, the pair ``(c0, c1)`` for FHP-III.
    """
    shape_words = planes.shape[-2:]
    s = stream_planes(planes, row0=y0)
    if chi is None:
        words = prng.random_words(shape_words, t,
                                  rules.RANDOM_WORDS[variant],
                                  y0=y0, xw0=xw0)
        chi = words[0] if len(words) == 1 else words
    s = collide(s, chi, variant)
    if p_force or accel is not None:
        if accel is None:
            accel = prng.bernoulli_words(shape_words, t, p_force, y0=y0, xw0=xw0)
        s = jnp.stack(boolean.force_planes(_as_plane_list(s), accel), axis=-3)
    return s


def run_planes(planes: jnp.ndarray, steps: int, p_force: float = 0.0,
               t0=0) -> jnp.ndarray:
    def body(i, s):
        return step_planes(s, t0 + i, p_force)
    return jax.lax.fori_loop(0, steps, body, planes)


# ---------------------------------------------------------------------------
# Observables on packed planes (popcount reductions, no unpacking)
# ---------------------------------------------------------------------------

def density_total(planes: jnp.ndarray) -> jnp.ndarray:
    """Total particle count (moving + rest); per-lane for batched planes."""
    dt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    n = jnp.zeros(planes.shape[:-3], dt)
    for i in range(7):
        n = n + jax.lax.population_count(
            planes[..., i, :, :]).sum(axis=(-2, -1), dtype=dt)
    return n


def momentum_total(planes: jnp.ndarray):
    """(sum px2, sum py) over the lattice; per-lane for batched planes."""
    px2 = jnp.zeros(planes.shape[:-3], jnp.int32)
    py = jnp.zeros(planes.shape[:-3], jnp.int32)
    for i in range(rules.N_DIR):
        c = jax.lax.population_count(
            planes[..., i, :, :]).sum(axis=(-2, -1), dtype=jnp.int32)
        px2 = px2 + c * int(rules.CX2[i])
        py = py + c * int(rules.CY[i])
    return px2, py


def row_velocity(planes: jnp.ndarray) -> jnp.ndarray:
    """Mean x-velocity per row (for Poiseuille profiles), float32."""
    px2 = jnp.zeros(planes.shape[:-3] + planes.shape[-2:], jnp.int32)
    n = jnp.zeros(planes.shape[:-3] + planes.shape[-2:], jnp.int32)
    for i in range(rules.N_DIR):
        c = jax.lax.population_count(planes[..., i, :, :]).astype(jnp.int32)
        px2 = px2 + c * int(rules.CX2[i])
        n = n + c
    n = n + jax.lax.population_count(
        planes[..., rules.REST_BIT, :, :]).astype(jnp.int32)
    mp = jnp.sum(px2, axis=-1).astype(jnp.float32) / 2.0
    mn = jnp.maximum(jnp.sum(n, axis=-1).astype(jnp.float32), 1e-9)
    return mp / mn


def pack_bits_from_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """Pack a (H, W) {0,1} uint8 mask into (H, W//32) uint32 words."""
    h, w = x.shape
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=_U32))
    return (x.astype(_U32).reshape(h, w // WORD, WORD) * weights).sum(
        axis=-1, dtype=_U32)
