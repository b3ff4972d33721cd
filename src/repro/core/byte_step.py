"""Paper-faithful byte-per-node FHP stepper (the AVX/SSE reference path).

One lattice node = one uint8 (paper Fig. 1).  The update is

    stream (motion)  ->  collide (LUT scattering, incl. bounce-back)  ->  force

exactly as in the paper Sec. 2.  Arrays are ``(H, W)`` uint8 with row index
``y`` increasing northward; the triangular lattice is mapped onto the
rectangular array with odd rows shifted east by half a lattice constant
(paper Fig. 3), so neighbour x-offsets depend on the *source* row parity
(see ``rules.OFFSETS``).

Boundary conditions: both axes wrap (``jnp.roll``); no-slip walls are solid
rows/cells (bit 7) whose LUT entry is full bounce-back, so with solid rows at
y = 0 and y = H-1 the wrap in y is never exercised by physical particles --
this replaces the paper's explicit ghost columns (Fig. 4) with the
XLA-native rotate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prng, rules

BIT = [np.uint8(1 << i) for i in range(8)]
_FORCE_XOR = np.uint8((1 << 0) | (1 << 3))  # swap W-mover into E-mover


def lut_array(variant: str = "fhp2") -> jnp.ndarray:
    """The flat uint8 collision LUT, index = r << 8 | state, with ``r``
    the site's random bits (``rules.build_lut``)."""
    return jnp.asarray(rules.lut_flat(variant))


def stream_bytes(state: jnp.ndarray, row0=0) -> jnp.ndarray:
    """Motion step: every moving particle hops to its neighbour node.

    Rest (bit 6) and solid (bit 7) bits stay in place.  Equivalent to the
    paper's Listing 1 (AND-mask, shift to neighbour, OR into destination)
    with jnp.roll playing the role of the neighbour index arithmetic.
    """
    h = state.shape[-2]
    parity = ((jnp.arange(h, dtype=jnp.uint8)
               + jnp.asarray(row0, jnp.uint8)) & 1)[:, None]  # (H, 1) source row parity
    out = state & (rules.REST_MASK | rules.SOLID_MASK)
    for k in range(rules.N_DIR):
        plane = state & BIT[k]
        for p in (0, 1):
            dx, dy = rules.OFFSETS[k][p]
            src = jnp.where(parity == p, plane, jnp.uint8(0))
            out = out | jnp.roll(src, shift=(dy, dx), axis=(-2, -1))
    return out


def collide_bytes(state: jnp.ndarray, chi: jnp.ndarray,
                  variant: str = "fhp2") -> jnp.ndarray:
    """Scattering step via the LUT; ``chi`` is the per-node random index
    ``r`` (FHP-II: the chirality bit; FHP-III: ``c0 + 2 * c1``)."""
    idx = chi.astype(jnp.int32) * 256 + state.astype(jnp.int32)
    return jnp.take(lut_array(variant), idx, axis=0)


def force_bytes(state: jnp.ndarray, accel: jnp.ndarray) -> jnp.ndarray:
    """Body force: where ``accel`` and the node holds a W-mover but no E-mover
    (and is fluid), reverse it (paper's pattern (..1..0..) -> (..0..1..))."""
    can = ((state & BIT[3]) != 0) & ((state & BIT[0]) == 0) & ((state & BIT[7]) == 0)
    return jnp.where(can & accel, state ^ _FORCE_XOR, state)


def step_bytes(state: jnp.ndarray, t, p_force: float = 0.0,
               y0: int = 0, x0: int = 0, *, chi=None, accel=None,
               variant: str = "fhp2") -> jnp.ndarray:
    """One full FHP time step on the byte representation.

    ``t`` may be traced (step counter).  ``y0/x0`` offset the counter-based
    RNG so a shard of a larger lattice reproduces the global stream.
    ``chi``/``accel`` override the RNG (equivalence tests); ``chi`` is the
    per-node random index of ``collide_bytes``.
    """
    shape = state.shape
    s = stream_bytes(state, row0=y0)
    if chi is None:
        chi = prng.random_bits(shape, t, rules.RANDOM_WORDS[variant],
                               y0=y0, x0=x0)
    s = collide_bytes(s, chi, variant)
    if p_force or accel is not None:
        if accel is None:
            accel = prng.bernoulli(shape, t, p_force, y0=y0, x0=x0)
        s = force_bytes(s, accel)
    return s


def run_bytes(state: jnp.ndarray, steps: int, p_force: float = 0.0,
              t0=0) -> jnp.ndarray:
    """Advance ``steps`` time steps with ``lax.fori_loop`` (donable carry)."""
    def body(i, s):
        return step_bytes(s, t0 + i, p_force)
    return jax.lax.fori_loop(0, steps, body, state)


# ---------------------------------------------------------------------------
# Initialisation and observables
# ---------------------------------------------------------------------------

def make_channel(h: int, w: int, density: float = 0.2, seed: int = 0,
                 obstacle=None) -> np.ndarray:
    """A channel: solid rows top/bottom, random fluid at given per-bit density.

    ``obstacle`` is an optional (H, W) bool mask of extra solid nodes.
    Returns a host numpy array (uint8); callers shard/transfer it.
    """
    rng = np.random.default_rng(seed)
    occ = (rng.random((7, h, w)) < density).astype(np.uint8)
    state = np.zeros((h, w), dtype=np.uint8)
    for i in range(7):
        state |= occ[i] << i
    solid = np.zeros((h, w), dtype=bool)
    solid[0, :] = True
    solid[-1, :] = True
    if obstacle is not None:
        solid |= obstacle
    state = np.where(solid, np.uint8(rules.SOLID_MASK), state)
    return state


def density(state: jnp.ndarray) -> jnp.ndarray:
    """Particles per node (0..7)."""
    n = jnp.zeros(state.shape, jnp.int32)
    for i in range(7):
        n = n + ((state >> i) & 1).astype(jnp.int32)
    return n


def momentum(state: jnp.ndarray):
    """(px2, py) integer momentum fields; px2 is doubled x-momentum."""
    px2 = jnp.zeros(state.shape, jnp.int32)
    py = jnp.zeros(state.shape, jnp.int32)
    for i in range(rules.N_DIR):
        b = ((state >> i) & 1).astype(jnp.int32)
        px2 = px2 + b * int(rules.CX2[i])
        py = py + b * int(rules.CY[i])
    return px2, py


def velocity_profile(state: jnp.ndarray) -> jnp.ndarray:
    """Mean x-velocity per row: <px>/<mass> with px = px2/2 (fluid rows)."""
    px2, _ = momentum(state)
    n = density(state)
    mean_p = jnp.mean(px2.astype(jnp.float32), axis=-1) / 2.0
    mean_n = jnp.maximum(jnp.mean(n.astype(jnp.float32), axis=-1), 1e-9)
    return mean_p / mean_n
