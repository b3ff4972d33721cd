"""Representation equivalence: byte/LUT path == boolean path == bit-plane
path, for streaming, collision and the fused step (shared RNG)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitplane, boolean, byte_step, prng, rules


def random_state(h, w, seed, density=0.35, walls=True):
    s = byte_step.make_channel(h, w, density=density, seed=seed)
    if not walls:  # pure fluid, no solid nodes anywhere
        rng = np.random.default_rng(seed + 1)
        occ = (rng.random((7, h, w)) < density).astype(np.uint8)
        s = np.zeros((h, w), np.uint8)
        for i in range(7):
            s |= occ[i] << i
    return jnp.asarray(s)


def words_to_bits(w):
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return ((w[..., None] >> shifts) & 1).reshape(w.shape[0], -1)


def test_pack_unpack_roundtrip():
    s = random_state(8, 64, 0, walls=False)
    assert bool((bitplane.unpack(bitplane.pack(s)) == s).all())


@pytest.mark.parametrize("h,w", [(8, 32), (16, 64), (10, 96)])
def test_stream_equivalence(h, w):
    s = random_state(h, w, seed=h * w, walls=False)
    p = bitplane.pack(s)
    out_b = byte_step.stream_bytes(s)
    out_p = bitplane.unpack(bitplane.stream_planes(p))
    assert bool((out_b == out_p).all())


def test_collide_lut_vs_boolean_exhaustive():
    """All 256 states x both chiralities: LUT == boolean algebra."""
    lut = rules.build_lut()
    states = jnp.arange(256, dtype=jnp.int32)[None, :].astype(jnp.uint8)
    for chi_val in (0, 1):
        chi = jnp.full(states.shape, chi_val, jnp.uint8)
        out_lut = byte_step.collide_bytes(states, chi)
        planes = [((states >> i) & 1) for i in range(8)]
        outp = boolean.collide_planes(planes, chi)
        out_bool = sum(
            (outp[i].astype(jnp.uint8) << i) for i in range(8))
        assert bool((out_lut == out_bool).all()), chi_val


@pytest.mark.parametrize("p_force", [0.0, 0.1, 0.5])
def test_full_step_equivalence(p_force):
    h, w = 16, 64
    s = random_state(h, w, seed=3)
    p = bitplane.pack(s)
    chi_w = prng.chirality_words((h, 2), t=7)
    acc_w = prng.bernoulli_words((h, 2), t=7, p=p_force)
    chi_b = words_to_bits(chi_w).astype(jnp.uint8)
    acc_b = words_to_bits(acc_w).astype(bool)
    out_b = byte_step.step_bytes(s, 7, chi=chi_b, accel=acc_b)
    out_p = bitplane.step_planes(p, 7, chi=chi_w, accel=acc_w)
    assert bool((bitplane.unpack(out_p) == out_b).all())


def test_multi_step_mass_conserved():
    s = random_state(16, 64, seed=4)
    p = bitplane.pack(s)
    m0 = int(bitplane.density_total(p))
    p2 = bitplane.run_planes(p, 20, p_force=0.05)
    assert int(bitplane.density_total(p2)) == m0


def test_momentum_conserved_without_force_or_walls():
    s = random_state(16, 64, seed=5, walls=False)
    p = bitplane.pack(s)
    px0, py0 = (int(v) for v in bitplane.momentum_total(p))
    p2 = bitplane.run_planes(p, 20, p_force=0.0)
    px1, py1 = (int(v) for v in bitplane.momentum_total(p2))
    assert (px0, py0) == (px1, py1)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 3))
def test_step_equivalence_property(seed, t):
    h, w = 8, 32
    s = random_state(h, w, seed=seed % 1000, walls=bool(seed & 1))
    p = bitplane.pack(s)
    chi_w = prng.chirality_words((h, 1), t=t)
    chi_b = words_to_bits(chi_w).astype(jnp.uint8)
    out_b = byte_step.step_bytes(s, t, chi=chi_b)
    out_p = bitplane.step_planes(p, t, chi=chi_w)
    assert bool((bitplane.unpack(out_p) == out_b).all())
