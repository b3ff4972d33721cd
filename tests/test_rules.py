"""FHP rule tables: exhaustive conservation + hypothesis properties,
plus the registry-wide audits (every rule in ``core.rulespec``)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import rules


def test_lut_shape_and_determinism():
    lut = rules.build_lut()
    assert lut.shape == (2, 256)
    assert lut.dtype == np.uint8
    assert np.array_equal(lut, rules.build_lut())


@pytest.mark.parametrize("chi", [0, 1])
def test_fluid_conservation_exhaustive(chi):
    lut = rules.build_lut()
    for s in range(128):  # fluid states (bit 7 clear)
        o = int(lut[chi, s])
        assert not (o & rules.SOLID_MASK)
        assert rules.mass_of(o) == rules.mass_of(s), (s, o)
        assert rules.momentum_of(o) == rules.momentum_of(s), (s, o)


@pytest.mark.parametrize("chi", [0, 1])
def test_solid_bounce_back_exhaustive(chi):
    lut = rules.build_lut()
    for s in range(128, 256):
        o = int(lut[chi, s])
        assert o & rules.SOLID_MASK
        px, py = rules.momentum_of(s)
        assert rules.momentum_of(o) == (-px, -py), (s, o)
        assert rules.mass_of(o & 0x7F) == rules.mass_of(s & 0x7F)
        # bounce-back is an involution: two applications restore the state
        assert int(lut[chi, o]) == s


def test_collisions_change_state_for_head_on():
    """The table must actually scatter: head-on pairs rotate."""
    lut = rules.build_lut()
    for i in range(3):
        s = (1 << i) | (1 << rules.opposite(i))
        assert int(lut[0, s]) != s
        assert int(lut[1, s]) != s
        assert int(lut[0, s]) != int(lut[1, s])  # chirality matters


def test_three_body_symmetric():
    lut = rules.build_lut()
    s = 0b010101
    assert int(lut[0, s]) == 0b101010
    assert int(lut[0, 0b101010]) == 0b010101


def test_rest_exchange_mass_two():
    lut = rules.build_lut()
    for i in range(6):
        s = (1 << i) | rules.REST_MASK
        o = int(lut[0, s])
        assert o != s
        assert rules.mass_of(o) == 2
        assert not (o & rules.REST_MASK)


@settings(max_examples=200, deadline=None)
@given(st.integers(0, 255), st.integers(0, 1))
def test_conservation_property(s, chi):
    lut = rules.build_lut()
    o = int(lut[chi, s])
    assert rules.mass_of(o & 0x7F) == rules.mass_of(s & 0x7F)
    if s & rules.SOLID_MASK:
        px, py = rules.momentum_of(s)
        assert rules.momentum_of(o) == (-px, -py)
    else:
        assert rules.momentum_of(o) == rules.momentum_of(s)


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 255))
def test_lut_flat_consistency(s):
    flat = rules.lut_flat()
    lut = rules.build_lut()
    assert flat[s] == lut[0, s]
    assert flat[256 + s] == lut[1, s]


# ---------------------------------------------------------------------------
# Registry-wide audits: every rule in ``core.rulespec``.
# ---------------------------------------------------------------------------

@settings(max_examples=200, deadline=None)
@given(st.integers(0, 255))
def test_bounce_back_involution(s):
    """``bounce_back`` reverses every moving particle (i -> i+3), leaves
    rest/solid bits alone, and is its own inverse."""
    o = rules.bounce_back(s)
    assert rules.bounce_back(o) == s
    assert (o & ~rules.MOVING_MASK) == (s & ~rules.MOVING_MASK)
    px, py = rules.momentum_of(s)
    assert rules.momentum_of(o) == (-px, -py)


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 255), st.integers(0, 3))
def test_fhp3_conservation_property(s, chi):
    """FHP-III's richer table honours the same conservation laws as
    FHP-II, for every ``r = c0 + 2 * c1`` (the exhaustive tests above
    pin the fhp2 default)."""
    lut = rules.build_lut("fhp3")
    o = int(lut[chi, s])
    assert rules.mass_of(o & 0x7F) == rules.mass_of(s & 0x7F)
    if s & rules.SOLID_MASK:
        px, py = rules.momentum_of(s)
        assert rules.momentum_of(o) == (-px, -py)
    else:
        assert rules.momentum_of(o) == rules.momentum_of(s)


def test_boolean_circuit_matches_lut_all_states():
    """The generated boolean circuit == the LUT on all (state, random
    bits) combos -- 512 for FHP-II, 1024 for FHP-III -- the contract that
    lets the Pallas kernel run pure vector algebra in place of the byte
    gather."""
    import jax.numpy as jnp

    from repro.core import bitplane, boolean
    # 1024 cells: row-major (r, s) on a (32, 32) lattice, one word/row
    s_all = np.arange(1024, dtype=np.uint16).reshape(32, 32)
    state = (s_all & 0xFF).astype(np.uint8)
    planes = bitplane.pack(jnp.asarray(state))
    bit = [bitplane.pack_bits_from_bytes(
        jnp.asarray(((s_all >> (8 + k)) & 1).astype(np.uint8)))
        for k in range(2)]
    for variant in ("fhp2", "fhp3"):
        lut = rules.build_lut(variant)
        r = (s_all >> 8) % lut.shape[0]
        chi = bit[0] if variant == "fhp2" else tuple(bit)
        out = boolean.collide_planes(
            [planes[k] for k in range(8)], chi, variant)
        got = np.asarray(bitplane.unpack(jnp.stack(out)))
        want = lut[r.astype(np.int64), state.astype(np.int64)]
        assert (got == want).all(), variant


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_registry_rules_conserve_claimed_mass(seed):
    """Every registered rule's collision circuit conserves its claimed
    mass planes pointwise on random 8-bit states (one stepper step on a
    tiny torus; streaming is a permutation, so any leak is the circuit's)."""
    import jax.numpy as jnp

    from repro.core import bitplane, rulespec
    rng = np.random.default_rng(seed)
    for name in rulespec.rule_names():
        spec = rulespec.get_rule(name)
        state = (rng.integers(0, 256, (4, 32), dtype=np.uint8)
                 & spec.byte_mask())
        planes = bitplane.pack(jnp.asarray(state), n_planes=spec.n_planes)
        out = rulespec.step_planes_rule(planes, int(seed) % 4, spec)

        def mass(p):
            import jax
            return sum(int(jax.lax.population_count(
                p[..., i, :, :]).sum()) for i in spec.mass_planes)

        if spec.conserves_mass:
            assert mass(out) == mass(planes), name
