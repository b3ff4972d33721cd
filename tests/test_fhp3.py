"""FHP-III, collision-saturated: conservation audit, LUT == boolean
algebra for every pair of random bits, and the mass-3 quintet's
conversions between moving and rest particles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boolean, rules

T0 = 0b010101                       # the symmetric triple {0, 2, 4}
T1 = 0b101010                       # the symmetric triple {1, 3, 5}


def pair_rest(i):
    return (1 << i) | (1 << (i + 3)) | rules.REST_MASK


# The zero-momentum class of mass 3: both triples and the three head-on
# pairs with a rest particle, in ascending order of state value.
QUINTET = sorted([T0, T1] + [pair_rest(i) for i in range(3)])


def outputs(lut, s):
    """The outputs of state ``s`` for r = c0 + 2 * c1 = 0..3."""
    return [int(lut[r, s]) for r in range(4)]


def test_fhp3_lut_builds_and_conserves():
    lut = rules.build_lut("fhp3")  # conservation audited inside
    assert lut.shape == (4, 256)
    assert not np.array_equal(lut[:2], rules.build_lut("fhp2"))


@pytest.mark.parametrize("i", range(3))
def test_fhp3_pair_rest_fusion(i):
    """A head-on pair with a rest particle goes, over the four (c0, c1),
    to each other member of its quintet once: both symmetric triples
    (rest cleared) and the two other pairs with a rest particle."""
    lut = rules.build_lut("fhp3")
    s = pair_rest(i)
    others = [o for o in QUINTET if o != s]
    assert outputs(lut, s) == others
    assert {T0, T1} <= set(outputs(lut, s))
    for o in outputs(lut, s):
        assert rules.mass_of(o) == rules.mass_of(s) == 3
        assert rules.momentum_of(o) == (0, 0)


@pytest.mark.parametrize("tri", [T0, T1])
def test_fhp3_triple_fission(tri):
    """A symmetric triple goes to the other triple or splits into one of
    the three head-on pairs with a rest particle, one member per (c0,
    c1); under FHP-II it only ever rotates."""
    lut = rules.build_lut("fhp3")
    assert outputs(lut, tri) == [o for o in QUINTET if o != tri]
    assert sum(bool(o & rules.REST_MASK) for o in outputs(lut, tri)) == 3
    lut2 = rules.build_lut("fhp2")
    assert not any(int(lut2[c, tri]) & rules.REST_MASK for c in range(2))


@pytest.mark.parametrize("r", range(4))
def test_fhp3_lut_equals_boolean(r):
    """The generated circuit == row ``r = c0 + 2 * c1`` of the LUT on all
    256 states."""
    lut = rules.build_lut("fhp3")
    states = jnp.arange(256, dtype=jnp.int32)[None, :].astype(jnp.uint8)
    c0 = jnp.full(states.shape, r & 1, jnp.uint8)
    c1 = jnp.full(states.shape, r >> 1, jnp.uint8)
    planes = [((states >> i) & 1) for i in range(8)]
    outp = boolean.collide_planes(planes, (c0, c1), variant="fhp3")
    out_bool = sum((outp[i].astype(jnp.uint8) << i) for i in range(8))
    assert np.array_equal(np.asarray(out_bool)[0], lut[r])


def test_fhp3_adds_rest_conversion_channels():
    """FHP-III's distinction: collisions that convert between moving and
    rest particles within the mass-3 class (pair+rest <-> triple).  Count
    transitions where the rest bit flips for 2- and 3-mover states."""
    def conversions(variant):
        lut = rules.build_lut(variant)
        n = 0
        for c in range(lut.shape[0]):
            for s in range(128):
                movers = bin(s & 0x3F).count("1")
                rest = bool(s & rules.REST_MASK)
                mass3 = (movers == 2 and rest) or (movers == 3 and not rest)
                if mass3 and (int(lut[c, s]) ^ s) & rules.REST_MASK:
                    n += 1
        return n
    assert conversions("fhp2") == 0
    assert conversions("fhp3") > 0


def test_fhp3_full_step_equivalence_across_paths():
    """byte/LUT == bit-plane boolean == Pallas kernel under fhp3."""
    from repro.core import bitplane, byte_step, prng
    from repro.kernels.fhp_step.ops import fhp_step_pallas

    h, w = 16, 64
    s = jnp.asarray(byte_step.make_channel(h, w, density=0.35, seed=9))
    p = bitplane.pack(s)
    c0, c1 = prng.random_words((h, w // 32), 3, 2)
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def bytes_of(word):
        return ((word[..., None] >> shifts) & 1).reshape(h, w).astype(
            jnp.uint8)

    r_b = bytes_of(c0) | (bytes_of(c1) << 1)
    out_byte = byte_step.step_bytes(s, 3, chi=r_b, variant="fhp3")
    out_plane = bitplane.step_planes(p, 3, chi=(c0, c1), variant="fhp3")
    out_kernel = fhp_step_pallas(p, 3, variant="fhp3")

    assert bool((bitplane.unpack(out_plane) == out_byte).all())
    assert bool((out_kernel == out_plane).all())
    # the counter-based defaults draw the same words
    assert bool((bitplane.step_planes(p, 3, variant="fhp3")
                 == out_plane).all())
    # and fhp3 dynamics genuinely differ from fhp2
    out2 = bitplane.step_planes(p, 3, chi=c0, variant="fhp2")
    assert not bool((out_plane == out2).all())
