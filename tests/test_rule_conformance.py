"""Cross-rule conformance harness: every registered rule, one contract.

``core.rulespec`` promises that the blocked Pallas substrate (temporal
T, 2-D (block_rows, block_words) tiles, word-halo aprons, global-mod
RNG) runs *any* registered rule bit-exactly.  This harness audits that
promise per rule, fully rule-parametrically:

* invariant audits on random states (property-based): mass conservation
  where claimed, momentum conservation where claimed (solid-free
  states), per-plane conservation (BML: cars never change species or
  vanish), determinism for RNG-free rules;
* bit-exactness of the blocked Pallas path against the rule's *byte
  oracle* (``RuleSpec.oracle_step`` driven by the word-RNG stream via
  ``rulespec.oracle_run``) swept over temporal depth T x block_words x
  {periodic, extended-shard} x {unbatched, batched ensemble lanes}.

A new rule registers once in ``core.rulespec`` and is conformance-gated
here with zero new test code.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import bitplane, rulespec
from repro.kernels.fhp_step.ops import run_extended, run_pallas

pytestmark = pytest.mark.rules

H, W = 8, 128          # Wd = 4 packed words; tiny: every case compiles
BH = 4                 # block_rows (T <= BH for every swept T)

# (T, block_words): covers T in {1, 2, 4} and bw in {1, 2} within the
# kernel's T <= bw constraint for x-blocked tiles (bw=0 = full width).
SWEEP = [(1, 1), (1, 2), (2, 2), (4, 0)]


def init(spec, seed=0, h=H, w=W, density=0.3):
    state = spec.init_bytes(h, w, density, seed)
    planes = bitplane.pack(jnp.asarray(state), n_planes=spec.n_planes)
    return state, planes


def popcounts(planes, plane_ids):
    return [int(jax.lax.population_count(planes[..., i, :, :]).sum())
            for i in plane_ids]


def periodic_ext(p, d):
    """Manually halo-extend a periodic lattice by d rows / 1 word."""
    ext = jnp.concatenate([p[..., -1:], p, p[..., :1]], axis=-1)
    return jnp.concatenate([ext[..., -d:, :], ext, ext[..., :d, :]],
                           axis=-2)


# ---------------------------------------------------------------------------
# The oracle sweep: blocked Pallas == byte oracle, per rule.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,bw", SWEEP)
def test_periodic_pallas_matches_oracle(T, bw):
    for name in rulespec.rule_names():
        spec = rulespec.get_rule(name)
        state, planes = init(spec, seed=T * 8 + bw)
        out = run_pallas(planes, T, steps_per_launch=T, block_rows=BH,
                         block_words=bw, variant=name)
        want = rulespec.oracle_run(state, T, spec)
        got = bitplane.unpack(out)
        assert bool((got == jnp.asarray(want)).all()), (name, T, bw)


@pytest.mark.parametrize("T,bw", SWEEP)
def test_extended_pallas_matches_oracle(T, bw):
    """Extended-shard mode on a manually halo-extended torus: the
    global-mod RNG and clamped index maps must reproduce the owning
    cell's stream for every rule (including the RNG-free ones, whose
    kernels skip the hash entirely)."""
    for name in rulespec.rule_names():
        spec = rulespec.get_rule(name)
        state, planes = init(spec, seed=T + bw)
        d = T
        ext = periodic_ext(planes, d)
        out = run_extended(ext, d, t0=0, y0=-d, xw0=-1, hg=H, wdg=W // 32,
                           steps_per_launch=T, block_rows=BH,
                           block_words=bw, variant=name)
        got = bitplane.unpack(out[..., d:d + H, 1:1 + W // 32])
        want = rulespec.oracle_run(state, d, spec)
        assert bool((got == jnp.asarray(want)).all()), (name, T, bw)


def test_batched_lanes_match_oracle():
    """Ensemble lanes share the RNG stream (common random numbers), so
    each lane must match its own oracle run independently -- periodic
    and extended, every rule."""
    T, bw, d = 2, 2, 2
    for name in rulespec.rule_names():
        spec = rulespec.get_rule(name)
        pairs = [init(spec, seed=s) for s in (3, 4)]
        pb = jnp.stack([p for _, p in pairs])
        out = run_pallas(pb, T, steps_per_launch=T, block_rows=BH,
                         block_words=bw, variant=name)
        ext = periodic_ext(pb, d)
        out_e = run_extended(ext, d, t0=0, y0=-d, xw0=-1, hg=H,
                             wdg=W // 32, steps_per_launch=T,
                             block_rows=BH, block_words=bw, variant=name)
        got_e = out_e[..., d:d + H, 1:1 + W // 32]
        for i, (state, _) in enumerate(pairs):
            want = jnp.asarray(rulespec.oracle_run(state, T, spec))
            assert bool((bitplane.unpack(out[i]) == want).all()), (name, i)
            assert bool((bitplane.unpack(got_e[i]) == want).all()), (name, i)


def test_fhp_rule_stepper_matches_bitplane():
    """For the FHP specs the generic tap/circuit stepper is bit-identical
    to the hand-written ``bitplane.step_planes`` -- the refactor moved
    the hot path onto the spec, so this anchors it to history."""
    for name in ("fhp2", "fhp3"):
        spec = rulespec.get_rule(name)
        _, planes = init(spec, seed=9)
        for t in (0, 1, 5):
            a = rulespec.step_planes_rule(planes, t, spec, p_force=0.1)
            b = bitplane.step_planes(planes, t, p_force=0.1, variant=name)
            assert bool((a == b).all()), (name, t)


# ---------------------------------------------------------------------------
# Invariant audits on random states (property-based).
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_conservation_on_random_states(seed):
    """Each rule's claimed conserved quantities hold on *arbitrary*
    random states (not just well-formed initial conditions), across a
    multi-step run of the generic stepper."""
    for name in rulespec.rule_names():
        spec = rulespec.get_rule(name)
        rng = np.random.default_rng(seed)
        state = (rng.integers(0, 256, (H, 64), dtype=np.uint8)
                 & spec.byte_mask())
        planes = bitplane.pack(jnp.asarray(state), n_planes=spec.n_planes)
        before = popcounts(planes, spec.mass_planes)
        cur = planes
        for t in range(3):
            cur = rulespec.step_planes_rule(cur, t, spec)
        after = popcounts(cur, spec.mass_planes)
        if spec.per_plane_conserved:
            assert before == after, (name, before, after)
        if spec.conserves_mass:
            assert sum(before) == sum(after), (name, before, after)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_momentum_conservation_solid_free(seed):
    """Rules claiming momentum conservation keep (sum px2, sum py) on a
    solid-free torus (solids and forcing transfer momentum by design)."""
    for name in rulespec.rule_names():
        spec = rulespec.get_rule(name)
        if not spec.conserves_momentum:
            continue
        rng = np.random.default_rng(seed + 1)
        state = (rng.integers(0, 256, (H, 64), dtype=np.uint8)
                 & spec.byte_mask())
        if spec.solid_plane is not None:
            state = state & ~np.uint8(1 << spec.solid_plane)
        planes = bitplane.pack(jnp.asarray(state), n_planes=spec.n_planes)
        px0, py0 = bitplane.momentum_total(planes)
        cur = planes
        for t in range(3):
            cur = rulespec.step_planes_rule(cur, t, spec)
        px1, py1 = bitplane.momentum_total(cur)
        assert int(px0) == int(px1) and int(py0) == int(py1), name


def test_rng_free_rules_are_deterministic():
    """Rules with ``needs_rng=False`` must not consume randomness on any
    path: repeated runs agree, and toggling the kernel's RNG plumbing
    (``rng_in_kernel``) changes nothing."""
    from repro.kernels.fhp_step.ops import fhp_step_pallas
    for name in rulespec.rule_names():
        spec = rulespec.get_rule(name)
        if spec.needs_rng:
            continue
        _, planes = init(spec, seed=7)
        a = rulespec.step_planes_rule(planes, 0, spec)
        b = rulespec.step_planes_rule(planes, 0, spec)
        assert bool((a == b).all()), name
        k1 = fhp_step_pallas(planes, 0, variant=name, rng_in_kernel=True)
        k2 = fhp_step_pallas(planes, 0, variant=name, rng_in_kernel=False)
        assert bool((k1 == a).all()) and bool((k2 == a).all()), name


def test_bml_exclusivity_preserved():
    """BML never creates a doubly-occupied cell from an exclusive state:
    a car advances only into a cell that was empty pre-move."""
    spec = rulespec.get_rule("bml")
    state, planes = init(spec, seed=11, density=0.5)
    assert not np.any((state & 3) == 3)  # init is exclusive
    cur = planes
    for t in range(8):
        cur = rulespec.step_planes_rule(cur, t, spec)
        e, n = cur[..., 0, :, :], cur[..., 1, :, :]
        assert not bool(jnp.any(e & n)), t


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="registered"):
        rulespec.get_rule("fhp9")
