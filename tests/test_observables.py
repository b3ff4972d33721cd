"""In-kernel fused observables: the bit-exactness gate vs the post-hoc
popcount path, plus observables edge shapes.

The fused path records ``rulespec.moment_spec`` reductions inside the
temporal-blocked kernel (popcounts on VMEM-resident intermediate states)
at a cadence k; the reference is the per-step jnp stepper followed by
``rulespec.compute_moments`` on the streamed-out state.  Tier-1 layers
cover every launch shape the kernel has -- periodic single-device,
2-D x-blocked, batched lanes, halo-extended, interior/boundary split --
across registered rules and cadences k in {1, T, depth}; a slow
subprocess layer runs the sharded 2x2-mesh stepper (psum'd per-shard
partials) and the serve engine's fused-audit path against the same
reference.

Edge shapes (the satellite coverage): non-divisible ``coarse_velocity``
tiles raise, all-solid tiles report zero velocity, batched leading axes
thread through, the int32 accumulator headroom guard refuses lattices
that could overflow, and ``obstacle_report`` hits the per-scenario
raster cache instead of re-rasterizing per call.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.core import bitplane, distributed, rulespec
from repro.kernels.fhp_step.ops import (run_extended, run_extended_split,
                                        run_pallas)
from repro.scenarios import observables


def _planes(spec, h, wd, seed=0, batch=()):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.integers(0, 2 ** 32,
                                 batch + (spec.n_planes, h, wd),
                                 dtype=np.uint32))
    if spec.name == "bml":
        a = p[..., 0, :, :] & ~p[..., 1, :, :]
        b = p[..., 1, :, :] & ~a
        p = jnp.stack([a, b], axis=-3)  # exclusivity invariant
    return p


def _posthoc(planes, steps, spec, ms, k, p_force=0.0, t0=0):
    """Per-step jnp stepper + compute_moments at cadence k: the
    reference the fused kernel is gated against."""
    moms = []
    p = planes
    for s in range(steps):
        p = rulespec.run_planes_rule(p, 1, spec, p_force=p_force,
                                     t0=t0 + s)
        if (t0 + s + 1) % k == 0:
            moms.append(rulespec.compute_moments(p, ms))
    mom = (jnp.stack(moms, axis=-2) if moms else
           jnp.zeros(planes.shape[:-3] + (0, ms.n_moments), jnp.int32))
    return p, mom


# ---------------------------------------------------------------------------
# Fused vs post-hoc: every single-device launch shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(rulespec.rule_names()))
def test_fused_matches_posthoc_per_rule(variant):
    spec = rulespec.get_rule(variant)
    ms = rulespec.moment_spec(spec)
    p = _planes(spec, 8, 2, seed=3)
    pf = 0.1 if spec.force is not None else 0.0
    out, mom = run_pallas(p, 4, p_force=pf, steps_per_launch=2,
                          variant=variant, moments_every=1)
    want, wmom = _posthoc(p, 4, spec, ms, 1, p_force=pf)
    assert bool((out == want).all()), variant
    assert mom.shape == (4, ms.n_moments)
    assert bool((mom == wmom).all()), variant


@pytest.mark.parametrize("T,k", [(1, 1), (2, 2), (4, 3), (2, 6), (3, 4)])
def test_fused_cadences(T, k):
    """k < T (in-launch), k == T, k not dividing T, k > total steps --
    the launch schedule covers them all, recording at global steps
    (s + 1) % k == 0."""
    spec = rulespec.get_rule("fhp2")
    ms = rulespec.moment_spec(spec)
    p = _planes(spec, 8, 2, seed=k * 7 + T)
    out, mom = run_pallas(p, 4, p_force=0.05, steps_per_launch=T,
                          moments_every=k)
    want, wmom = _posthoc(p, 4, spec, ms, k, p_force=0.05)
    assert bool((out == want).all())
    assert mom.shape == wmom.shape
    assert bool((mom == wmom).all())


def test_fused_xblock_batched():
    """2-D x-blocked tiles + batched ensemble lanes: per-block partial
    moments sum over both grid axes and keep the lane axis."""
    spec = rulespec.get_rule("fhp2")
    ms = rulespec.moment_spec(spec)
    p = _planes(spec, 8, 4, seed=11, batch=(2,))
    out, mom = run_pallas(p, 4, p_force=0.05, steps_per_launch=2,
                          block_words=2, moments_every=2)
    want, wmom = _posthoc(p, 4, spec, ms, 2, p_force=0.05)
    assert mom.shape == (2, 2, ms.n_moments)
    assert bool((out == want).all())
    assert bool((mom == wmom).all())


def test_fused_extended_and_split():
    """Halo-extended launches accumulate moments over the *owned* region
    only (apron excluded by the bounds mask); the interior/boundary
    split sums its five disjoint pieces to the identical totals."""
    spec = rulespec.get_rule("fhp2")
    ms = rulespec.moment_spec(spec)
    h, wd, d = 16, 4, 4
    p = _planes(spec, h, wd, seed=5)
    rows = np.arange(-d, h + d) % h
    ext = p[..., rows, :]
    ext = jnp.concatenate([ext[..., -1:], ext, ext[..., :1]], axis=-1)
    kw = dict(t0=0, p_force=0.05, y0=-d, xw0=-1, hg=h, wdg=wd,
              steps_per_launch=2, block_rows=32)
    for k in (1, 2, 4):
        a, mom_a = run_extended(ext, d, moments_every=k, **kw)
        b, mom_b = run_extended_split(ext, d, moments_every=k, **kw)
        want, wmom = _posthoc(p, d, spec, ms, k, p_force=0.05)
        got = a[..., d:d + h, 1:1 + wd]
        assert bool((got == want).all()), k
        assert bool((mom_a == wmom).all()), k
        assert bool((b[..., d:d + h, 1:1 + wd] == want).all()), k
        assert bool((mom_b == mom_a).all()), k


def test_ensemble_run_jnp_fallback_moments():
    """``make_ensemble_run(mesh=None, use_pallas=False)`` returns the
    same (state, moments) contract from the plain jnp stepper."""
    spec = rulespec.get_rule("fhp2")
    ms = rulespec.moment_spec(spec)
    p = _planes(spec, 8, 2, seed=9, batch=(3,))
    run, _ = distributed.make_ensemble_run(None, 4, variant="fhp2",
                                           p_force=0.05, moments_every=2)
    out, mom = run(p, 0)
    want, wmom = _posthoc(p, 4, spec, ms, 2, p_force=0.05)
    assert mom.shape == (3, 2, ms.n_moments)
    assert bool((out == want).all())
    assert bool((mom == wmom).all())


def test_moment_headroom_guard():
    """int32 accumulation refuses lattices whose worst-case |moment|
    could wrap; comfortable lattices pass."""
    ms = rulespec.moment_spec(rulespec.get_rule("fhp2"))
    rulespec.require_moment_headroom(ms, 1 << 20)       # fine
    worst_per_site = max(sum(abs(c) for c in row) for row in ms.coeffs)
    too_big = (2 ** 31) // worst_per_site + 1
    with pytest.raises(ValueError, match="overflow"):
        rulespec.require_moment_headroom(ms, too_big)
    assert rulespec.moment_headroom(ms, 100) == worst_per_site * 100


# ---------------------------------------------------------------------------
# Observables edge shapes
# ---------------------------------------------------------------------------

def test_coarse_velocity_non_divisible_raises():
    p = jnp.zeros((8, 6, 3), jnp.uint32)
    with pytest.raises(AssertionError):       # rows don't tile
        observables.coarse_velocity(p, tile_rows=4, tile_words=3)
    with pytest.raises(AssertionError):       # words don't tile
        observables.coarse_velocity(p, tile_rows=3, tile_words=2)


def test_coarse_velocity_empty_tiles_and_batch():
    """All-empty (all-solid) tiles report zero velocity instead of 0/0;
    leading ensemble axes pass straight through."""
    spec = rulespec.get_rule("fhp2")
    p = np.array(_planes(spec, 8, 4, seed=2, batch=(2, 3)))  # writable copy
    p[..., :, 4:, :] = 0                 # bottom half: no particles at all
    v = observables.coarse_velocity(jnp.asarray(p), tile_rows=4,
                                    tile_words=2)
    assert v.shape == (2, 3, 2, 2, 2)
    assert bool((v[..., 1, :, :] == 0.0).all())   # empty tiles: zero
    assert np.isfinite(np.asarray(v)).all()


def test_obstacle_report_uses_raster_cache(monkeypatch):
    """The scanline rasterizer runs once per scenario, not once per
    report call."""
    from repro.geometry import raster
    sc = scenarios.get("cylinder", height=16, width=64)
    calls = {"n": 0}
    real = raster.solid_words

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(raster, "solid_words", counting)
    spec = sc.rule()
    p = _planes(spec, 16, 2, seed=1)
    r1 = observables.obstacle_report(p, sc)
    r2 = observables.obstacle_report(p, sc)
    assert r1 == r2 and set(r1) == {n for n, _ in sc.obstacles}
    assert calls["n"] == len(sc.obstacles), calls


def test_frame_summary_accepts_precomputed_invariants():
    """A frame built from supplied invariants (the serve engine's fused
    moments) is identical to the recomputed one."""
    spec = rulespec.get_rule("fhp2")
    ms = rulespec.moment_spec(spec)
    p = _planes(spec, 8, 2, seed=4)
    base = observables.frame_summary(p, spec, 7)
    mom = rulespec.compute_moments(p, ms)
    inv = {n: v for n, v in rulespec.moments_dict(ms, mom).items()
           if not n.startswith("excl")}
    assert observables.frame_summary(p, spec, 7, inv=inv) == base


# ---------------------------------------------------------------------------
# Sharded 2x2 mesh + serve fused-audit path (subprocess; slow)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import distributed, rulespec

    spec = rulespec.get_rule("fhp2")
    ms = rulespec.moment_spec(spec)
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.integers(0, 2**32, (8, 16, 4), dtype=np.uint32))
    mesh = distributed.make_mesh((2, 2), ("data", "model"))
    for overlap in (False, True):
        run = distributed.make_run(mesh, 4, depth=2, p_force=0.05,
                                   use_pallas=True, steps_per_launch=2,
                                   overlap=overlap, moments_every=2)
        out, mom = jax.jit(run)(p, 0)
        want = p
        moms = []
        for s in range(4):
            want = rulespec.step_planes_rule(want, s, spec, p_force=0.05)
            if (s + 1) % 2 == 0:
                moms.append(rulespec.compute_moments(want, ms))
        wmom = jnp.stack(moms, axis=-2)
        assert bool((out == want).all()), overlap
        assert mom.shape == wmom.shape, (mom.shape, wmom.shape)
        assert bool((mom == wmom).all()), overlap
    print("SHARDED_MOMENTS_OK")
""")


@pytest.mark.slow
def test_sharded_mesh_moments_subprocess():
    """Per-shard fused partials psum to the global moments on a 2x2
    mesh, serial and overlapped exchange alike."""
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_MOMENTS_OK" in r.stdout
