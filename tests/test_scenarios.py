"""Scenario subsystem + static-geometry cache.

Fast (tier-1) layers:

* registry contract: >= 5 named scenarios, all scalable, solid plane ==
  rasterized geometry, seeded initial states reproducible;
* the CI scenario smoke sweep: every registered scenario on a tiny
  lattice for a few steps with a mass-conservation audit;
* 7-plane static-solid bit-exactness vs the 8-plane reference, single
  device: periodic kernel mode, extended mode (incl. remainder launch),
  and batched lanes;
* observables sanity.

Slow layer: every scenario through the sharded extended Pallas path with
the static-geometry cache on a fake 2x2 mesh, bit-identical to the
single-device reference (subprocess; the acceptance gate).
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.core import bitplane, rulespec
from repro.kernels.fhp_step.ops import fhp_step_pallas, run_extended
from repro.scenarios import observables

TINY = dict(height=16, width=128)


def ref_steps(p, n, t0=0, p_force=0.0):
    for s in range(n):
        p = bitplane.step_planes(p, t0 + s, p_force=p_force)
    return p


# ---------------------------------------------------------------------------
# Registry contract.
# ---------------------------------------------------------------------------

def test_registry_has_scenario_suite():
    names = scenarios.names()
    assert len(names) >= 5, names
    for required in ("cylinder", "poiseuille", "backward_step",
                     "porous_plug", "cavity"):
        assert required in names, (required, names)


def test_scenarios_build_and_scale():
    for name in scenarios.names():
        sc = scenarios.get(name, **TINY)
        spec = sc.rule()
        assert sc.height == TINY["height"] and sc.width == TINY["width"]
        planes = sc.initial_planes()
        assert planes.shape == (spec.n_planes, sc.height, sc.width // 32)
        if spec.solid_plane is not None:
            sp = spec.solid_plane
            # the packed solid plane is exactly the rasterized geometry
            assert (np.asarray(planes[sp]) == sc.solid_plane()).all()
            # solid nodes carry no particles initially
            assert int(observables.solid_momentum(planes, planes[sp])[0]) == 0
        else:
            # solid-free rules may not carry obstacle geometry
            assert not sc.solid_plane().any(), name
        mass = sum(
            int(np.unpackbits(np.asarray(planes[i]).view(np.uint8)).sum())
            for i in spec.mass_planes)
        assert mass > 0, name


def test_scenario_states_are_seeded():
    a = scenarios.get("cylinder", **TINY).initial_bytes()
    b = scenarios.get("cylinder", **TINY).initial_bytes()
    c = scenarios.get("cylinder", seed=11, **TINY).initial_bytes()
    assert (a == b).all()
    assert (a != c).any()


def test_unknown_scenario_raises():
    with pytest.raises(KeyError):
        scenarios.get("no-such-flow")


# ---------------------------------------------------------------------------
# CI scenario smoke sweep: every scenario, tiny lattice, mass audit.
# ---------------------------------------------------------------------------

def test_scenario_smoke_sweep_mass_conservation():
    def counts(spec, p):
        return [int(np.unpackbits(np.asarray(p[i]).view(np.uint8)).sum())
                for i in spec.mass_planes]

    for name in scenarios.names():
        sc = scenarios.get(name, **TINY)
        spec = sc.rule()
        planes = sc.initial_planes()
        c0 = counts(spec, planes)
        out = rulespec.run_planes_rule(planes, 4, spec, p_force=sc.p_force)
        if spec.per_plane_conserved:
            assert counts(spec, out) == c0, name
        else:
            assert sum(counts(spec, out)) == sum(c0), name
        if spec.solid_plane is not None:
            # geometry is invariant under the update
            sp = spec.solid_plane
            assert bool((out[sp] == planes[sp]).all()), name


# ---------------------------------------------------------------------------
# Static-geometry (7-plane) path == 8-plane reference, single device.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 2])
def test_static_solid_periodic_matches_reference(T):
    sc = scenarios.get("cylinder", **TINY)
    p = sc.initial_planes()
    want = ref_steps(p, T, t0=3, p_force=0.05)
    got = fhp_step_pallas(p[:7], 3, p_force=0.05, steps_per_launch=T,
                          block_rows=8, solid=p[7])
    assert bool((got == want[:7]).all()), T


@pytest.mark.parametrize("d,T", [(2, 2), (4, 2), (3, 2)])
def test_static_solid_extended_matches_reference(d, T):
    """run_extended with the cached solid apron: (3, 2) exercises the
    remainder launch; the solid tile serves every launch unchanged."""
    sc = scenarios.get("backward_step", **TINY)
    h, wd = sc.height, sc.width // 32
    p = sc.initial_planes()
    ext = jnp.concatenate([p[..., -1:], p, p[..., :1]], axis=-1)
    ext = jnp.concatenate([ext[..., -d:, :], ext, ext[..., :d, :]], axis=-2)
    out = run_extended(ext[:7], d, t0=5, p_force=0.1, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=8,
                       solid_ext=ext[7])
    got = out[..., d:d + h, 1:1 + wd]
    want = ref_steps(p, d, t0=5, p_force=0.1)
    assert bool((got == want[:7]).all()), (d, T)


def test_static_solid_batched_lanes_share_geometry():
    d = T = 2
    sc = scenarios.get("cylinder", **TINY)
    lanes = [sc.initial_planes(),
             scenarios.get("cylinder", seed=8, **TINY).initial_planes()]
    pb = jnp.stack(lanes)
    h, wd = sc.height, sc.width // 32
    ext = jnp.concatenate([pb[..., -1:], pb, pb[..., :1]], axis=-1)
    ext = jnp.concatenate([ext[..., -d:, :], ext, ext[..., :d, :]], axis=-2)
    out = run_extended(ext[:, :7], d, t0=1, p_force=0.05, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=8,
                       solid_ext=ext[0, 7])
    got = out[..., d:d + h, 1:1 + wd]
    for i, lane in enumerate(lanes):
        want = ref_steps(lane, d, t0=1, p_force=0.05)
        assert bool((got[i] == want[:7]).all()), i


def test_static_solid_make_run_jnp_fallback_and_batched():
    """The two make_run static-geometry configurations the sharded
    sweeps don't reach: the use_pallas=False fallback (rebuilds the
    8-plane stack from the cache) and batched lanes (lane 0's geometry
    shared).  A 1x1 in-process mesh keeps it fast and in tier-1; the
    2x2 sweep covers the multi-shard exchange."""
    import jax

    from repro.core import distributed
    mesh = distributed.make_mesh((1, 1), ("data", "model"))
    sc = scenarios.get("cylinder", **TINY)
    p = sc.initial_planes()
    want = ref_steps(p, 4, p_force=sc.p_force)

    run = jax.jit(distributed.make_run(
        mesh, 4, y_axes=("data",), x_axis="model", p_force=sc.p_force,
        depth=2, use_pallas=False, static_solid=True))
    assert bool((run(p, 0) == want).all())

    lanes = [p, scenarios.get("cylinder", seed=8, **TINY).initial_planes()]
    pb = jnp.stack(lanes)
    wantb = jnp.stack([ref_steps(q, 4, p_force=sc.p_force) for q in lanes])
    runb = jax.jit(distributed.make_run(
        mesh, 4, y_axes=("data",), x_axis="model", p_force=sc.p_force,
        depth=2, use_pallas=True, steps_per_launch=2, batched=True,
        static_solid=True))
    assert bool((runb(pb, 0) == wantb).all())


def test_static_solid_shape_validation():
    sc = scenarios.get("cylinder", **TINY)
    p = sc.initial_planes()
    with pytest.raises(ValueError):
        fhp_step_pallas(p, 0, solid=p[7])          # 8 planes + solid
    with pytest.raises(ValueError):
        fhp_step_pallas(p[:7], 0)                   # 7 planes, no solid


# ---------------------------------------------------------------------------
# Observables.
# ---------------------------------------------------------------------------

def test_coarse_velocity_shape_and_rest_frame():
    sc = scenarios.get("poiseuille", **TINY)
    p = sc.initial_planes()
    v = observables.coarse_velocity(p, tile_rows=4, tile_words=2)
    assert v.shape == (4, 2, 2)
    # forced run develops positive mean x-velocity
    out = bitplane.run_planes(p, 30, p_force=0.2)
    v2 = observables.coarse_velocity(out, tile_rows=4, tile_words=2)
    assert float(v2[..., 0].mean()) > float(v[..., 0].mean())


def test_obstacle_report_names_match():
    sc = scenarios.get("cylinder", **TINY)
    rep = observables.obstacle_report(sc.initial_planes(), sc)
    assert set(rep) == {"disk"} and rep["disk"] == (0, 0)


# ---------------------------------------------------------------------------
# Full sharded path on a fake 2x2 mesh (subprocess): every scenario.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_all_scenarios_sharded_static_geometry_bit_exact():
    """The acceptance gate: drive ``benchmarks.bench_scenarios`` itself
    (one sweep definition, no duplicate script to drift) -- it asserts
    per-scenario bit-exactness and mass conservation through the sharded
    static-geometry path on the fake 2x2 mesh and fails loudly otherwise.
    The full environment is inherited (plus PYTHONPATH=src) so backend
    overrides like JAX_PLATFORMS keep working in the children."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scenarios", "--smoke"],
        capture_output=True, text=True, timeout=900, cwd=repo, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "_sps," in r.stdout, r.stdout   # timed per-scenario records ran
