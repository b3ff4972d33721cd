"""Compute/communication overlap: the interior/boundary split.

Tier-1 layers (single device, fabricated halo-extended shards):

* ``run_extended_split`` == ``run_extended`` == the byte oracle, bit for
  bit, across registered rules x odd shard heights x d % T != 0 x
  x-blocked tiles;
* degenerate shards (boundary band covers the whole shard, or no
  interior word) fall back to the serial path bit-exactly;
* the overlap roofline model: strictly cheaper than serial whenever the
  modeled interior time is positive, exactly 1.0x on degenerate shapes,
  and the joint autotuner returns the 5-tuple with the overlap flag;
* ``measured_exchange_latency`` caches per mesh fingerprint;
* ``input_output_aliases`` donation rides every extended launch --
  main-loop *and* remainder -- checked on the jaxpr.

Plus a 4-fake-device subprocess layer: the overlap stepper on a 2x2 mesh
for static-solid geometry, batched lanes, and a degenerate shard
(depth = hl/2 so the bands cover the shard), all vs the single-device
reference.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rulespec
from repro.kernels.fhp_step.ops import (autotune_launch, run_extended,
                                        run_extended_split)
from repro.roofline.analysis import sharded_fhp_traffic


def _planes(spec, h, wd, seed):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.integers(0, 2 ** 32, (spec.n_planes, h, wd),
                                 dtype=np.uint32))
    if spec.name == "bml":
        a = p[0] & ~p[1]
        p = jnp.stack([a, p[1] & ~a])   # BML exclusivity invariant
    return p


def _sub_ext(p, r0, hl, d):
    """Halo-extended array of the shard = global rows [r0, r0 + hl),
    all words: wrap halos sliced from the (periodic) global lattice.
    Returns (ext, y0, xw0)."""
    h = p.shape[-2]
    rows = (np.arange(r0 - d, r0 + hl + d) % h)
    e = p[..., rows, :]
    e = jnp.concatenate([e[..., -1:], e, e[..., :1]], axis=-1)
    return e, r0 - d, -1


@pytest.mark.parametrize("variant", sorted(rulespec.rule_names()))
@pytest.mark.parametrize("r0,hl,d,T", [
    (0, 16, 4, 2),    # even shard, d % T == 0
    (0, 9, 3, 2),     # odd shard height, d % T != 0
    (5, 12, 4, 4),    # offset sub-band, T == d
    (9, 9, 2, 1),     # odd height + odd offset, T == 1
])
def test_split_matches_serial_and_oracle(variant, r0, hl, d, T):
    """The composed interior+boundary launches reproduce the serial
    extended path and the rule's reference stepper bit for bit, at any
    global offset (the global-mod RNG/parity make the sub-slice launches
    exact)."""
    spec = rulespec.get_rule(variant)
    h, wd = 18, 8                         # global lattice; shard is a band
    p = _planes(spec, h, wd, seed=r0 * 31 + hl)
    pf = 0.1 if spec.force is not None else 0.0
    ext, y0, xw0 = _sub_ext(p, r0, hl, d)
    kw = dict(t0=2, p_force=pf, y0=y0, xw0=xw0, hg=h, wdg=wd,
              steps_per_launch=T, block_rows=32, variant=variant)
    a = run_extended(ext, d, **kw)[..., d:d + hl, 1:1 + wd]
    b = run_extended_split(ext, d, **kw)[..., d:d + hl, 1:1 + wd]
    want = p
    for s in range(d):
        want = rulespec.step_planes_rule(want, 2 + s, spec, p_force=pf)
    rows = np.arange(r0, r0 + hl) % h
    want = want[..., rows, :]
    assert bool((a == want).all()), (variant, r0, hl, d, T, "serial")
    assert bool((b == want).all()), (variant, r0, hl, d, T, "split")


@pytest.mark.parametrize("hl,wd,d", [
    (8, 8, 4),     # hl == 2d: boundary bands cover the whole shard
    (6, 8, 4),     # hl < 2d
    (16, 2, 4),    # wdl == 2: no interior word
])
def test_split_degenerate_falls_back_serial(hl, wd, d):
    """Shards the split cannot cover with a non-empty interior must take
    the serial path bit-exactly (same composition as run_extended)."""
    spec = rulespec.get_rule("fhp2")
    h = 18
    p = _planes(spec, h, wd, seed=hl)
    ext, y0, xw0 = _sub_ext(p, 0, hl, d)
    kw = dict(t0=0, p_force=0.05, y0=y0, xw0=xw0, hg=h, wdg=wd,
              steps_per_launch=2, block_rows=32)
    a = run_extended(ext, d, **kw)
    b = run_extended_split(ext, d, **kw)
    assert bool((a == b).all()), (hl, wd, d)


def test_split_x_blocked_tile():
    """The split composes with the 2-D (x x y) blocked kernel grid."""
    spec = rulespec.get_rule("fhp2")
    h, wd, d, T = 16, 16, 4, 2
    p = _planes(spec, h, wd, seed=3)
    ext, y0, xw0 = _sub_ext(p, 0, h, d)
    kw = dict(t0=1, p_force=0.1, y0=y0, xw0=xw0, hg=h, wdg=wd,
              steps_per_launch=T, block_rows=8, block_words=4)
    a = run_extended(ext, d, **kw)[..., d:d + h, 1:1 + wd]
    b = run_extended_split(ext, d, **kw)[..., d:d + h, 1:1 + wd]
    want = p
    for s in range(d):
        want = rulespec.step_planes_rule(want, 1 + s, spec, p_force=0.1)
    assert bool((a == want).all())
    assert bool((b == want).all())


# ---------------------------------------------------------------------------
# Roofline model.
# ---------------------------------------------------------------------------

def test_overlap_model_strictly_cheaper_when_interior_positive():
    """Acceptance gate: ``sharded_fhp_traffic(overlap=True)`` must model
    strictly lower cost than the serial model whenever the interior
    launch has positive modeled time -- the exchange hides under it."""
    for hl, wdl, d, T, bh, bw in [(256, 32, 8, 8, 32, 0),
                                  (1024, 128, 8, 4, 16, 0),
                                  (8192, 2048, 16, 8, 32, 128),
                                  (64, 16, 4, 2, 8, 0)]:
        s = sharded_fhp_traffic(hl, wdl, depth=d, T=T, block_rows=bh,
                                block_words=bw)
        o = sharded_fhp_traffic(hl, wdl, depth=d, T=T, block_rows=bh,
                                block_words=bw, overlap=True)
        assert o["t_interior_s_per_site"] > 0, (hl, wdl)
        assert o["total_s_per_site"] < s["total_s_per_site"], (hl, wdl)
        assert o["overlap_speedup_modeled"] > 1.0, (hl, wdl)
        assert o["serial_s_per_site"] == pytest.approx(
            s["total_s_per_site"])
        # the round is priced max(exchange, interior) + boundary
        assert o["total_s_per_site"] == pytest.approx(
            max(o["t_exchange_s_per_site"], o["t_interior_s_per_site"])
            + o["t_boundary_s_per_site"])


def test_overlap_model_degenerate_is_serial():
    """Shapes where the runtime falls back to the serial path must price
    at exactly the serial cost (ratio 1.0, no interior time)."""
    for hl, wdl in [(6, 32), (16, 2)]:
        s = sharded_fhp_traffic(hl, wdl, depth=4, T=2, block_rows=8)
        o = sharded_fhp_traffic(hl, wdl, depth=4, T=2, block_rows=8,
                                overlap=True)
        assert o["overlap_speedup_modeled"] == 1.0, (hl, wdl)
        assert o["t_interior_s_per_site"] == 0.0
        assert o["overlap"] == 0.0
        assert o["total_s_per_site"] == pytest.approx(s["total_s_per_site"])


def test_autotune_overlap_flag():
    """The sharded search returns (bh, bw, T, depth, overlap); on a
    representative shard the overlapped plan must never model worse than
    the serial plan at the same point, and a zero-latency, zero-bandwidth
    exchange gives overlap nothing to hide -- the tuner keeps the serial
    plan (ties break serial)."""
    from repro.kernels.fhp_step.ops import sharded_launch_cost
    bh, bw, T, d, ov = autotune_launch(1024, 128, max_depth=16,
                                       exchange_latency_s=3e-6)
    assert isinstance(ov, bool)
    cost_s = sharded_launch_cost(bh, T, d, 1024, 128, block_words=bw,
                                 exchange_latency_s=3e-6)
    cost_o = sharded_launch_cost(bh, T, d, 1024, 128, block_words=bw,
                                 overlap=True, exchange_latency_s=3e-6)
    assert ov == (cost_o < cost_s)


# ---------------------------------------------------------------------------
# Exchange-latency cache per mesh fingerprint.
# ---------------------------------------------------------------------------

def test_exchange_latency_cached_per_mesh_fingerprint():
    from repro.roofline import analysis
    analysis._MEASURED_EXCHANGE_LATENCY.clear()
    lat = analysis.measured_exchange_latency()
    key = analysis._mesh_fingerprint()
    assert key in analysis._MEASURED_EXCHANGE_LATENCY
    assert analysis._MEASURED_EXCHANGE_LATENCY[key] == lat
    # repeated calls hit the cache (same object state, same answer)
    assert analysis.measured_exchange_latency() == lat
    # a foreign fingerprint's entry does not shadow this mesh's
    analysis._MEASURED_EXCHANGE_LATENCY[("other", 99, "?")] = 123.0
    assert analysis.measured_exchange_latency() == lat
    del analysis._MEASURED_EXCHANGE_LATENCY[("other", 99, "?")]
    # refresh=True re-measures and re-populates the same key
    lat2 = analysis.measured_exchange_latency(refresh=True)
    assert analysis._MEASURED_EXCHANGE_LATENCY[key] == lat2


# ---------------------------------------------------------------------------
# Donation on every extended launch (main loop + remainder).
# ---------------------------------------------------------------------------

def _pallas_eqns(jaxpr, out):
    for e in jaxpr.eqns:
        if "pallas" in str(e.primitive):
            out.append(e)
        for v in e.params.values():
            if hasattr(v, "jaxpr"):
                _pallas_eqns(v.jaxpr, out)
            elif hasattr(v, "eqns"):
                _pallas_eqns(v, out)
    return out


def test_remainder_launch_carries_donation():
    """steps=3, T=2 -> one full launch + one remainder launch; both
    pallas_calls must alias their carry (input_output_aliases), incl. on
    a thin boundary-band-sized slice where an uncapped explicit
    block_rows used to pad the tile (the cap keeps it single-tile)."""
    for he, wde in [(24, 10),    # hl=16 shard + apron
                    (9, 10)]:    # 3d-row boundary band, d=3
        ext = jnp.zeros((8, he, wde), jnp.uint32)
        jx = jax.make_jaxpr(
            lambda e: run_extended(e, 3, t0=0, y0=-3, xw0=-1, hg=32,
                                   wdg=8, steps_per_launch=2,
                                   block_rows=32))(ext)
        eqns = _pallas_eqns(jx.jaxpr, [])
        assert len(eqns) == 2, (he, len(eqns))          # full + remainder
        for e in eqns:
            assert e.params.get("input_output_aliases"), \
                (he, "launch without donated carry")


def test_explicit_block_rows_capped_to_slice():
    """The tile cap: an explicit block_rows=32 on a 9-row slice must not
    pad the launch to 32 rows (wasted traffic on every boundary band of
    the split) -- the padded array stays at the pow2 cap."""
    ext = jnp.zeros((8, 9, 10), jnp.uint32)
    out = run_extended(ext, 2, t0=0, y0=0, xw0=0, hg=32, wdg=8,
                       steps_per_launch=2, block_rows=32)
    assert out.shape == ext.shape
    jx = jax.make_jaxpr(
        lambda e: run_extended(e, 2, t0=0, y0=0, xw0=0, hg=32, wdg=8,
                               steps_per_launch=2, block_rows=32))(ext)
    eqns = _pallas_eqns(jx.jaxpr, [])
    # the launch operand is the 16-row (pow2 >= 9) padded array, not 32
    rows = {v.aval.shape[-2] for e in eqns for v in e.invars
            if len(getattr(v.aval, "shape", ())) >= 2}
    assert 16 in rows and 32 not in rows, rows


# ---------------------------------------------------------------------------
# Mesh coverage the other subprocess sweeps don't reach: static-solid,
# batched, and degenerate-shard overlap on a fake 2x2 mesh.
# ---------------------------------------------------------------------------

MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro import scenarios
    from repro.core import bitplane, distributed

    failures = []
    mesh = distributed.make_mesh((2, 2), ("data", "model"))
    sh = NamedSharding(mesh, distributed.lattice_spec(("data",), "model"))
    sc = scenarios.get("cylinder", height=16, width=256)
    p = sc.initial_planes()
    steps = 4
    want = p
    for s in range(steps):
        want = bitplane.step_planes(want, s, p_force=sc.p_force)

    # static-solid geometry through the overlapped stepper
    run = jax.jit(distributed.make_run(
        mesh, steps, y_axes=("data",), x_axis="model", p_force=sc.p_force,
        depth=2, use_pallas=True, steps_per_launch=2, static_solid=True,
        overlap=True))
    ok = bool((run(jax.device_put(p, sh), 0) == want).all())
    print(f"static_solid overlap: {ok}")
    if not ok:
        failures.append("static_solid")

    # degenerate shard: hl = 8, depth = 4 -> boundary bands cover the
    # shard; overlap must fall back to the serial path bit-exactly
    rund = jax.jit(distributed.make_run(
        mesh, 4, y_axes=("data",), x_axis="model", p_force=sc.p_force,
        depth=4, use_pallas=True, steps_per_launch=2, static_solid=True,
        overlap=True))
    ok = bool((rund(jax.device_put(p, sh), 0) == want).all())
    print(f"degenerate-shard overlap fallback: {ok}")
    if not ok:
        failures.append("degenerate")

    # batched ensemble lanes
    p2 = scenarios.get("cylinder", seed=9, height=16, width=256)
    pb = jnp.stack([p, p2.initial_planes()])
    shb = NamedSharding(mesh, distributed.lattice_spec(
        ("data",), "model", batched=True))
    wantb = []
    for lane in pb:
        q = lane
        for s in range(steps):
            q = bitplane.step_planes(q, s, p_force=sc.p_force)
        wantb.append(q)
    wantb = jnp.stack(wantb)
    runb = jax.jit(distributed.make_run(
        mesh, steps, y_axes=("data",), x_axis="model", p_force=sc.p_force,
        depth=2, use_pallas=True, steps_per_launch=2, batched=True,
        overlap=True))
    ok = bool((runb(jax.device_put(pb, shb), 0) == wantb).all())
    print(f"batched overlap: {ok}")
    if not ok:
        failures.append("batched")

    # overlap without use_pallas must be rejected
    try:
        distributed.make_sharded_stepper(mesh, overlap=True)
        failures.append("overlap without pallas not rejected")
    except AssertionError:
        print("overlap-needs-pallas rejected: True")

    assert not failures, failures
    print("ALL_OK")
""")


def test_overlap_mesh_static_batched_degenerate():
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout
