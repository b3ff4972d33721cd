"""Sharded temporal blocking: the extended-shard Pallas kernel under
shard_map must be bit-identical to the single-device jnp reference for
every (depth, steps_per_launch), including forcing and batched lanes.

Three layers of proof:

* a property test that the global-mod RNG coordinates make apron rows /
  halo words draw the *owning* shard's stream exactly (the invariant that
  lets one depth-d exchange feed d in-kernel steps);
* single-device extended-mode equivalence: ``run_extended`` on a manually
  halo-extended array reproduces the periodic reference (fast, no mesh);
* the full shard_map path over a fake-device mesh (subprocess, so the 4
  host devices never leak into other tests), depth in {1, 2, 4} x
  T in {1, 2, d}, plus a 3-axis mesh and a batched-ensemble case.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import bitplane, byte_step, prng
from repro.kernels.fhp_step import kernel as _k
from repro.kernels.fhp_step.ops import (autotune_launch, run_extended,
                                        sharded_hbm_bytes_per_site,
                                        vmem_bytes, VMEM_BUDGET_BYTES)


def state(h, w, seed=0):
    return bitplane.pack(jnp.asarray(
        byte_step.make_channel(h, w, density=0.3, seed=seed)))


def ref_steps(p, n, t0=0, p_force=0.0):
    for s in range(n):
        p = bitplane.step_planes(p, t0 + s, p_force=p_force)
    return p


# ---------------------------------------------------------------------------
# Property: global-mod coordinates reproduce the owning shard's RNG stream.
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4),      # ny: shards in y
       st.integers(1, 8),      # hl/2: local rows (kept even)
       st.integers(1, 6),      # depth
       st.integers(0, 3),      # iy: this shard's y index (mod ny)
       st.integers(0, 9))      # t
def test_global_mod_rng_matches_owner(ny, hl2, depth, iy, t):
    """The extended kernel's (y0 + local) % H_g rows and (xw0 + word) %
    Wd_g cols give every apron row / halo word exactly the draw the owning
    shard makes for it -- including across the global periodic wrap."""
    hl, iy = 2 * hl2, iy % ny
    hg, wdl, nx, ix = ny * hl, 4, 2, 1
    wdg = nx * wdl
    full = prng.chirality_words((hg, wdg), t)

    # Kernel-side coordinates: int32 arithmetic, then the uint32 cast the
    # in-kernel hash applies (kernel._word_u32 on broadcast iota blocks).
    y0, xw0 = iy * hl - depth, ix * wdl - 1
    rows = (y0 + np.arange(hl + 2 * depth, dtype=np.int64)) % hg
    cols = (xw0 + np.arange(wdl + 2, dtype=np.int64)) % wdg
    got = _k._word_u32(jnp.asarray(rows, jnp.uint32)[:, None],
                       jnp.asarray(cols, jnp.uint32)[None, :],
                       jnp.uint32(t), salt=0x11)
    want = jnp.asarray(np.asarray(full)[rows[:, None], cols[None, :]])
    assert bool((got == want).all()), (ny, hl, depth, iy, t)


# ---------------------------------------------------------------------------
# Single-device extended mode (no mesh): run_extended on a periodic halo.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,T", [(1, 1), (2, 2), (4, 2), (4, 4), (3, 2)])
def test_extended_mode_matches_reference(d, T):
    """d steps on a manually extended array == d periodic reference steps
    on the interior.  (3, 2) exercises the one-launch remainder path."""
    h, w = 16, 128
    wd = w // 32
    p = state(h, w, seed=d + T)
    ext = jnp.concatenate([p[..., -1:], p, p[..., :1]], axis=-1)
    ext = jnp.concatenate([ext[..., -d:, :], ext, ext[..., :d, :]], axis=-2)
    out = run_extended(ext, d, t0=5, p_force=0.1, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=8)
    got = out[..., d:d + h, 1:1 + wd]
    want = ref_steps(p, d, t0=5, p_force=0.1)
    assert bool((got == want).all()), (d, T)


def test_extended_mode_batched_lanes():
    d, T, h, w = 2, 2, 16, 128
    wd = w // 32
    lanes = [state(h, w, seed=s) for s in range(2)]
    pb = jnp.stack(lanes)
    ext = jnp.concatenate([pb[..., -1:], pb, pb[..., :1]], axis=-1)
    ext = jnp.concatenate([ext[..., -d:, :], ext, ext[..., :d, :]], axis=-2)
    out = run_extended(ext, d, t0=1, p_force=0.05, y0=-d, xw0=-1,
                       hg=h, wdg=wd, steps_per_launch=T, block_rows=8)
    got = out[..., d:d + h, 1:1 + wd]
    for i, lane in enumerate(lanes):
        assert bool((got[i] == ref_steps(lane, d, t0=1, p_force=0.05)).all())


# ---------------------------------------------------------------------------
# Joint autotune: the sharded (block_rows, T, depth) point and its model.
# ---------------------------------------------------------------------------

def test_traffic_model_static_solid():
    """Static geometry cuts exchange bytes by exactly 7/8 (the solid
    plane leaves every round) and the HBM writeback term by 7/8, while
    reads are unchanged; the one-time solid-apron exchange is priced
    separately and excluded from per-step totals."""
    from repro.roofline.analysis import sharded_fhp_traffic
    for depth, T in [(1, 1), (4, 2), (8, 8)]:
        dyn = sharded_fhp_traffic(256, 32, depth=depth, T=T, block_rows=32)
        sta = sharded_fhp_traffic(256, 32, depth=depth, T=T, block_rows=32,
                                  static_solid=True)
        assert sta["ici_bytes_per_site_step"] == pytest.approx(
            dyn["ici_bytes_per_site_step"] * 7 / 8)
        assert sta["ici_bytes_per_exchange"] == pytest.approx(
            dyn["ici_bytes_per_exchange"] * 7 / 8)
        assert sta["hbm_bytes_per_site_step"] < dyn["hbm_bytes_per_site_step"]
        assert sta["geometry_exchange_bytes"] == pytest.approx(
            dyn["ici_bytes_per_exchange"] / 8)
        assert dyn["geometry_exchange_bytes"] == 0.0
        # latency/exchange-count structure is untouched by the cache
        assert sta["exchanges_per_step"] == dyn["exchanges_per_step"]
        assert sta["launches_per_step"] == dyn["launches_per_step"]


def test_measured_exchange_latency_constant_off_mesh():
    """On CPU / single-device backends the ppermute microbenchmark would
    time a host memcpy, so the tuner must fall back to the documented
    constant (and cache the answer); autotune accepts an explicit
    override and gives the same point for the same latency."""
    from repro.roofline import analysis
    lat = analysis.measured_exchange_latency()
    assert lat == analysis.measured_exchange_latency()  # cached
    import jax
    if jax.default_backend() == "cpu" or len(jax.devices()) < 2:
        assert lat == analysis.EXCHANGE_LATENCY_S
    else:
        assert 0 < lat < 1e-2
    assert (autotune_launch(1024, 128, max_depth=16)
            == autotune_launch(1024, 128, max_depth=16,
                               exchange_latency_s=lat))
    # a much larger latency must push the tuner at least as deep
    _, _, _, d0, _ = autotune_launch(1024, 128, max_depth=16,
                                     exchange_latency_s=lat)
    _, _, _, d1, _ = autotune_launch(1024, 128, max_depth=16,
                                     exchange_latency_s=100 * lat)
    assert d1 >= d0


def test_autotune_joint_sharded():
    for hl, wdl in [(256, 32), (1024, 128), (8192, 2048)]:
        bh, bw, T, d, ov = autotune_launch(hl, wdl, max_depth=16)
        assert 1 <= T <= min(bh, d) and 1 <= d <= 31, (bh, bw, T, d)
        assert isinstance(ov, bool)
        assert bw >= wdl + 2 or T <= bw, (bw, T)
        assert vmem_bytes(bh, wdl + 2, T, bw) <= VMEM_BUDGET_BYTES
        # The exchange-latency term must push the tuner to a deep halo,
        # and the modeled sharded traffic must hit the stage-4 target.
        assert d >= 4, (hl, wdl, d)
        assert sharded_hbm_bytes_per_site(bh, T, d, hl, wdl,
                                          block_words=bw) <= 0.6
    # depth can never exceed the shard rows (nearest-neighbour exchange)
    bh, bw, T, d, ov = autotune_launch(8, 32, max_depth=16)
    assert d <= 8, d
    # single-device signature: the 2-D (block_rows, block_words, T) tile
    bh, bw, T = autotune_launch(1024, 128)
    assert isinstance(bh, int) and isinstance(bw, int) and isinstance(T, int)


# ---------------------------------------------------------------------------
# Full shard_map path on a fake-device mesh (subprocess).
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core import byte_step, bitplane, distributed

    failures = []
    mesh = distributed.make_mesh((2, 2), ("data", "model"))
    H, W = 32, 256
    s = jnp.asarray(byte_step.make_channel(H, W, density=0.3, seed=3))
    p = bitplane.pack(s)
    sh = NamedSharding(mesh, distributed.lattice_spec(("data",), "model"))
    pd = jax.device_put(p, sh)
    ref = bitplane.run_planes(p, 8, p_force=0.03)
    for depth in (1, 2, 4):
        for T in sorted({1, 2, depth}):
            if T > depth:
                continue
            for overlap in (False, True):
                run = jax.jit(distributed.make_run(
                    mesh, 8, y_axes=("data",), x_axis="model", p_force=0.03,
                    depth=depth, use_pallas=True, steps_per_launch=T,
                    overlap=overlap))
                ok = bool((run(pd, 0) == ref).all())
                print(f"pallas depth={depth} T={T} overlap={overlap}: {ok}")
                if not ok:
                    failures.append(("2x2", depth, T, overlap))

    # 2-D (x x y) blocked tile through the full mesh path: block_words
    # below the extended shard width (wde = wdl + 2 = 6) forces the
    # nine-view kernel grid; bw=4 also exercises word padding (6 -> 8)
    for bw in (2, 4):
        run2d = jax.jit(distributed.make_run(
            mesh, 8, y_axes=("data",), x_axis="model", p_force=0.03,
            depth=4, use_pallas=True, steps_per_launch=2,
            block_rows=8, block_words=bw))
        ok = bool((run2d(pd, 0) == ref).all())
        print(f"pallas 2-D bw={bw} depth=4 T=2: {ok}")
        if not ok:
            failures.append(("2x2", "xblock", bw))

    # batched ensemble lanes through the sharded pallas path
    p2 = bitplane.pack(jnp.asarray(
        byte_step.make_channel(H, W, density=0.4, seed=7)))
    pb = jnp.stack([p, p2])
    shb = NamedSharding(mesh, distributed.lattice_spec(
        ("data",), "model", batched=True))
    pdb = jax.device_put(pb, shb)
    refb = jnp.stack([bitplane.run_planes(pb[i], 4, p_force=0.03)
                      for i in range(2)])
    runb = jax.jit(distributed.make_run(
        mesh, 4, y_axes=("data",), x_axis="model", p_force=0.03,
        depth=4, use_pallas=True, steps_per_launch=2, batched=True))
    ok = bool((runb(pdb, 0) == refb).all())
    print(f"pallas batched depth=4 T=2: {ok}")
    if not ok:
        failures.append(("2x2", "batched"))

    # 3-axis mesh: y sharded over ("pod", "data") -- tuple-axes path
    mesh3 = distributed.make_mesh((2, 2, 2), ("pod", "data", "model"))
    sh3 = NamedSharding(mesh3, distributed.lattice_spec(
        ("pod", "data"), "model"))
    pd3 = jax.device_put(p, sh3)
    ref4 = bitplane.run_planes(p, 4, p_force=0.03)
    for overlap in (False, True):
        run3 = jax.jit(distributed.make_run(
            mesh3, 4, y_axes=("pod", "data"), x_axis="model", p_force=0.03,
            depth=2, use_pallas=True, steps_per_launch=2, overlap=overlap))
        ok = bool((run3(pd3, 0) == ref4).all())
        print(f"pallas 3-axis depth=2 T=2 overlap={overlap}: {ok}")
        if not ok:
            failures.append(("2x2x2", 2, 2, overlap))

    # depth > hl must be rejected (halo cannot outreach the neighbour)
    try:
        distributed.make_run(mesh, 17, y_axes=("data",), x_axis="model",
                             depth=17)(pd, 0)
        failures.append("depth>hl not rejected")
    except AssertionError:
        print("depth>hl rejected: True")

    assert not failures, failures
    print("ALL_OK")
""")


@pytest.mark.slow
def test_sharded_pallas_matches_single_device():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout


# ---------------------------------------------------------------------------
# Rule-parametric sharding: non-default rules through the full mesh path.
# ---------------------------------------------------------------------------

RULE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core import bitplane, distributed, rulespec

    failures = []
    mesh = distributed.make_mesh((2, 2), ("data", "model"))
    sh = NamedSharding(mesh, distributed.lattice_spec(("data",), "model"))
    for name, steps, depth, T in [("fhp3", 4, 2, 2), ("bml", 4, 2, 2)]:
        spec = rulespec.get_rule(name)
        state = spec.init_bytes(16, 128, 0.3, 5)
        p = bitplane.pack(jnp.asarray(state), n_planes=spec.n_planes)
        ref = rulespec.run_planes_rule(p, steps, spec)
        pd = jax.device_put(p, sh)
        for overlap in (False, True):
            run = jax.jit(distributed.make_run(
                mesh, steps, y_axes=("data",), x_axis="model", depth=depth,
                use_pallas=True, steps_per_launch=T, variant=name,
                overlap=overlap))
            ok = bool((run(pd, 0) == ref).all())
            print(f"{name} sharded pallas depth={depth} T={T} "
                  f"overlap={overlap}: {ok}")
            if not ok:
                failures.append((name, overlap))

    assert not failures, failures
    print("ALL_OK")
""")


def test_sharded_pallas_rule_variants():
    """fhp3 and bml over the 2x2-mesh shard_map + ppermute path must be
    bit-identical to the single-device rule stepper (tier-1: the rule
    threading through ``distributed`` is load-bearing for every rule)."""
    r = subprocess.run([sys.executable, "-c", RULE_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout
