"""Sharded temporal-blocking sweep: the distributed FHP hot path as a
function of halo depth d, in-kernel steps-per-launch T, local-update
implementation (fused Pallas extended-shard kernel vs jnp), and
compute/communication ``overlap`` (interior/boundary split vs serial),
on a host-platform mesh of 4 fake devices (2x2 over ("data", "model")).

Every Pallas config is timed as an overlap on/off **pair** at the same
``(lattice, mesh, T, depth)`` (``--smoke`` pairs only the ``T == depth``
configs to hold the time budget), recording the measured ratio
``overlap_speedup_measured`` next to the model's
``overlap_speedup_modeled``.  Wall-clock here is only meaningful on a
real multi-chip backend (on CPU the Pallas kernel interprets and
ppermute is a memcpy, so the launches serialize and the measured ratio
shows split *overhead* only); the durable output is the *model* columns
persisted to BENCH_kernel.json -- modeled HBM bytes/site/step of the
extended-shard launches, exchange count and ICI bytes per step, the
overlap round time ``max(t_exchange, t_interior) + t_boundary`` -- plus
the joint (block_rows, block_words, T, depth, overlap) point the
autotuner picks.  The sweep, model records included, runs in a child
process: the fake-device XLA_FLAGS never leak into the parent, and the
parent never touches JAX, so on a TPU host the child can hold the chips.

    PYTHONPATH=src python -m benchmarks.bench_distributed          # full
    PYTHONPATH=src python -m benchmarks.bench_distributed --smoke  # tiny/CI
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import Dict, List

MESH = (2, 2)

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
    import json, time
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core import byte_step, bitplane, distributed
    from repro.kernels.fhp_step.ops import pick_block_rows_extended
    from repro.roofline.analysis import sharded_fhp_traffic

    from benchmarks.bench_distributed import _model_records

    smoke = sys.argv[1] == "smoke"
    for rec in _model_records(smoke):
        print("RECORD " + json.dumps(rec))
    h, w = (32, 512) if smoke else (128, 2048)
    steps = 8 if smoke else 16
    depths = (1, 2, 4) if smoke else (1, 2, 4, 8)
    mesh = distributed.make_mesh((2, 2), ("data", "model"))
    hl, wdl = h // 2, w // 32 // 2
    planes = bitplane.pack(jnp.asarray(
        byte_step.make_channel(h, w, density=0.3, seed=0)))
    sh = NamedSharding(mesh, distributed.lattice_spec(("data",), "model"))
    pd = jax.device_put(planes, sh)

    def timed(fn):
        fn(pd, 0)[0].block_until_ready()       # compile + warm-up
        t0 = time.perf_counter()
        fn(pd, 0)[0].block_until_ready()
        return time.perf_counter() - t0

    for depth in depths:
        assert steps % depth == 0, (steps, depth)
        t_sweep = sorted({1, depth} if smoke else
                         {t for t in (1, 2, 4, 8) if t <= depth})
        for use_pallas, impl in ((False, "jnp-sharded"),
                                 (True, "pallas-sharded")):
            for T in (t_sweep if use_pallas else [1]):
                # Overlap on/off pairs at the same (lattice, mesh, T,
                # depth): every Pallas config in full mode; --smoke pairs
                # only T == depth to hold the time budget.
                ovs = [False] + ([True] if use_pallas and
                                 (not smoke or T == depth) else [])
                dt_serial = None
                for overlap in ovs:
                    kw = dict(y_axes=("data",), x_axis="model",
                              p_force=0.01, depth=depth,
                              use_pallas=use_pallas)
                    if use_pallas:
                        kw["steps_per_launch"] = T
                        kw["overlap"] = overlap
                    run = jax.jit(distributed.make_run(mesh, steps, **kw))
                    dt = timed(run)
                    if not overlap:
                        dt_serial = dt
                    rec = {"bench": "distributed", "impl": impl,
                           "backend": jax.default_backend(), "mesh": [2, 2],
                           "depth": depth, "T": T, "B": 1,
                           "overlap": overlap,
                           "sites_per_sec": h * w * steps / dt,
                           "steps": steps, "lattice": [h, w],
                           "smoke": smoke, "structural": False,
                           "model_exchanges_per_step": 1.0 / depth}
                    if use_pallas:
                        bh = pick_block_rows_extended(wdl + 2, steps=T)
                        m = sharded_fhp_traffic(hl, wdl, depth=depth, T=T,
                                                block_rows=bh,
                                                overlap=overlap)
                        rec.update(
                            block_rows=bh,
                            model_hbm_bytes_per_site=m["hbm_bytes_per_site_step"],
                            model_ici_bytes_per_site=m["ici_bytes_per_site_step"],
                            model_launches_per_step=m["launches_per_step"],
                            model_total_s_per_site=m["total_s_per_site"])
                        if overlap:
                            rec["overlap_speedup_modeled"] = \
                                m["overlap_speedup_modeled"]
                            rec["overlap_speedup_measured"] = dt_serial / dt
                    print("RECORD " + json.dumps(rec))
    print("BENCH_DONE")
""")


def _model_records(smoke: bool) -> List[Dict]:
    """Structural records (no timing): the joint autotuner
    point and its modeled sharded traffic for representative shard sizes."""
    from repro.kernels.fhp_step.ops import autotune_launch
    from repro.roofline.analysis import sharded_fhp_traffic
    shards = [(256, 32)] if smoke else [(256, 32), (1024, 128), (8192, 2048)]
    out = []
    for hl, wdl in shards:
        bh, bw, T, depth, overlap = autotune_launch(hl, wdl, max_depth=16)
        m = sharded_fhp_traffic(hl, wdl, depth=depth, T=T, block_rows=bh,
                                block_words=bw, overlap=overlap)
        m_ov = sharded_fhp_traffic(hl, wdl, depth=depth, T=T, block_rows=bh,
                                   block_words=bw, overlap=True)
        out.append({
            "bench": "distributed", "impl": "pallas-sharded",
            "backend": None, "shard": [hl, wdl], "block_rows": bh,
            "block_words": bw,
            "T": T, "depth": depth, "B": 1, "overlap": overlap,
            "sites_per_sec": None,
            "lattice": None, "smoke": smoke, "structural": True,
            "autotuned": True,
            "model_hbm_bytes_per_site": m["hbm_bytes_per_site_step"],
            "model_ici_bytes_per_site": m["ici_bytes_per_site_step"],
            "model_exchanges_per_step": m["exchanges_per_step"],
            "model_launches_per_step": m["launches_per_step"],
            "model_total_s_per_site": m["total_s_per_site"],
            "overlap_speedup_modeled": m_ov["overlap_speedup_modeled"]})
    return out


def main(smoke: bool = False) -> List[Dict]:
    """Run the sweep in a child process and collect its records.  This
    process never touches JAX: on a TPU host the child is the one
    process that holds the chips."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, "smoke" if smoke else "full"],
        capture_output=True, text=True, timeout=1800, env=env)
    if r.returncode != 0 or "BENCH_DONE" not in r.stdout:
        # Fail loudly: silently returning only the structural rows would
        # leave BENCH_kernel.json without timed distributed records while
        # CI stays green, breaking the never-empty-trajectory guarantee.
        raise RuntimeError("bench_distributed subprocess failed:\n"
                           f"{r.stdout}\n{r.stderr}")
    records = []
    for line in r.stdout.splitlines():
        if not line.startswith("RECORD "):
            continue
        rec = json.loads(line[len("RECORD "):])
        records.append(rec)
        if rec["structural"]:
            print(f"autotune(shard={rec['shard']}),(bh={rec['block_rows']} "
                  f"bw={rec['block_words']} T={rec['T']} d={rec['depth']} "
                  f"ov={int(rec['overlap'])}),config")
            print(f"model_hbm_bytes_per_site(shard={rec['shard']}),"
                  f"{rec['model_hbm_bytes_per_site']:.4f},B")
            print(f"overlap_speedup_modeled(shard={rec['shard']}),"
                  f"{rec['overlap_speedup_modeled']:.4f},x")
        else:
            ov = "_ov" if rec.get("overlap") else ""
            print(f"{rec['impl']}_d{rec['depth']}_T{rec['T']}{ov}_sps,"
                  f"{rec['sites_per_sec']:.3e},sites/s")
    return records


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
