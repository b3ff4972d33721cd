"""Temporal-blocking sweep: site-updates/sec of the fused FHP kernel as a
function of steps-per-launch T (and ensemble width B), plus the modeled
HBM traffic per site update each T implies, plus a 1-D vs 2-D
(x x y) blocking comparison on the same lattice (the x-block sweep runs
under ``--smoke`` too, so CI tracks the 2-D grid).

On a TPU the wall-clock column is the headline number (the kernel is
memory-bound, so Mups should scale with the modeled traffic cut).  On CPU
the kernel runs in Pallas interpret mode, which measures Python -- so the
smoke profile keeps shapes tiny and the *model* columns (bytes/site/step,
VMEM fit, chosen block) are the meaningful output; the jnp oracle row
gives a real wall-clock anchor.

    PYTHONPATH=src python -m benchmarks.bench_temporal          # full
    PYTHONPATH=src python -m benchmarks.bench_temporal --smoke  # tiny/CI
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.core import bitplane, byte_step
from repro.kernels.fhp_step.ops import (autotune_launch, hbm_bytes_per_site,
                                        pick_block_rows, run_pallas,
                                        vmem_bytes)

FULL_SHAPE = (1024, 4096)      # H, W -- matches bench_kernel's lattice
SMOKE_SHAPE = (32, 1024)
T_SWEEP = (1, 2, 4, 8)
B_SWEEP = (1, 4)
XBLOCK_T = 4                   # fused steps for the 1-D vs 2-D comparison


def _time(fn, *args) -> float:
    fn(*args).block_until_ready()        # compile + warm-up
    t0 = time.perf_counter()
    fn(*args).block_until_ready()
    return time.perf_counter() - t0


def main(smoke: bool = False) -> List[Dict]:
    backend = jax.default_backend()
    h, w = SMOKE_SHAPE if smoke else FULL_SHAPE
    steps = 4 if smoke else 50
    wd = w // 32
    planes = bitplane.pack(jnp.asarray(
        byte_step.make_channel(h, w, density=0.3, seed=0)))
    records: List[Dict] = []
    print("metric,value,unit")

    # Wall-clock anchor: the pure-jnp oracle stepper (compiled, not
    # interpreted, on every backend).
    oracle = jax.jit(lambda p: bitplane.run_planes(p, steps, p_force=0.01))
    dt = _time(oracle, planes)
    mups = h * w * steps / dt / 1e6
    print(f"oracle_mups,{mups:.2f},Mups")
    records.append({"bench": "temporal", "impl": "oracle-jnp",
                    "backend": backend, "block_rows": None, "T": 1, "B": 1,
                    "sites_per_sec": mups * 1e6, "steps": steps,
                    "lattice": [h, w], "smoke": smoke, "structural": False})

    bh_auto, bw_auto, t_auto = autotune_launch(h, wd)
    print(f"autotune_block_rows,{bh_auto},rows")
    print(f"autotune_block_words,{bw_auto},words")
    print(f"autotune_steps_per_launch,{t_auto},steps")

    for t_launch in T_SWEEP:
        if t_launch > steps:
            # run_pallas would route everything through the single-step
            # remainder path; recording that as a T-row would be a lie.
            print(f"pallas_T{t_launch},skipped,steps<{t_launch}")
            continue
        try:
            bh = pick_block_rows(h, wd, steps=t_launch)
        except ValueError:
            print(f"pallas_T{t_launch},skipped,no-valid-block")
            continue
        for b in B_SWEEP:
            p_in = planes if b == 1 else jnp.broadcast_to(
                planes, (b, *planes.shape))
            fn = jax.jit(lambda p, _t=t_launch, _bh=bh: run_pallas(
                p, steps, p_force=0.01, steps_per_launch=_t, block_rows=_bh))
            dt = _time(fn, p_in)
            mups = b * h * w * steps / dt / 1e6
            print(f"pallas_T{t_launch}_B{b}_mups,{mups:.2f},Mups")
            records.append({
                "bench": "temporal", "impl": "pallas-fused",
                "backend": backend, "block_rows": bh, "T": t_launch, "B": b,
                "sites_per_sec": mups * 1e6, "steps": steps,
                "lattice": [h, w], "smoke": smoke, "structural": False,
                "model_hbm_bytes_per_site": hbm_bytes_per_site(bh, t_launch),
                "vmem_bytes": vmem_bytes(bh, wd, t_launch)})
        print(f"model_hbm_bytes_per_site_T{t_launch},"
              f"{hbm_bytes_per_site(bh, t_launch):.4f},B")

    # 1-D vs 2-D blocking on the SAME lattice: the x-blocked tile pays a
    # T-word apron per side but frees VMEM for deeper T on wide shards;
    # both rows are timed so BENCH_kernel.json carries the comparison.
    t_x = min(XBLOCK_T, steps)
    bh_x = pick_block_rows(h, wd, steps=t_x)
    sps_1d = None
    for bw in (wd, max(t_x, wd // 4)):
        fn = jax.jit(lambda p, _bw=bw: run_pallas(
            p, steps, p_force=0.01, steps_per_launch=t_x,
            block_rows=bh_x, block_words=_bw))
        dt = _time(fn, planes)
        sps = h * w * steps / dt
        tag = "1d" if bw == wd else "2d"
        if bw == wd:
            sps_1d = sps
        rec = {"bench": "temporal", "impl": "pallas-fused",
               "backend": backend, "block_rows": bh_x, "block_words": bw,
               "xblock": tag, "T": t_x, "B": 1,
               "sites_per_sec": sps, "steps": steps,
               "lattice": [h, w], "smoke": smoke, "structural": False,
               "model_hbm_bytes_per_site":
                   hbm_bytes_per_site(bh_x, t_x, bw, wd),
               "vmem_bytes": vmem_bytes(bh_x, wd, t_x, bw)}
        if tag == "2d" and sps_1d:
            rec["speedup_vs_1d"] = sps / sps_1d
        records.append(rec)
        print(f"pallas_xblock_{tag}_bw{bw}_mups,{sps / 1e6:.2f},Mups")
        print(f"vmem_bytes_xblock_{tag}_bw{bw},"
              f"{vmem_bytes(bh_x, wd, t_x, bw)},B")
    return records


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
