"""Kernel-level microbenchmark: per-step cost of the fused FHP update as a
function of block height and RNG placement, plus the VMEM footprint the
BlockSpec tiling claims and the (block_rows, steps_per_launch) point the
autotuner picks.  Wall-clock here is the *oracle* path (interpret Pallas
measures Python); the structural numbers (VMEM bytes, HBM traffic per
site) are the TPU-relevant output.  ``bench_temporal`` sweeps the
temporal-blocking axis itself.
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

H, W = 1024, 4096
WD = W // 32
SMOKE_H, SMOKE_W = 64, 1024


def main(smoke: bool = False) -> List[Dict]:
    backend = jax.default_backend()
    h, w = (SMOKE_H, SMOKE_W) if smoke else (H, W)
    wd_full = w // 32
    steps = 2 if smoke else 5
    planes = bitplane.pack(jnp.asarray(
        byte_step.make_channel(h, w, density=0.3, seed=0)))
    records: List[Dict] = []

    @jax.jit
    def oracle(p):
        return bitplane.run_planes(p, steps, p_force=0.01)

    oracle(planes).block_until_ready()
    t0 = time.perf_counter()
    oracle(planes).block_until_ready()
    dt = time.perf_counter() - t0
    print("metric,value,unit")
    print(f"oracle_step,{dt / steps * 1e3:.2f},ms")
    mups = h * w * steps / dt / 1e6
    print(f"oracle_mups,{mups:.1f},Mups")
    records.append({"bench": "kernel", "impl": "oracle-jnp",
                    "backend": backend, "block_rows": None, "T": 1, "B": 1,
                    "sites_per_sec": mups * 1e6, "steps": steps,
                    "lattice": [h, w], "smoke": smoke, "structural": False})

    # Real timed record for the pallas impl (interpret mode off-TPU: the
    # number measures Python there, but the perf trajectory per impl must
    # never be empty, and on TPU this is the headline row).
    bh_run = pick_block_rows(h, w // 32)
    fn = jax.jit(lambda p: run_pallas(p, steps, p_force=0.01,
                                      block_rows=bh_run))
    fn(planes).block_until_ready()
    t0 = time.perf_counter()
    fn(planes).block_until_ready()
    dt = time.perf_counter() - t0
    mups = h * w * steps / dt / 1e6
    print(f"pallas_mups,{mups:.1f},Mups")
    records.append({"bench": "kernel", "impl": "pallas-fused",
                    "backend": backend, "block_rows": bh_run, "T": 1, "B": 1,
                    "sites_per_sec": mups * 1e6, "steps": steps,
                    "lattice": [h, w], "smoke": smoke, "structural": False})

    for wd in (128, 512, 2048, wd_full):
        bh = pick_block_rows(h, wd)
        bh_t, bw_t, t_launch = autotune_launch(h, wd)
        print(f"block_rows(wd={wd}),{bh},rows")
        print(f"vmem_bytes(wd={wd}),{vmem_bytes(bh, wd)},B")
        print(f"autotune(wd={wd}),(bh={bh_t} bw={bw_t} T={t_launch}),config")
        # Structural record for a hypothetical per-device row width wd --
        # no lattice/wall-clock fields, they would contradict wd.
        records.append({"bench": "kernel", "impl": "pallas-fused",
                        "backend": backend, "wd": wd, "block_rows": bh_t,
                        "block_words": bw_t,
                        "T": t_launch, "B": 1, "sites_per_sec": None,
                        "vmem_bytes": vmem_bytes(bh_t, wd, t_launch, bw_t),
                        "model_hbm_bytes_per_site":
                            hbm_bytes_per_site(bh_t, t_launch, bw_t, wd),
                        "lattice": None, "smoke": smoke,
                        "structural": True})
    # HBM traffic of the fused kernel: one read + one write of 8 planes
    print(f"hbm_bytes_per_site,{2 * 8 * 4 / 32.0},B")
    print(f"hbm_bytes_per_site_unfused,{2 * 2 * 8 * 4 / 32.0},B")
    bh_t, bw_t, t_launch = autotune_launch(h, wd_full)
    print(f"hbm_bytes_per_site_temporal,"
          f"{hbm_bytes_per_site(bh_t, t_launch, bw_t, wd_full):.4f},B")
    return records


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
