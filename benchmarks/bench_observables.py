"""Fused in-kernel observables vs post-hoc re-streaming, plus the
telemetry no-op overhead gate.

The fused path records the rule's MomentSpec reductions (mass, momentum,
per-species counts, exclusivity) *inside* the temporal-blocked kernel at
a dense cadence k=1: the moving state is already in VMEM at every
intermediate step, so a dense time series costs popcounts, not HBM round
trips.  The post-hoc baseline gets the same series the only way it can:
chop the run into 1-step launches (one HBM round trip each) and popcount
the streamed-out state after every one.  Both paths are bit-identical by
construction (``rulespec.compute_moments`` is the reference the kernel
accumulation is gated against); this bench asserts that and times them.

Off-TPU the kernel runs in interpret mode, so the wall-clock comparison
*inverts*: there is no VMEM/HBM hierarchy to save traffic in, and the
kernel's SWAR popcount emulates as ~6 scalar ops per word per term while
the post-hoc ``jax.lax.population_count`` is one vectorized XLA op.  The
honest currency off-TPU is the memory model: the record carries modeled
HBM bytes/site for both paths (``hbm_fused_b_site`` vs
``hbm_posthoc_b_site`` -- the post-hoc path re-streams the full plane
stack every step *plus* re-reads it to reduce, the fused path adds only
the tiny per-block moments write), and asserts the fused path is cheaper
there.  On TPU the timed ``fused_vs_posthoc_speedup`` is the headline;
off-TPU it is recorded but expected < 1 (see the interpret-mode caveat
in EXPERIMENTS.md stage 10).

The second record prices the telemetry layer's disabled path: library
code is instrumented unconditionally (``telemetry.span`` at every layer
boundary), so the no-op span must be nanoseconds.  The record carries
the measured per-call cost and expresses it as a fraction of one fused
CA step (``telemetry_overhead_frac``) at ~10 calls/round -- CI asserts
the fraction stays negligible.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import bitplane, byte_step, rulespec
from repro.kernels.fhp_step.ops import (hbm_bytes_per_site,
                                        pick_block_rows, run_pallas)

H, W = 256, 2048
SMOKE_H, SMOKE_W = 32, 512


def _time(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def main(smoke: bool = False) -> List[Dict]:
    backend = jax.default_backend()
    h, w = (SMOKE_H, SMOKE_W) if smoke else (H, W)
    steps, t_launch = (4, 2) if smoke else (16, 4)
    spec = rulespec.get_rule("fhp2")
    ms = rulespec.moment_spec(spec)
    planes = bitplane.pack(jnp.asarray(
        byte_step.make_channel(h, w, density=0.3, seed=0)))
    bh = pick_block_rows(h, w // 32)
    records: List[Dict] = []
    print("metric,value,unit")

    # --- fused k=1: dense series from VMEM, steps/T launches ----------
    fused = jax.jit(lambda p: run_pallas(
        p, steps, p_force=0.01, steps_per_launch=t_launch,
        block_rows=bh, moments_every=1))
    dt_fused, (out_f, mom_f) = _time(fused, planes)

    # --- post-hoc: 1-step launches, re-stream + popcount every step ---
    def posthoc(p):
        moms = []
        for j in range(steps):
            p = run_pallas(p, 1, p_force=0.01, t0=j, block_rows=bh)
            moms.append(rulespec.compute_moments(p, ms))
        return p, jnp.stack(moms, axis=-2)

    posthoc = jax.jit(posthoc)
    dt_post, (out_p, mom_p) = _time(posthoc, planes)

    bit_exact = bool((out_f == out_p).all()) and bool((mom_f == mom_p).all())
    assert bit_exact, "fused moments diverge from post-hoc popcounts"
    speedup = dt_post / dt_fused
    mups = h * w * steps / dt_fused / 1e6

    # Backend-independent memory model: fused T-step launches with the
    # per-block moments write vs 1-step launches (T=1 forced by the
    # dense cadence) plus a full re-read per step for the reduction.
    mom_words = t_launch * ms.n_moments
    hbm_fused = hbm_bytes_per_site(bh, t_launch, width_words=w // 32,
                                   moments_words=mom_words)
    hbm_posthoc = (hbm_bytes_per_site(bh, 1, width_words=w // 32)
                   + spec.n_planes * 4 / 32.0)
    assert hbm_fused < hbm_posthoc, (hbm_fused, hbm_posthoc)

    print(f"fused_k1_s,{dt_fused:.4f},s")
    print(f"posthoc_restream_s,{dt_post:.4f},s")
    print(f"fused_vs_posthoc_speedup,{speedup:.2f},x")
    print(f"hbm_fused_b_site,{hbm_fused:.2f},B")
    print(f"hbm_posthoc_b_site,{hbm_posthoc:.2f},B")
    records.append({
        "bench": "observables", "impl": "pallas-fused-moments",
        "backend": backend, "lattice": [h, w], "T": t_launch, "B": 1,
        "block_rows": bh, "steps": steps, "moments_every": 1,
        "moment_rows": list(ms.names), "sites_per_sec": mups * 1e6,
        "fused_s": dt_fused, "posthoc_s": dt_post,
        "fused_vs_posthoc_speedup": speedup,
        "hbm_fused_b_site": hbm_fused,
        "hbm_posthoc_b_site": hbm_posthoc,
        "fused_cheaper_modeled": hbm_fused < hbm_posthoc,
        "bit_exact": bit_exact,
        "smoke": smoke, "structural": False})

    # --- disabled-telemetry no-op cost --------------------------------
    tel = telemetry.Telemetry(enabled=False)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tel.span("noop"):
            pass
        tel.count("noop")
    dt_ins = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        pass
    dt_bare = time.perf_counter() - t0
    per_call_s = max(0.0, dt_ins - dt_bare) / (2 * n)
    step_s = dt_fused / steps
    # ~10 instrumented boundaries fire per serve round (admit, kernel,
    # exchange, audit, frames, retire, checkpoint + counters); price
    # them against one CA step of the *smallest* timed lattice -- the
    # most adverse ratio this suite produces.
    frac = per_call_s * 10 / step_s
    print(f"telemetry_noop_ns,{per_call_s * 1e9:.0f},ns")
    print(f"telemetry_overhead_frac,{frac:.6f},frac")
    records.append({
        "bench": "observables", "impl": "telemetry-noop",
        "backend": backend, "lattice": [h, w],
        "telemetry_noop_ns": per_call_s * 1e9,
        "telemetry_overhead_frac": frac,
        "smoke": smoke, "structural": True,
        "sites_per_sec": None})
    return records


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
