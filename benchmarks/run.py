"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # full sweep
    PYTHONPATH=src python -m benchmarks.run --smoke    # tiny CI profile
    PYTHONPATH=src python -m benchmarks.run --smoke --profile  # + tracing

Table 1  -> bench_table1  (Mups per implementation tier)
Fig. 9   -> bench_fig9    (speedup over sequential analogue + v5e projection)
Fig. 10  -> bench_fig10   (USD/Mups, Watt/Mups)
kernel   -> bench_kernel  (fused-kernel structure: blocks, VMEM, B/site)
temporal -> bench_temporal (steps-per-launch x ensemble-lane sweep)
distributed -> bench_distributed ((depth, T, use_pallas) sharded sweep)
scenarios -> bench_scenarios (registered geometries through the sharded
             static-geometry path; bit-exactness + exchange-byte model)
serve    -> bench_serve   (continuous-batching job engine under open-loop
             load, with/without seeded faults; jobs/s, frame latency
             percentiles, recovery overhead, bit-exact recovery gate)
observables -> bench_observables (in-kernel fused moments vs post-hoc
             re-streaming, bit-exactness gate; disabled-telemetry no-op
             cost)

``--profile`` turns the telemetry layer on for the sweep (JSONL sink
``BENCH_telemetry.jsonl``, summary appended to the output JSON) and
wraps the record-producing benches in ``jax.profiler.trace`` writing to
``bench_trace/`` -- the ``telemetry.span`` names land on the HLO via
``jax.named_scope``, so kernel/exchange/boundary regions are findable
in the trace viewer.

The kernel-shaped benches (kernel, temporal, distributed) also return
machine-readable records; this driver persists them to
``BENCH_kernel.json`` -- site-updates/sec per ``(backend, block_rows, T,
B)`` -- so the perf trajectory is tracked across PRs.  Records with
``"structural": true`` carry model-only columns (no wall clock --
``sites_per_sec``/``lattice`` are null by design); every impl also emits
at least one real timed record, even under ``--smoke``, so the perf
trajectory is never empty.  A top-level ``"headline"`` block summarises
the best *timed* single-device and sharded configs (sites/s) so the
cross-PR trajectory is one lookup, not a records scan.  ``--smoke`` runs
the record-producing benches on tiny lattices (interpret mode on CPU) so
CI gets the same JSON shape in seconds.
"""
from __future__ import annotations

import json
import platform
import sys
import time

BENCH_JSON = "BENCH_kernel.json"

_HEADLINE_KEYS = ("bench", "impl", "backend", "lattice", "block_rows",
                  "block_words", "T", "B", "depth", "sites_per_sec", "smoke")


def _headline(records):
    """Best *timed* sites/s per tier -- the single number the cross-PR
    perf trajectory tracks.  Single-device = the fused kernel benches
    (kernel / temporal); sharded = the mesh benches (distributed /
    scenarios).  Structural (model-only) rows never qualify."""
    timed = [r for r in records
             if not r.get("structural") and r.get("sites_per_sec")]

    def best(benches):
        rows = [r for r in timed if r.get("bench") in benches
                and "pallas" in str(r.get("impl", ""))]
        if not rows:
            return None
        top = max(rows, key=lambda r: r["sites_per_sec"])
        return {k: top.get(k) for k in _HEADLINE_KEYS if k in top}

    # The modeled compute/communication-overlap ratio at the best
    # overlapped sharded point (bench_distributed pairs every overlap=True
    # record with its serial twin; the measured ratio sits on the record).
    ov = [r for r in records if r.get("overlap")
          and r.get("overlap_speedup_modeled") is not None]
    ov_best = max((r["overlap_speedup_modeled"] for r in ov), default=None)

    # The serve trajectory: clean-profile throughput/latency next to the
    # faulted profile's recovery tax (bench_serve asserts bit-exact
    # recovery before emitting, so a present record implies the gate).
    srv = {r.get("profile"): r for r in records
           if r.get("bench") == "serve"}
    serve = None
    if "clean" in srv and "faulted" in srv:
        c, f = srv["clean"], srv["faulted"]
        serve = {"impl": c.get("impl"), "lattice": c.get("lattice"),
                 "slots": c.get("slots"), "jobs": c.get("jobs"),
                 "jobs_per_sec": c.get("jobs_per_sec"),
                 "frame_lat_p99_s": c.get("frame_lat_p99_s"),
                 "recovery_overhead_pct": f.get("recovery_overhead_pct"),
                 "straggler_tax_pct": f.get("straggler_tax_pct"),
                 "rollbacks": f.get("rollbacks"),
                 "recovered_bit_exact": f.get("recovered_bit_exact"),
                 "smoke": c.get("smoke")}
        if "overload" in srv:
            o = srv["overload"]
            # The SLO trajectory under offered load >> capacity: gold's
            # p99 frame latency vs its SLO, bronze's completions (the
            # non-starvation bound), typed sheds/rejects, and fairness.
            serve["overload"] = {
                "p99_frame_latency": o.get("p99_frame_latency"),
                "hi_frame_slo_s": o.get("hi_frame_slo_s"),
                "lo_done": o.get("lo_done"),
                "shed_count": o.get("shed_count"),
                "rejected": o.get("rejected"),
                "preemptions": o.get("preemptions"),
                "jain_fairness": o.get("jain_fairness")}

    return {"best_single_device": best(("kernel", "temporal")),
            "best_sharded": best(("distributed", "scenarios")),
            "overlap_speedup_modeled": ov_best,
            "serve": serve}


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    profile = "--profile" in argv
    from benchmarks import (bench_distributed, bench_fig9, bench_fig10,
                            bench_kernel, bench_observables,
                            bench_scenarios, bench_serve, bench_table1,
                            bench_temporal)
    import contextlib

    import jax

    from repro import telemetry
    from repro.launch import compile_cache
    compile_cache.enable()
    records = []

    def run(name, mod):
        print(f"== {name} ==")
        t0 = time.time()
        records.extend(mod.main(smoke=smoke) or [])
        print(f"-- {name} done in {time.time() - t0:.1f}s --\n")

    # These three sweep in a child process each, which needs the chips:
    # run them before this process touches the backend and holds them.
    for name, mod in [("distributed", bench_distributed),
                      ("scenarios", bench_scenarios),
                      ("serve", bench_serve)]:
        run(name, mod)
    trace_ctx = contextlib.nullcontext()
    if profile:
        telemetry.configure(enabled=True,
                            jsonl_path="BENCH_telemetry.jsonl")
        trace_ctx = jax.profiler.trace("bench_trace")
    paper_benches = [] if smoke else [
        ("table1", bench_table1), ("fig9", bench_fig9),
        ("fig10", bench_fig10)]
    for name, mod in paper_benches:
        print(f"== {name} ==")
        t0 = time.time()
        mod.main()
        print(f"-- {name} done in {time.time() - t0:.1f}s --\n")
    with trace_ctx:
        for name, mod in [("kernel", bench_kernel),
                          ("temporal", bench_temporal),
                          ("observables", bench_observables)]:
            run(name, mod)
    out = {"meta": {"backend": jax.default_backend(),
                    "jax": jax.__version__,
                    "python": platform.python_version(),
                    "smoke": smoke},
           "headline": _headline(records),
           "records": records}
    if profile:
        out["telemetry"] = telemetry.summary()
        telemetry.default().flush()
    with open(BENCH_JSON, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {len(records)} records -> {BENCH_JSON}")


if __name__ == "__main__":
    main()
