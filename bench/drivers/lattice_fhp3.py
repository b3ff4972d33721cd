"""One large FHP-III lattice advanced call after call.

The ``lattice`` driver's workload, set-up, window, check and control
(``bench/drivers/lattice.py``, whose state maker, conserved counts,
bands and comparison it imports), for the collision-saturated FHP-III
rule on one chip: the program's entry is built for ``fhp3`` and the
window's last call is compared with ``bench/reference_fhp3.py``.

Traffic parameters (``kind: lattice_fhp3``): ``steps_per_call`` and
``check_bands``, as for ``lattice``.  Configuration: the ``lattice``
block, ``p_force``, ``guarantees.conserved``, and ``random_words``, the
random words a site-step of the rule draws: a program whose ``fhp3``
draws another number is another rule, and the run stops before
building anything.

Traced (``--trace 1``), the program's telemetry is on for set-up, and
the attributes of the kernel's ``kernel.build`` event (``rule``,
``random_words``, ``ops_per_word_step``, ``block_rows``,
``block_words``, ``steps_per_launch``) join ``readings["counts"]``; a
program that records no such event adds nothing.
"""
from __future__ import annotations

import contextlib
import time

import jax

from bench import harness, reference_fhp3
from bench.drivers.lattice import (COMPARE_BAND_ROWS, check_bands,
                                   conserved, cut_rows, initial_state,
                                   mismatched_words)
from bench.reference import band_rows

RULE = "fhp3"


def build(cell: harness.Cell):
    """The program's compiled single-chip entry for this lattice, its
    plan and the steps of a call."""
    from repro.core import distributed, rulespec
    from repro.kernels.fhp_step import ops
    cfg, lat = cell.config, cell.config["lattice"]
    spec = rulespec.get_rule(cfg["rule"])
    drawn = getattr(spec, "random_words", None)
    if drawn != cfg["random_words"]:
        raise RuntimeError(
            f"the program's {cfg['rule']!r} draws {drawn} random words a "
            f"site-step; this configuration's rule draws "
            f"{cfg['random_words']}: not the configured rule")
    bh, bw, T = ops.autotune_launch(lat["height"], lat["width"] // 32,
                                    n_planes=spec.n_planes)
    plan = {"block_rows": bh, "block_words": bw, "steps_per_launch": T}
    steps = int(cell.traffic["steps_per_call"])
    run, _ = distributed.make_ensemble_run(
        None, steps, variant=cfg["rule"], p_force=cfg.get("p_force", 0.0),
        use_pallas=True, steps_per_launch=T, block_rows=bh, block_words=bw)
    return jax.jit(run), plan, steps


def reference_bands(cfg: dict, steps: int, p_force: float, rows: int,
                    device):
    """``band(state, t0, r0)``: rows ``[r0, r0 + rows)`` of the FHP-III
    reference's ``steps`` steps of ``state`` from ``t0``, run on
    ``device`` from a band with an apron of ``steps`` rows each side."""
    h = cfg["lattice"]["height"]
    run = jax.jit(lambda ext, idx, t0: reference_fhp3.run_band(
        ext, idx, t0, steps=steps, rule=cfg["rule"], p_force=p_force))

    def band(state, t0, r0):
        idx = band_rows(h, r0, rows, steps)
        return run(cut_rows(state, r0 - steps, rows + 2 * steps, device),
                   jax.device_put(idx, device), t0)

    return band


@contextlib.contextmanager
def kernel_build(on: bool):
    """With ``on``, the program's default telemetry is on inside; the dict
    yielded then receives the attributes of the last ``kernel.build``
    event for this rule recorded inside (nothing without one)."""
    from repro import telemetry
    tel = telemetry.default()
    was, seen, attrs = tel.enabled, len(tel.events()), {}
    tel.enabled = was or on
    try:
        yield attrs
    finally:
        tel.enabled = was
    for e in tel.events()[seen:] if on else ():
        if e["name"] == "kernel.build" and e["attrs"]["rule"] == RULE:
            attrs.update(e["attrs"])


def run(cell: harness.Cell) -> harness.Outcome:
    cfg, lat = cell.config, cell.config["lattice"]
    if cfg["rule"] != RULE:
        raise ValueError(f"the lattice_fhp3 driver runs {RULE!r}, not "
                         f"{cfg['rule']!r}")
    p_force = float(cfg.get("p_force", 0.0))
    sites = lat["height"] * lat["width"]
    marks = [("start", time.perf_counter() - cell.started)]
    with jax.profiler.TraceAnnotation("bench.setup"), \
            kernel_build(cell.trace) as built:
        step, plan, steps = build(cell)
        marks.append(("plan", time.perf_counter() - cell.started))
        cell.log(f"plan: {cfg['rule']} {lat['height']}x{lat['width']} "
                 f"on {cell.chips} chip(s) {plan}, {steps} steps per call")
        state = initial_state(cfg, cell.seed)
        before = conserved(cfg, state)
        marks.append(("state", time.perf_counter() - cell.started))
        jax.block_until_ready(step(state, 0))
    setup_s = time.perf_counter() - cell.started
    marks.append(("warm call", setup_s))
    cell.log("setup: " + ", ".join(f"{k} at {v:.3f} s" for k, v in marks))

    profiler = harness.Profiler(cell.trace, f"{cell.workdir}/trace")
    calls = 0
    with profiler:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while True:
                prev = state
                with jax.profiler.TraceAnnotation("bench.call"):
                    state = jax.block_until_ready(step(prev, calls * steps))
                calls += 1
                window_s = time.perf_counter() - t0
                if window_s >= cell.seconds:
                    break
    rate = sites * steps * calls / window_s / 1e9
    cell.log(f"window: {calls} calls of {steps} steps in {window_s:.6f} s: "
             f"{rate:.4f} Gsite/s")
    peak = harness.memory_peak(cell.devices)
    after = conserved(cfg, state)

    rows = min(COMPARE_BAND_ROWS, lat["height"])
    r0s = check_bands(cell, rows)
    last_t0 = (calls - 1) * steps
    cell.log(f"check: the last call (t0 {last_t0}) in the {len(r0s)} bands "
             f"of {rows} rows starting at rows {r0s}")
    dev = cell.devices[0]
    want = reference_bands(cfg, steps, p_force, rows, dev)
    checks = [harness.Check("mismatched_words", mismatched_words(
                  lambda r0: cut_rows(state, r0, rows, dev),
                  lambda r0: want(prev, last_t0, r0), r0s), 0),
              harness.Check("conserved_drift",
                            sum(abs(a - b) for a, b in zip(after, before)),
                            0)]
    control = {}
    if cell.control:
        # The reference in the program's place with the configuration's
        # body force left out (for a rule without one, the call's last
        # step).
        broken = reference_bands(cfg, steps - (0 if p_force else 1), 0.0,
                                 rows, dev)
        control["mismatched_words"] = mismatched_words(
            lambda r0: broken(prev, last_t0, r0),
            lambda r0: want(prev, last_t0, r0), r0s)
    del prev, state
    counts = {"site_updates": sites * steps * calls,
              "ca_steps": steps * calls, "chips": cell.chips}
    counts.update(built)
    return harness.Outcome(
        end_to_end={"site_updates_per_s": rate, "setup_s": setup_s},
        checks=checks, attempted=calls, failed=0, memory_peak_bytes=peak,
        readings={"trace": profiler.reduce(), "counts": counts,
                  "spans": None},
        control=control)
