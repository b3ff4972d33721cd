"""Simulation jobs arriving open loop at the serve engine.

Traffic parameters (``bench/traffic/<mix>.json``, ``kind: open_loop``):
``scenario``, ``rate_per_s`` (offered jobs per second), ``steps_mix``
(CA steps -> share of jobs), ``density`` ([low, high] fill),
``frame_every`` (CA steps between a job's frames), ``check_jobs`` (jobs
compared with the reference after the window), ``drain_cap_s`` and
``pattern_seed`` (fixes the arrival pattern).
Configuration (``engine`` block): the ``CAServeEngine`` settings.

Every seed is offered the same work at the same times: n = rate x
seconds jobs, whose step counts are the mix's shares of n and whose
inter-arrival gaps are the n quantiles of an exponential of that rate (a
Poisson process's gaps), both in an order fixed by the traffic file's
``pattern_seed``, like a recorded arrival trace.  The seed draws what
each job simulates: its fill, one of n evenly spaced quantiles of the
density range, and the seed of its initial state.  (Arrival orders
drawn from the seed moved the p95 latency by a quarter between seeds,
against a few hundredths between runs of one seed.)

Set-up builds the engine and drains one job per slot, which compiles
every program the window uses.  The window submits each job when it is
due, ticking the engine between arrivals; after ``--seconds`` arrivals
stop and the engine drains until every job due in the window has
finished (or ``drain_cap_s`` passes: a job still unfinished, shed or
refused is a miss).  A job's latency runs from its due time to its final
frame; its median over every job due in the window is the end-to-end
metric.  The window holds a few tens of jobs (the engine sustains
about one a second), too few for a 95th percentile, which is the
second-largest latency there: it is read as a per-layer metric.  Frame
gaps, between consecutive frames of one job, are read as
a per-layer metric: their p95 falls either among the ordinary 16-round
gaps or among those stalled by an admission or a checkpoint, so it
swings by a fifth between runs of one arrival pattern.

Correctness: a sample of finished jobs drawn from the seed, the longest
among them, is replayed by the plain reference from the job's initial
state at its admission time; every frame and the final state must match.
"""
from __future__ import annotations

import collections
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, reference, stats


def schedule(traffic: dict, seed: int, seconds: float) -> list:
    """``[{due, steps, density, seed}]`` in due order (see module doc)."""
    pattern = np.random.default_rng(int(traffic["pattern_seed"]))
    rate = float(traffic["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    pattern.shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    mix = sorted((int(k), float(v)) for k, v in traffic["steps_mix"].items())
    exact = [share * n for _, share in mix]
    counts = [int(x) for x in exact]
    by_remainder = sorted(range(len(mix)), key=lambda i: counts[i] - exact[i])
    for i in by_remainder[:n - sum(counts)]:
        counts[i] += 1
    steps = np.repeat([s for s, _ in mix], counts)
    pattern.shuffle(steps)
    rng = np.random.default_rng(seed)
    lo, hi = traffic["density"]
    dens = lo + (hi - lo) * (rng.permutation(n) + 0.5) / n
    seeds = rng.integers(0, 2 ** 31 - 1, size=n)
    return [{"due": float(due[i]), "steps": int(steps[i]),
             "density": float(dens[i]), "seed": int(seeds[i])}
            for i in range(n)]


def latencies(jobs: dict, walls: dict, due: dict) -> tuple:
    """``(latencies of finished jobs, misses)`` over the jobs ``due``
    names: a job's latency runs from its due time to its last frame."""
    from repro.serve import DONE
    done, misses = [], 0
    for rid, t_due in due.items():
        job = jobs.get(rid)
        if job is None or job.status != DONE or not walls.get(rid):
            misses += 1
        else:
            done.append(max(walls[rid]) - t_due)
    return done, misses


def frame_walls(frame_log: list, rids) -> dict:
    out = collections.defaultdict(list)
    for e in frame_log:
        if e["rid"] in rids:
            out[e["rid"]].append(e["wall"])
    return out


def compare_jobs(cell: harness.Cell, jobs: list, p_force: float) -> dict:
    """Replay ``jobs`` with the reference at ``p_force``; count the words
    of final states and the frames that differ from what the engine
    served."""
    eng = cell.config["engine"]
    h, w = eng["height"], eng["width"]
    every = int(cell.traffic["frame_every"])
    rows = jnp.arange(h, dtype=jnp.uint32)[:, None]
    chunk = jax.jit(lambda s, t0: reference.run_rows(
        s, t0, rows, every, "fhp2", p_force))
    diff = jax.jit(lambda a, b: jnp.sum(a != b, dtype=jnp.int32))
    words = frames = 0
    for job in jobs:
        ov = job.overrides
        state = jnp.asarray(reference.cylinder_planes(
            h, w, ov["density"], ov["seed"]))
        if len(job.segments) != 1:
            frames += job.steps // every
            continue
        t0 = job.segments[0][0]
        for k in range(1, job.steps // every + 1):
            state = chunk(state, t0 + (k - 1) * every)
            want = dict(reference.fhp2_frame(state), t=t0 + k * every)
            got = {key: job.frames.get(k * every, {}).get(key)
                   for key in want}
            frames += int(got != want)
        words += int(diff(state, jnp.asarray(job.result)))
    return {"mismatched_words": words, "frame_mismatches": frames}


def run(cell: harness.Cell) -> harness.Outcome:
    from repro.serve import CAServeEngine, SimJob
    from repro.serve.admission import AdmissionError
    from repro.telemetry import Telemetry
    cfg, trf = cell.config, cell.traffic
    eng_cfg = dict(cfg["engine"])
    every = int(trf["frame_every"])
    tel = Telemetry(enabled=cell.trace)
    with jax.profiler.TraceAnnotation("bench.setup"):
        eng = CAServeEngine(ckpt_dir=f"{cell.workdir}/ckpt", telemetry=tel,
                            **eng_cfg)
        shortest = min(int(s) for s in trf["steps_mix"])
        for lane in range(eng.slots):
            eng.submit(SimJob(rid=lane, scenario=trf["scenario"],
                              steps=shortest, frame_every=every,
                              overrides={"seed": lane,
                                         "density": trf["density"][0]}))
        eng.drain()
    setup_s = time.perf_counter() - cell.started
    cell.log(f"setup: {setup_s:.3f} s (engine {eng_cfg}, warmed with "
             f"{eng.slots} jobs of {shortest} steps)")

    plan = schedule(trf, cell.seed, cell.seconds)
    pending = collections.deque(
        (eng.slots + i, job) for i, job in enumerate(plan))
    due, late, backlog = {}, [], []
    profiler = harness.Profiler(cell.trace, f"{cell.workdir}/trace")
    tel.reset()

    def submit_due(now):
        while pending and start + pending[0][1]["due"] <= now:
            rid, job = pending.popleft()
            due[rid] = start + job["due"]
            with jax.profiler.TraceAnnotation("bench.submit"):
                try:
                    eng.submit(SimJob(rid=rid, scenario=trf["scenario"],
                                      steps=job["steps"], frame_every=every,
                                      overrides={"seed": job["seed"],
                                                 "density": job["density"]}))
                except AdmissionError:
                    pass            # refused: a miss, logged by the engine
            late.append(time.perf_counter() - due[rid])

    def busy():
        return len(eng.sched) or any(g.live_jobs()
                                     for g in eng.groups.values())

    with profiler:
        with jax.profiler.TraceAnnotation("bench.window"):
            start = time.perf_counter()
            rounds0 = eng.stats["rounds"]
            end = start + cell.seconds
            while (now := time.perf_counter()) < end:
                submit_due(now)
                if busy():
                    with jax.profiler.TraceAnnotation("bench.tick"):
                        eng.tick()
                    backlog.append(len(eng.sched))
                else:
                    nxt = start + pending[0][1]["due"] if pending else end
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(max(min(nxt, end) - now, 0.0))
            window_s = time.perf_counter() - start
            rounds = eng.stats["rounds"] - rounds0
    spans = tel.summary()["spans"] if cell.trace else None
    submit_due(math.inf)                # due in the window, sent late
    drained = time.perf_counter()
    while busy() and time.perf_counter() - drained < trf["drain_cap_s"]:
        eng.tick()
    drain_s = time.perf_counter() - drained
    peak = harness.memory_peak(cell.devices)

    walls = frame_walls(eng.frame_log, set(due))
    done, misses = latencies(eng.jobs, walls, due)
    tail = stats.with_misses(done, misses)
    gap = stats.gaps(walls)
    cell.log(f"window: {len(due)} jobs due in {window_s:.3f} s, {rounds} "
             f"rounds ({1e3 * window_s / max(rounds, 1):.3f} ms/round), "
             f"backlog at close {backlog[-1] if backlog else 0} (max "
             f"{max(backlog, default=0)}), drained in {drain_s:.3f} s, "
             f"{misses} missed")
    cell.log(f"latency s: p50 {stats.percentile(tail, 50):.6f} p95 "
             f"{stats.percentile(tail, 95):.6f} max {max(tail):.6f}; frame "
             f"gap s: p50 {stats.percentile(gap, 50):.6f} p95 "
             f"{stats.percentile(gap, 95):.6f} ({len(gap)} gaps)")
    cell.log(f"generator lateness s: p50 {stats.percentile(late, 50):.6f} "
             f"p95 {stats.percentile(late, 95):.6f} max {max(late):.6f}")

    from repro.serve import DONE
    finished = [eng.jobs[r] for r in sorted(due)
                if eng.jobs[r].status == DONE]
    rng = np.random.default_rng(cell.seed + 1)
    longest = max(finished, key=lambda j: (j.steps, -j.rid))
    others = [j for j in finished if j is not longest]
    pick = rng.choice(len(others), size=min(int(trf["check_jobs"]) - 1,
                                            len(others)), replace=False)
    sample = [longest] + [others[i] for i in sorted(pick)]
    p_force = float(cfg.get("p_force", 0.0))
    got = compare_jobs(cell, sample, p_force)
    checks = [harness.Check(k, v, 0) for k, v in got.items()]
    control = {}
    if cell.control:
        # The reference in the program's place, with the configuration's
        # body force left out.
        broken = compare_jobs(cell, sample, 0.0)
        control = {k: broken[k] for k in got}
    return harness.Outcome(
        end_to_end={"job_latency_p50_s": stats.percentile(tail, 50),
                    "setup_s": setup_s},
        checks=checks, attempted=len(due), failed=misses,
        memory_peak_bytes=peak,
        readings={"trace": profiler.reduce(), "spans": spans,
                  "counts": {"rounds": rounds, "window_s": window_s,
                             "backlog_end": backlog[-1] if backlog else 0,
                             "backlog_max": max(backlog, default=0),
                             "drain_s": drain_s,
                             "frame_gap_p95_s": stats.percentile(gap, 95),
                             "job_latency_p95_s": stats.percentile(tail,
                                                                   95)}},
        control=control)
