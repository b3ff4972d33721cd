"""Telemetry layer: span nesting, rollups, the JSONL sink schema, the
disabled-path no-op contract, spans on the profiler's host plane, no
spans under jit, the serve engine's job spans, and the crash-safe
fault trace through the serve engine.

The crash-safety test rides the fault-injection harness: a seeded
bitflip drives the engine through detection -> rollback, and the
telemetry JSONL on disk must already contain the critical events
*without any flush/close from this side* -- the engine fsyncs them at
emission, so the trace survives the process death that
``CAServeEngine.resume`` recovers from.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.telemetry.core import _NULL, Telemetry


def test_span_nesting_and_summary():
    tel = Telemetry(enabled=True)
    with tel.span("outer", depth=2):
        with tel.span("inner"):
            pass
        with tel.span("inner"):
            pass
    s = tel.summary()
    assert s["spans"]["outer"]["count"] == 1
    assert s["spans"]["inner"]["count"] == 2
    for col in ("total_s", "p50_s", "p99_s", "max_s"):
        assert s["spans"]["inner"][col] >= 0.0
    tel.count("hits", 3)
    tel.count("hits")
    tel.gauge("depth", 7)
    s = tel.summary()
    assert s["counters"]["hits"] == 4
    assert s["gauges"]["depth"] == 7


def test_jsonl_sink_schema(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tel = Telemetry(enabled=True, jsonl_path=path)
    with tel.span("outer"):
        with tel.span("inner", k=1):
            pass
    tel.count("c")
    tel.gauge("g", 2.5)
    tel.event("e", critical=True, round=3)
    tel.close()
    recs = [json.loads(l) for l in open(path)]
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
        assert "name" in r and "wall" in r
    assert {r["name"] for r in by_kind["span"]} == {"outer", "inner"}
    inner = next(r for r in by_kind["span"] if r["name"] == "inner")
    assert inner["parent"] == "outer" and inner["attrs"] == {"k": 1}
    assert inner["traced"] is False and inner["dur_s"] >= 0.0
    assert by_kind["counter"][0]["n"] == 1
    assert by_kind["gauge"][0]["value"] == 2.5
    assert by_kind["event"][0]["critical"] is True
    assert by_kind["event"][0]["attrs"] == {"round": 3}


def test_disabled_is_true_noop(tmp_path):
    """Disabled telemetry: the span is one shared null object, and no
    state (registry or sink) is touched."""
    path = str(tmp_path / "t.jsonl")
    tel = Telemetry(enabled=False, jsonl_path=path)
    s1 = tel.span("a", attr=1)
    s2 = tel.span("b")
    assert s1 is s2 is _NULL
    with s1:
        pass
    tel.count("c")
    tel.gauge("g", 1)
    tel.event("e", critical=True)
    summ = tel.summary()
    assert summ["spans"] == {} and summ["counters"] == {}
    assert summ["events"] == 0
    tel.close()
    assert open(path).read() == ""


def test_traced_span_under_jit():
    """A span opened while jax traces is the null span: nothing is
    recorded, the jitted function computes identically, and it lowers to
    the same program with telemetry on or off."""
    tel = Telemetry(enabled=True)

    def f(x):
        with tel.span("traced.region"):
            return x * 2

    jf = jax.jit(f)
    assert int(jf(jnp.int32(21))) == 42
    assert int(jf(jnp.int32(4))) == 8
    assert tel.records("traced.region") == []
    assert "traced.region" not in tel.summary()["spans"]
    on = jax.jit(f).lower(jnp.int32(1)).as_text()
    tel.enabled = False
    assert jax.jit(f).lower(jnp.int32(1)).as_text() == on


def test_enabled_span_on_profiler_host_plane(tmp_path):
    """An enabled span enters a ``TraceAnnotation``: a profiler trace
    taken meanwhile holds it, with its attrs, on a host plane, and the
    nesting of the records matches the trace's."""
    import glob

    from jax.profiler import ProfileData

    tel = Telemetry(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("probe.outer", rid=3):
            with tel.span("probe.inner"):
                jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    xplane, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    profile = ProfileData.from_file(xplane)
    found = {e.name: e for plane in profile.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("probe.")}
    assert set(found) == {"probe.outer", "probe.inner"}
    outer, inner = found["probe.outer"], found["probe.inner"]
    assert dict(outer.stats).get("rid") == 3
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
    recs = {r["name"]: r for r in tel.records()}
    assert recs["probe.inner"]["parent"] == "probe.outer"
    assert recs["probe.outer"]["attrs"] == {"rid": 3}
    assert (recs["probe.outer"]["start"] <= recs["probe.inner"]["start"]
            <= recs["probe.inner"]["end"] <= recs["probe.outer"]["end"])


def test_disabled_span_opens_no_annotation(monkeypatch):
    """Disabled: no annotation is opened and nothing is recorded, for
    spans and intervals alike; enabled: one annotation per span."""
    opened = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **attrs):
        opened.append(name)
        return real(name, **attrs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    tel = Telemetry(enabled=False)
    with tel.span("off.span", rid=1):
        pass
    tel.interval("off.interval", 1.0, 2.0, rid=1)
    assert opened == [] and tel.records() == []
    tel.enabled = True
    with tel.span("on.span"):
        pass
    assert opened == ["on.span"]


def test_interval_is_a_finished_span():
    """``interval`` records an explicit [start, end] with no parent, even
    inside an open span, and rolls up like any span (p95 nearest rank)."""
    tel = Telemetry(enabled=True)
    with tel.span("outer"):
        for k in range(20):
            tel.interval("wait", 10.0, 10.0 + k, rid=k)
    recs = tel.records("wait")
    assert len(recs) == 20 and all(r["parent"] is None for r in recs)
    assert recs[3] == {"name": "wait", "start": 10.0, "end": 13.0,
                       "parent": None, "attrs": {"rid": 3}}
    roll = tel.summary()["spans"]["wait"]
    assert roll["count"] == 20 and roll["total_s"] == sum(range(20))
    assert roll["p95_s"] == 18.0 and roll["max_s"] == 19.0


@pytest.mark.serve
def test_traced_tick_waits_only_on_the_moments(tmp_path, monkeypatch):
    """With telemetry on, a round calls no ``block_until_ready`` (the
    traced program is the timed one), and every placed job has exactly
    one ``serve.queue`` interval with its rid -- also a job placed again
    after a rollback -- plus a ``serve.place`` per placement."""
    from repro.serve import CAServeEngine, Fault, FaultInjector, SimJob

    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(1) or real(x))
    tel = Telemetry(enabled=True)
    inj = FaultInjector([Fault(kind="bitflip", round=3, rule="fhp2",
                               lane=0, bits=1, seed=7)])
    eng = CAServeEngine(height=16, width=64, slots=3, depth=2,
                        ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2,
                        injector=inj, telemetry=tel)
    for rid in range(2):
        eng.submit(SimJob(rid=rid, scenario="cylinder", steps=8,
                          frame_every=2, overrides={"seed": rid}))
    eng.tick()
    eng.tick()
    eng.tick()
    eng.submit(SimJob(rid=2, scenario="cylinder", steps=4,
                      overrides={"seed": 2}))
    done = eng.drain()
    assert len(done) == 3 and eng.stats["rollbacks"] == 1
    assert waits == []
    queue = tel.records("serve.queue")
    assert sorted(r["attrs"]["rid"] for r in queue) == [0, 1, 2]
    for r in queue:
        job = eng.jobs[r["attrs"]["rid"]]
        assert r["start"] == job.submitted_wall <= r["end"] == job.placed_wall
    place = tel.records("serve.place")
    assert sorted(r["attrs"]["rid"] for r in place) == [0, 1, 2, 2]
    assert all(r["parent"] == "serve.admit" for r in place)
    assert tel.summary()["spans"]["serve.checkpoint"]["count"] >= 1


@pytest.mark.serve
def test_resumed_engine_times_only_unplaced_jobs_in_the_queue(tmp_path):
    """A checkpoint keeps whether a job was placed: after ``resume`` a job
    that was running records no second ``serve.queue``, and a job still
    queued records one, from the resume."""
    from repro.serve import CAServeEngine, SimJob

    ckpt = str(tmp_path / "ckpt")
    eng = CAServeEngine(height=16, width=64, slots=1, depth=2,
                        ckpt_dir=ckpt, ckpt_every=1,
                        telemetry=Telemetry(enabled=True))
    for rid in range(2):
        eng.submit(SimJob(rid=rid, scenario="cylinder", steps=8,
                          overrides={"seed": rid}))
    eng.tick()
    assert eng.jobs[0].status == "running" and eng.jobs[1].status == "queued"
    tel = Telemetry(enabled=True)
    back = CAServeEngine.resume(ckpt, telemetry=tel)
    assert back.jobs[0].placed_wall is not None
    assert back.jobs[1].placed_wall is None
    assert len(back.drain()) == 2
    queue = tel.records("serve.queue")
    assert [r["attrs"]["rid"] for r in queue] == [1]
    assert queue[0]["start"] == back.jobs[1].submitted_wall


def test_module_default_configure(tmp_path):
    tel = telemetry.default()
    was = tel.enabled
    try:
        telemetry.configure(enabled=True)
        with telemetry.span("mod.span"):
            telemetry.count("mod.count")
        assert telemetry.summary()["counters"]["mod.count"] == 1
    finally:
        telemetry.configure(enabled=was)
        tel.reset()
        tel.close()


@pytest.mark.faults
def test_fault_trace_survives_unflushed(tmp_path):
    """Detection/rollback/quarantine events are on disk the instant they
    are emitted (fsync), so the fault trace survives a process that dies
    before any flush -- the scenario ``CAServeEngine.resume`` recovers
    from."""
    from repro.serve import CAServeEngine, Fault, FaultInjector, SimJob

    path = str(tmp_path / "serve.jsonl")
    tel = Telemetry(enabled=True, jsonl_path=path)
    ckpt = str(tmp_path / "ckpt")
    inj = FaultInjector([Fault(kind="bitflip", round=2, rule="fhp2",
                               lane=0, bits=1, seed=7)])
    eng = CAServeEngine(height=16, width=64, slots=2, depth=2,
                        ckpt_dir=ckpt, ckpt_every=1, injector=inj,
                        telemetry=tel)
    eng.submit(SimJob(rid=0, scenario="cylinder", steps=12))
    done = eng.drain()
    assert len(done) == 1 and eng.stats["rollbacks"] == 1

    # Read the sink path directly, *without* flushing or closing the
    # writer: everything critical must already be durable.
    recs = [json.loads(l) for l in open(path)]
    crit = [r for r in recs if r.get("critical")]
    names = {r["name"] for r in crit}
    assert "serve.detection" in names and "serve.rollback" in names
    rb = next(r for r in crit if r["name"] == "serve.rollback")
    assert rb["attrs"]["steps_lost"] > 0

    # The in-memory registry agrees, and the engine's fused-moment
    # audits only fell back to recomputation on the corrupted round.
    c = tel.summary()["counters"]
    assert c["serve.audit.recomputed"] >= 1
    assert c["serve.audit.fused"] >= 1
    tel.close()
