"""The fault-tolerant CA serve engine: continuous batching into ensemble
lanes, invariant-audit corruption detection, rollback-replay, quarantine,
and crash resume.

Bit-exactness is the acceptance bar throughout: the counter-based RNG
keys on global ``(t, row, word)`` with no lane term, so a job admitted at
``t0`` must finish identical to a solo ``run_planes_rule(..., t0=t0)``
reference -- and a recovered (rolled-back, replayed) ensemble must be
bit-identical to one that never faulted."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import scenarios
from repro.core import rulespec
from repro.serve import (DONE, QUARANTINED, CAServeEngine, Fault,
                         FaultInjector, SimJob, SimulatedCrash)

pytestmark = pytest.mark.serve

H, W = 16, 128


def _submit_mixed(eng, n=3, steps=8, frame_every=0):
    """cylinder(fhp2) + bml_city jobs: two lane groups."""
    for rid in range(n):
        sc = "bml_city" if rid % 3 == 1 else "cylinder"
        eng.submit(SimJob(rid=rid, scenario=sc, steps=steps,
                          frame_every=frame_every,
                          overrides={"seed": rid}))


def _reference(eng, job):
    sc = scenarios.get(job.scenario, height=eng.height, width=eng.width,
                       **job.overrides)
    return np.asarray(rulespec.run_planes_rule(
        sc.initial_planes(), job.steps, sc.rule(), p_force=sc.p_force,
        t0=job.admitted_t))


def test_continuous_batching_bit_exact():
    """More jobs than slots: later jobs admitted mid-stream at a later
    t0, every result bit-identical to its solo reference at that t0."""
    eng = CAServeEngine(height=H, width=W, slots=1, depth=2)
    for rid in range(3):
        eng.submit(SimJob(rid=rid, scenario="cylinder", steps=6,
                          overrides={"seed": rid}))
    done = eng.drain()
    assert len(done) == 3 and eng.stats["jobs_done"] == 3
    t0s = sorted(eng.jobs[r].admitted_t for r in range(3))
    assert t0s == [0, 6, 12]        # slots=1: strictly staggered
    for job in done:
        assert np.array_equal(job.result, _reference(eng, job)), job.rid


def test_two_rule_groups_one_engine():
    eng = CAServeEngine(height=H, width=W, slots=2, depth=2)
    _submit_mixed(eng, n=4, steps=8)
    done = eng.drain()
    assert len(done) == 4
    assert {g.variant for g in eng.groups.values()} == {"fhp2", "bml"}
    for job in done:
        assert np.array_equal(job.result, _reference(eng, job)), job.rid


def test_fault_detected_rolled_back_bit_identical(tmp_path):
    """The headline property: a seeded transient-fault schedule (bit
    flip + NaN'd shard + torn checkpoint) is fully detected by the rule
    invariants, rolled back to the last audited checkpoint, and the
    recovered ensemble is bit-identical to a fault-free run."""
    def build(injector, d):
        eng = CAServeEngine(height=H, width=W, slots=2, depth=2,
                            ckpt_dir=d, ckpt_every=2, injector=injector)
        _submit_mixed(eng, n=3, steps=12)
        return eng

    base = build(None, str(tmp_path / "clean"))
    base_res = {j.rid: j.result for j in base.drain()}

    inj = FaultInjector([
        # round-4 checkpoint is torn on publish; the round-4 state
        # faults are then detected at round 5 and must anchor on the
        # (intact) round-2 checkpoint.
        Fault(kind="torn_checkpoint", round=4, seed=3),
        Fault(kind="bitflip", round=4, rule="fhp2", lane=0, plane=2,
              bits=1, seed=4),
        Fault(kind="nan_shard", round=4, rule="bml", lane=0, plane=0,
              rows=2, seed=5),
    ])
    eng = build(inj, str(tmp_path / "faulty"))
    done = eng.drain()

    assert len(inj.corruption_events()) == 2
    assert len(eng.detections) == len(inj.corruption_events())
    assert eng.stats["rollbacks"] >= 1
    assert eng.stats["steps_replayed"] >= 6   # detected r5, anchor r2
    rec = eng.stats["recovery"][0]
    assert rec["restored_round"] == 2 and rec["detected_round"] == 5
    assert rec["restore_s"] > 0
    assert len(done) == 3
    for job in done:
        assert np.array_equal(job.result, base_res[job.rid]), job.rid


def test_frames_survive_rollback_bit_exact(tmp_path):
    """Streamed frames replayed after a rollback are re-derived from the
    bit-exact replay: the faulty run's frame stream equals the clean
    run's, with no stale (pre-rollback, corrupted) frames surviving."""
    def build(injector, d):
        eng = CAServeEngine(height=H, width=W, slots=2, depth=2,
                            ckpt_dir=d, ckpt_every=2, injector=injector)
        _submit_mixed(eng, n=2, steps=12, frame_every=4)
        return eng

    base = build(None, str(tmp_path / "clean"))
    base.drain()
    inj = FaultInjector([Fault(kind="bitflip", round=4, rule="fhp2",
                               lane=0, plane=1, bits=3, seed=9)])
    eng = build(inj, str(tmp_path / "faulty"))
    eng.drain()
    assert eng.stats["rollbacks"] == 1
    for rid, job in eng.jobs.items():
        want = base.jobs[rid].frames
        assert job.frames.keys() == want.keys()
        for s in want:
            for k in want[s]:
                assert np.array_equal(np.asarray(job.frames[s][k]),
                                      np.asarray(want[s][k])), (rid, s, k)


def test_persistent_fault_quarantined_others_unharmed(tmp_path):
    """A sticky fault re-fires on every replay: after max_retries
    rollbacks the poisoned job is quarantined (lane zeroed and freed)
    and the healthy jobs still finish bit-exact."""
    base = CAServeEngine(height=H, width=W, slots=3, depth=2,
                         ckpt_dir=str(tmp_path / "clean"), ckpt_every=2)
    _submit_mixed(base, n=3, steps=12)
    base_res = {j.rid: j.result for j in base.drain()}

    inj = FaultInjector([Fault(kind="bitflip", round=4, rule="fhp2",
                               lane=0, plane=2, bits=1, seed=6,
                               sticky=True)])
    eng = CAServeEngine(height=H, width=W, slots=3, depth=2,
                        ckpt_dir=str(tmp_path / "faulty"), ckpt_every=2,
                        max_retries=2, injector=inj)
    _submit_mixed(eng, n=3, steps=12)
    done = eng.drain()

    victim = eng.detections[0]["rid"]
    assert eng.jobs[victim].status == QUARANTINED
    assert eng.stats["quarantined"] == 1
    assert eng.stats["rollbacks"] == eng.max_retries
    survivors = {j.rid for j in done}
    assert survivors == {0, 1, 2} - {victim}
    for job in done:
        assert np.array_equal(job.result, base_res[job.rid]), job.rid


def test_no_checkpoint_restart_fallback():
    """Without a checkpoint anchor, recovery degrades to restarting the
    offending job from its initial state -- it still completes, and
    still bit-exact for its (new, later) admission t0."""
    inj = FaultInjector([Fault(kind="bitflip", round=1, rule="fhp2",
                               lane=0, plane=0, bits=1, seed=2)])
    eng = CAServeEngine(height=H, width=W, slots=1, depth=2, injector=inj)
    eng.submit(SimJob(rid=0, scenario="cylinder", steps=6))
    done = eng.drain()
    assert len(eng.detections) == 1 and eng.stats["rollbacks"] == 0
    assert len(done) == 1
    job = done[0]
    assert job.admitted_t > 0       # restarted mid-stream
    assert np.array_equal(job.result, _reference(eng, job))


def test_crash_resume_completes_bit_exact(tmp_path):
    """killed_step mid-run: the engine dies; ``resume`` rebuilds lanes,
    jobs, and queue from the last valid checkpoint and the finished
    ensemble is bit-identical to an uninterrupted run."""
    d = str(tmp_path / "svc")
    base = CAServeEngine(height=H, width=W, slots=2, depth=2,
                         ckpt_dir=str(tmp_path / "clean"), ckpt_every=2)
    _submit_mixed(base, n=3, steps=12)
    base_res = {j.rid: j.result for j in base.drain()}

    inj = FaultInjector([Fault(kind="killed_step", round=5)])
    eng = CAServeEngine(height=H, width=W, slots=2, depth=2,
                        ckpt_dir=d, ckpt_every=2, injector=inj)
    _submit_mixed(eng, n=3, steps=12)
    with pytest.raises(SimulatedCrash):
        eng.drain()

    eng2 = CAServeEngine.resume(d, ckpt_every=2)
    assert eng2.round == 4          # last published checkpoint
    done = eng2.drain()
    assert {j.rid for j in done} == {0, 1, 2}
    for job in done:
        assert np.array_equal(job.result, base_res[job.rid]), job.rid


def test_submit_after_checkpoint_requeued_on_rollback(tmp_path):
    """A job submitted after the anchor checkpoint is unknown to the
    restored bookkeeping: rollback must re-queue it (not lose it)."""
    inj = FaultInjector([Fault(kind="bitflip", round=3, rule="fhp2",
                               lane=0, plane=1, bits=1, seed=8)])
    eng = CAServeEngine(height=H, width=W, slots=2, depth=2,
                        ckpt_dir=str(tmp_path), ckpt_every=2,
                        injector=inj)
    eng.submit(SimJob(rid=0, scenario="cylinder", steps=10))
    eng.tick(); eng.tick()          # checkpoint at round 2
    eng.submit(SimJob(rid=1, scenario="cylinder", steps=6,
                      overrides={"seed": 1}))
    done = eng.drain()
    assert eng.stats["rollbacks"] == 1
    assert {j.rid for j in done} == {0, 1}
    for job in done:
        assert np.array_equal(job.result, _reference(eng, job)), job.rid


def test_cli_fault_run_serves_all_jobs(tmp_path, capsys):
    """The launcher's fault schedule must span the rounds the batched
    run actually executes (jobs run concurrently, not serially) -- the
    seeded faults fire, are detected, and every job is still served."""
    from repro.launch import serve as cli
    rc = cli.main(["--height", "16", "--width", "128", "--slots", "2",
                   "--jobs", "4", "--steps", "12", "--ckpt-every", "2",
                   "--ckpt-dir", str(tmp_path), "--faults", "17"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "served 4/4 jobs" in out
    fired = int(out.split("faults fired: ")[1].split()[0])
    assert fired >= 1, out
    assert "detections: 0" not in out, out


# ---------------------------------------------------------------------------
# Acceptance: two rules (fhp3 + bml) on a sharded 2x2 mesh through the
# Pallas kernel, seeded bitflip + torn checkpoint + NaN'd shard -- every
# corruption detected, and the recovered ensemble bit-identical to the
# fault-free run.  Subprocess so the fake-device XLA flag can't leak.
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core.distributed import make_mesh
    from repro.serve import CAServeEngine, Fault, FaultInjector, SimJob

    H, W = 16, 128
    mesh = make_mesh((2, 2), ("data", "model"))

    def build(injector, d):
        eng = CAServeEngine(height=H, width=W, slots=2, depth=2,
                            steps_per_launch=2, use_pallas=True,
                            mesh=mesh, ckpt_dir=d, ckpt_every=2,
                            injector=injector)
        for rid in range(3):
            sc = "bml_city" if rid == 1 else "cylinder"
            ov = {"seed": rid}
            if sc == "cylinder":
                ov["variant"] = "fhp3"
            eng.submit(SimJob(rid=rid, scenario=sc, steps=12,
                              frame_every=4, overrides=ov))
        return eng

    base = build(None, tempfile.mkdtemp())
    base_res = {j.rid: j.result for j in base.drain()}
    assert set(base.groups) == {"fhp3|0.03", "bml|0.0"}, set(base.groups)

    inj = FaultInjector([
        Fault(kind="torn_checkpoint", round=4, seed=1),
        Fault(kind="bitflip", round=4, rule="fhp3", lane=0, plane=3,
              bits=1, seed=2),
        Fault(kind="nan_shard", round=4, rule="bml", lane=0, plane=0,
              rows=2, seed=3),
    ])
    eng = build(inj, tempfile.mkdtemp())
    done = eng.drain()

    assert len(inj.corruption_events()) == 2
    assert len(eng.detections) == len(inj.corruption_events()), \\
        eng.detections
    rules_hit = {v["rule"] for v in eng.detections}
    assert rules_hit == {"fhp3", "bml"}, rules_hit
    assert eng.stats["rollbacks"] >= 1
    rec = eng.stats["recovery"][0]
    assert rec["restored_round"] == 2, rec    # torn r4 -> anchor r2
    assert len(done) == 3
    for job in done:
        assert np.array_equal(job.result, base_res[job.rid]), job.rid
    print("SERVE_SHARDED_OK")
""")


def test_sharded_fault_recovery_two_rules():
    # Inherit the parent env (JAX_PLATFORMS etc. must reach the child);
    # only the fake-device XLA flag is script-local.
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=env)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "SERVE_SHARDED_OK" in r.stdout
