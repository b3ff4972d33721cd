"""The benchmark's correctness check, driven through whole runs at a tiny
size on the CPU (the look for a chip skipped): sound runs are correct,
the control (the reference with a guarantee broken, in the program's
place) is not, and neither is a run whose timed path is broken
underneath."""
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax.numpy as jnp  # noqa: E402

from bench import harness  # noqa: E402
from repro.core import distributed, rulespec  # noqa: E402
from repro.kernels.fhp_step import ops  # noqa: E402

TINY = {
    "fhp2-flow.lattice": {
        "config": {"lattice": {"height": 32, "width": 4096, "density": 0.22}},
        "traffic": {"steps_per_call": 4}},
    "bml-traffic.lattice": {
        "config": {"lattice": {"height": 32, "width": 4096, "east": 0.175,
                               "north": 0.175}},
        "traffic": {"steps_per_call": 4}},
    "fhp2-flow.sweep": {
        "config": {"engine": {"height": 64, "width": 256, "slots": 4,
                              "depth": 8, "audit_every": 1, "ckpt_every": 16,
                              "use_pallas": True}},
        # Enough load to fill the lanes, and every job compared, so a
        # fault in any lane shows.
        "traffic": {"rate_per_s": 16.0, "steps_mix": {"32": 0.5, "64": 0.5},
                    "frame_every": 16, "check_jobs": 100}},
}


@pytest.fixture(autouse=True)
def one_step_launches(monkeypatch):
    # Interpret-mode compiles of deep temporal blocks take minutes on the
    # CPU; the planner's choice is not what these tests are about.
    monkeypatch.setattr(ops, "autotune_launch",
                        lambda h, wd, **kw: (h, wd, 1))


def run(cell, control=False, seed=2 ** 33 + 3):
    args = harness.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", "0.5", "--trace", "0"])
    return harness.run_cell(args, require_tpu=False, overrides=TINY[cell],
                            control=control)


def broken(monkeypatch, fault):
    """Plant ``fault(planes_in, planes_out) -> planes`` under the program's
    ensemble entry, which every cell's timed path calls."""
    real = distributed.make_ensemble_run

    def make(mesh, steps, **kw):
        inner, sharding = real(mesh, steps, **kw)
        spec = rulespec.get_rule(kw.get("variant", "fhp2"))

        def run_(planes, t0):
            out = inner(planes, t0)
            if isinstance(out, tuple):
                bad = fault(planes, out[0])
                mom = rulespec.compute_moments(bad, rulespec.moment_spec(spec))
                return bad, jnp.broadcast_to(mom[:, None, :], out[1].shape)
            return fault(planes, out)
        return run_, sharding
    monkeypatch.setattr(distributed, "make_ensemble_run", make)


def unchanged(planes, out):
    return planes


def half_batch(planes, out):
    half = out.shape[0] // 2
    return jnp.concatenate([out[:half], planes[half:]])


def altered(planes, out):
    """Swap one word of planes 0 and 1: every conserved total holds, so
    the serve engine's audits pass it."""
    a, b = out[..., 0, 1, 0], out[..., 1, 1, 0]
    return out.at[..., 0, 1, 0].set(b).at[..., 1, 1, 0].set(a)


@pytest.mark.parametrize("cell", ["fhp2-flow.lattice", "bml-traffic.lattice",
                                  "fhp2-flow.sweep"])
def test_sound_run_is_correct_and_control_is_not(cell, capsys):
    result = run(cell, control=True)
    out = result["_outcome"]
    assert result["correct"], result["checks"]
    assert all(c.value == 0 for c in out.checks)
    assert out.control and any(v > 0 for v in out.control.values()), \
        out.control
    if cell.endswith(".sweep"):
        assert result["failed"] == 0 and result["attempted"] >= 2
        assert "generator lateness s:" in capsys.readouterr().err


@pytest.mark.parametrize("cell,fault", [
    ("fhp2-flow.lattice", unchanged), ("fhp2-flow.lattice", altered),
    ("bml-traffic.lattice", unchanged),
    ("fhp2-flow.sweep", unchanged), ("fhp2-flow.sweep", half_batch),
    ("fhp2-flow.sweep", altered)])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    broken(monkeypatch, fault)
    result = run(cell)
    assert not result["correct"], result["checks"]
