"""The FHP-III lattice cell (fhp3-flow.lattice) driven through whole runs
at a tiny size on the CPU (the look for a chip skipped): a sound run is
correct and reports the kernel's operation count when traced, the
control is not correct, and neither is a run whose timed path collides
with the old one-bit restriction of FHP-III or ignores the second random
word.  A program whose fhp3 draws one random word stops at once."""
import dataclasses
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, trace  # noqa: E402
from repro.core import boolean, rulespec  # noqa: E402
from repro.kernels.fhp_step import ops  # noqa: E402

CELL = "fhp3-flow.lattice"
TINY = {"config": {"lattice": {"height": 32, "width": 4096, "density": 0.22}},
        "traffic": {"steps_per_call": 4}}


@pytest.fixture(autouse=True)
def one_step_launches(monkeypatch):
    # Interpret-mode compiles of deep temporal blocks take minutes on the
    # CPU; the planner's choice is not what these tests are about.
    monkeypatch.setattr(ops, "autotune_launch",
                        lambda h, wd, **kw: (h, wd, 1))
    # Kernels traced by an earlier test are not traced again, and the
    # kernel's build event and a planted rule both need a fresh trace.
    jax.clear_caches()
    yield
    jax.clear_caches()


def run(control=False, trace_=0, seed=2 ** 33 + 5):
    args = harness.parse(["--workload", CELL, "--seed", str(seed),
                          "--seconds", "0.5", "--trace", str(trace_)])
    return harness.run_cell(args, require_tpu=False, overrides=TINY,
                            control=control)


def test_sound_run_is_correct_and_control_is_not():
    result = run(control=True, trace_=1)
    out = result["_outcome"]
    assert result["correct"], result["checks"]
    assert all(c.value == 0 for c in out.checks)
    assert out.control["mismatched_words"] > 0, out.control
    counts = out.readings["counts"]
    spec = rulespec.get_rule("fhp3")
    assert counts["rule"] == "fhp3" and counts["random_words"] == 2
    assert counts["ops_per_word_step"] == rulespec.ops_per_word_step(
        spec, 0.03)
    assert (counts["block_rows"], counts["block_words"],
            counts["steps_per_launch"]) == (32, 128, 1)
    # No device plane in a CPU trace: the device metric stays silent, the
    # program's count is reported.
    assert result["metrics"]["kernel.ops_per_word_step.fhp3"]["value"] == \
        counts["ops_per_word_step"]
    assert "kernel.gop_per_s.fhp3" not in result["metrics"]


def test_untraced_run_reports_no_build_counts():
    result = run()
    assert result["correct"], result["checks"]
    assert "ops_per_word_step" not in result["_outcome"].readings["counts"]
    assert set(result["metrics"]) == {"site_updates_per_s", "setup_s"}


def one_bit_table():
    """The one-bit restriction FHP-III used to be: ``(out for chirality 0,
    for 1)`` of each of the 128 fluid states.  FHP-II's table, with the
    rest particle barred from head-on pairs, a pair with a rest particle
    fused into a triple, and a triple without one rotated or split."""
    def rot(s, k):                     # movers turned by k x 60 degrees
        return ((s << k) | (s >> (6 - k))) & 63

    t0, t1, rest = 0b010101, 0b101010, 64
    table = {s: (s, s) for s in range(128)}
    for i in range(3):
        pair = (1 << i) | (1 << (i + 3))
        table[pair] = (rot(pair, 1), rot(pair, 5))
        table[pair | rest] = (t0, t1)
        quad = pair | rot(pair, 1)
        for r in (0, rest):
            table[quad | r] = (rot(quad, 1) | r, rot(quad, 5) | r)
    table[t0], table[t1] = (t1, 0b001001 | rest), (t0, 0b010010 | rest)
    table[t0 | rest], table[t1 | rest] = (t1 | rest,) * 2, (t0 | rest,) * 2
    for i in range(6):
        one, two = 1 << i, rot(1 << i, 1) | rot(1 << i, 5)
        table[one | rest] = (two, two)
        table[two] = (one | rest, one | rest)
    return table


def one_bit_fhp3(streamed, words, t):
    """``one_bit_table`` as a circuit: one minterm per fluid state, and
    bounce-back on solid sites."""
    a, chi = list(streamed), words[0]
    out = [jnp.zeros_like(a[0]) for _ in range(7)]
    for s, outs in one_bit_table().items():
        m = jnp.full_like(a[0], 0xFFFFFFFF)
        for d in range(7):
            m = m & (a[d] if (s >> d) & 1 else ~a[d])
        for o, pick in zip(outs, (~chi, chi)):
            for d in range(7):
                if (o >> d) & 1:
                    out[d] = out[d] | (m & pick)
    solid = a[7]
    movers = [(solid & a[(d + 3) % 6]) | (~solid & out[d]) for d in range(6)]
    return movers + [(solid & a[6]) | (~solid & out[6]), solid]


def ignore_c1(streamed, words, t):
    c0 = words[0]
    return boolean.collide_planes(streamed, (c0, jnp.zeros_like(c0)), "fhp3")


@pytest.mark.parametrize("collide", [one_bit_fhp3, ignore_c1])
def test_broken_timed_path_is_not_correct(collide, monkeypatch):
    """The planted collision runs in the program's kernel, the timed
    path; the reference is the published rule."""
    spec = rulespec.get_rule("fhp3")
    monkeypatch.setitem(rulespec._REGISTRY, "fhp3",
                        dataclasses.replace(spec, collide=collide))
    result = run()
    assert not result["correct"], result["checks"]
    assert result["checks"]["mismatched_words"]["value"] > 0


def test_a_program_whose_fhp3_draws_one_word_stops(monkeypatch):
    spec = rulespec.get_rule("fhp3")
    monkeypatch.setitem(rulespec._REGISTRY, "fhp3",
                        dataclasses.replace(spec, random_words=1))
    with pytest.raises(RuntimeError, match="not the configured rule"):
        run()


def test_gop_per_s_reads_the_slowest_chip():
    read = harness.load_module(harness.ROOT / "bench" / "metrics"
                               / "kernel.gop_per_s.fhp3.py").read
    kern = "%fhp_step_pallas.3 = u32[1,8,64,2] custom-call(), " \
           "custom_call_target=\"tpu_custom_call\""
    devs = [trace.Device(0, [(kern, 0, 2e9)]),
            trace.Device(1, [(kern, 0, 4e9), ("fusion.2", 4e9, 5e9)])]
    red = trace.Reduction(window=(0.0, 5e9), devices=devs, spans=[])
    counts = {"site_updates": 64e9, "ops_per_word_step": 1000}
    # 1e9 words a chip, 1000 ops each, over 4 s on the slower chip.
    assert read({"trace": red, "counts": counts}) == pytest.approx(250.0)
    assert read({"trace": red, "counts": {"site_updates": 64e9}}) is None
