"""The trace reduction (bench/trace.py): interval arithmetic on made-up
events, and the whole reduction on a small trace recorded on a TPU v5e
chip and kept in bench/fixtures/.  CPU only; loads no TPU library."""
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import trace  # noqa: E402


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert trace.length([(0, 2), (1, 3), (10, 11)]) == 4


def test_subtract_leaves_the_bare_parts():
    cover = trace.union([(1, 2), (4, 6)])
    assert trace.subtract([(0, 5)], cover) == [(0, 1), (2, 4)]
    assert trace.subtract([(1, 2)], cover) == []
    assert trace.subtract([(6, 9)], cover) == [(6, 9)]


def _device(ops):
    return trace.Device(0, ops)


def test_exposed_collectives_exclude_overlapped_compute():
    dev = _device([("collective-permute.1", 0, 10), ("fusion.2", 2, 5),
                   ("all-reduce.3", 20, 22), ("fusion.4", 21, 30)])
    assert dev.exposed_collectives() == [(0, 2), (5, 10), (20, 21)]
    # A container around them (the round's ``while``) hides nothing, and
    # an op that only takes a collective's result is no collective.
    dev = _device([("while.9", 0, 40), ("collective-permute.1", 0, 10),
                   ("fusion.2", 2, 5), ("%fusion.5 = fusion(%collective-"
                                        "permute.1)", 10, 12)])
    assert dev.exposed_collectives() == [(0, 2), (5, 10)]


def test_busy_idle_and_labelled_gaps():
    red = trace.Reduction(
        window=(0.0, 100.0),
        devices=[_device([("%while.1", 10, 50), ("k", 10, 30), ("k", 30, 50),
                          ("copy", 80, 90)])],
        spans=[("bench.window", 0, 100), ("bench.call", 0, 60),
               ("bench.tick", 60, 100)])
    assert red.busy_s() == pytest.approx(50e-9)
    assert red.idle_share() == pytest.approx(0.5)
    gaps = red.idle_gaps()
    assert gaps[0] == ("bench.tick", pytest.approx(30e-9))
    assert ("bench.call", pytest.approx(10e-9)) in gaps
    # The while loop holds the kernel launches: only its body counts.
    assert red.top_ops() == [("k", pytest.approx(40e-9)),
                             ("copy", pytest.approx(10e-9))]


FIXTURE = os.path.join(ROOT, "bench", "fixtures", "bml_two_calls.xplane.pb")


def test_chip_trace_reduction():
    """Two 64-step BML calls on 16384 x 65536 (T=8: 8 launches each), 50
    ms apart, traced on one TPU v5e; each call in a ``bench.call`` span."""
    from jax.profiler import ProfileData
    red = trace.load(FIXTURE)
    assert len(red.devices) == 1
    dev = red.devices[0]
    kernels = dev.events(trace.KERNEL)
    assert len(kernels) == 16
    # Independently: the custom calls on the device's op line.
    pd = ProfileData.from_file(FIXTURE)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = [e for line in plane.lines if line.name == "XLA Ops"
           for e in line.events]
    custom = [e for e in ops if "tpu_custom_call" in e.name]
    assert len(custom) == 16
    assert trace.kernel_seconds(dev, trace.KERNEL) == pytest.approx(
        sum(e.duration_ns for e in custom) / 1e9)
    # No bench.window span: the window is the device's activity, and the
    # two calls' while loops cover the busy time.
    assert red.window == (min(e.start_ns for e in ops),
                          max(e.start_ns + e.duration_ns for e in ops))
    loops = [e for e in ops if e.name.startswith("%while")]
    assert red.busy_s() == pytest.approx(
        trace.length((e.start_ns, e.start_ns + e.duration_ns)
                     for e in ops) / 1e9)
    assert red.busy_s() >= sum(e.duration_ns for e in loops) / 1e9 * 0.99
    gap, width = red.idle_gaps(1)[0]
    assert gap == "outside any benchmark span" and 0.05 < width < 0.06
    assert {s[0] for s in red.spans} == {"bench.call"}
    name, seconds = red.top_ops(1)[0]
    assert name.startswith("%fhp_step_pallas") and seconds == pytest.approx(
        trace.kernel_seconds(dev, trace.KERNEL))
    assert dev.exposed_collectives() == []
