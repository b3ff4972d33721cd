"""The per-layer readers of the serve engine's own spans, fed the span
rollup of a ``Telemetry`` that recorded made-up spans.  CPU only."""
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness, stats  # noqa: E402
from repro.telemetry import Telemetry  # noqa: E402


def _reader(name):
    return harness.load_module(harness.ROOT / "bench" / "metrics"
                               / f"{name}.py")


def _spans(records):
    """The driver's ``readings["spans"]``: the summary's span rollup."""
    tel = Telemetry(enabled=True)
    for name, start, end in records:
        tel.interval(name, start, end)
    return tel.summary()["spans"]


def test_queue_wait_p95_is_nearest_rank_over_placed_jobs():
    waits = [0.05 * k for k in range(1, 41)]
    spans = _spans([("serve.queue", 100.0, 100.0 + w) for w in waits]
                   + [("serve.place", 0.0, 9.0)])
    got = _reader("serve.queue_wait_p95_s.sweep").read({"spans": spans})
    assert got == pytest.approx(stats.percentile(waits, 95))
    assert got == pytest.approx(1.9)


@pytest.mark.parametrize("metric,span", [
    ("serve.admit_s_per_job.sweep", "serve.place"),
    ("serve.checkpoint_s_per_save.sweep", "serve.checkpoint"),
])
def test_seconds_per_span_is_total_over_count(metric, span):
    spans = _spans([(span, 0.0, 0.5), (span, 1.0, 1.75), (span, 2.0, 2.25),
                    ("serve.round", 0.0, 3.0)])
    got = _reader(metric).read({"spans": spans})
    assert got == pytest.approx(1.5 / 3)


@pytest.mark.parametrize("metric", [
    "serve.queue_wait_p95_s.sweep", "serve.admit_s_per_job.sweep",
    "serve.checkpoint_s_per_save.sweep"])
def test_span_readers_return_nothing_when_the_span_is_absent(metric):
    mod = _reader(metric)
    assert mod.read({}) is None
    assert mod.read({"spans": None}) is None
    assert mod.read({"spans": _spans([("serve.round", 0.0, 1.0)])}) is None
