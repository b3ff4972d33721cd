"""Metric arithmetic of the chip benchmark: tails, latency, lateness,
the peaks table, and the per-layer readers.  CPU only."""
import math
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness, peaks, stats  # noqa: E402
from bench.drivers import open_loop  # noqa: E402


def test_tail_counts_misses_as_infinite():
    done = [0.1 * k for k in range(1, 20)]           # 19 finished
    assert stats.percentile(stats.with_misses(done, 0), 95) == pytest.approx(1.9)
    # One shed, refused or unfinished job of 20 is the 95th percentile's
    # neighbour; two put the tail at infinity.
    assert stats.percentile(stats.with_misses(done, 1), 95) == pytest.approx(1.9)
    assert stats.percentile(stats.with_misses(done, 2), 95) == math.inf


def test_percentile_is_nearest_rank():
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile([5], 95) == 5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_frame_gaps_are_within_one_request():
    gaps = stats.gaps({1: [0.0, 1.0, 3.0], 2: [10.0, 10.5]})
    assert sorted(gaps) == [0.5, 1.0, 2.0]


class _Job:
    def __init__(self, status):
        self.status = status


def test_latency_runs_from_due_time_to_last_frame():
    from repro.serve import DONE, QUEUED, SHED
    jobs = {1: _Job(DONE), 2: _Job(DONE), 3: _Job(SHED), 4: _Job(QUEUED)}
    walls = {1: [5.0, 7.5], 2: [9.0]}
    due = {1: 2.0, 2: 8.0, 3: 1.0, 4: 3.0, 5: 4.0}   # 5 was refused
    done, misses = open_loop.latencies(jobs, walls, due)
    assert sorted(done) == [1.0, 5.5]
    assert misses == 3


def test_schedule_offers_every_seed_the_same_work():
    trf = {"rate_per_s": 7.0, "density": [0.15, 0.30], "pattern_seed": 3,
           "steps_mix": {"256": 0.4, "512": 0.3, "1024": 0.2, "2048": 0.1}}
    a = open_loop.schedule(trf, 1, 30)
    b = open_loop.schedule(trf, 2 ** 33 + 1, 30)
    assert len(a) == len(b) == 210
    assert [(j["due"], j["steps"]) for j in a] == \
        [(j["due"], j["steps"]) for j in b]
    assert sorted(j["density"] for j in a) == \
        pytest.approx(sorted(j["density"] for j in b))
    assert [j["seed"] for j in a] != [j["seed"] for j in b]
    assert sorted(j["steps"] for j in a).count(2048) == 21
    assert a[0]["due"] == 0.0 and a[-1]["due"] < 30
    quantiles = sorted(-math.log1p(-(k + 0.5) / 210) / 7.0
                       for k in range(210))
    for x, y in zip(a, a[1:]):
        gap = y["due"] - x["due"]
        assert min(abs(gap - q) for q in quantiles) < 1e-9
    other = open_loop.schedule(dict(trf, pattern_seed=4), 1, 30)
    assert [j["steps"] for j in other] != [j["steps"] for j in a]


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_every_peak_names_its_source():
    import json
    for kind, row in json.loads(peaks.TABLE.read_text()).items():
        assert row["source"], kind


def test_checks_pass_only_within_their_limit():
    assert harness.Check("x", 0, 0).ok
    assert not harness.Check("x", 1, 0).ok


def test_lattice_fill_comes_from_the_whole_seed():
    import jax.numpy as jnp
    import numpy as np
    from bench import reference
    rows = jnp.arange(64, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(32, dtype=jnp.uint32)[None, :]
    words = lambda seed: np.asarray(reference.seeded_words(
        reference.seed_key(seed), 0, rows, cols))
    a, b = words(2 ** 33 + 7), words(7)
    assert (a != b).mean() > 0.99
    assert (a == words(2 ** 33 + 7)).all()
    rows = jnp.arange(256, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(64, dtype=jnp.uint32)[None, :]
    east, north = reference.seeded_fill(reference.seed_key(5), 0, rows, cols,
                                        [0.175, 0.175])
    bits = lambda p: np.unpackbits(np.asarray(p).view(np.uint8)).mean()
    assert abs(bits(east) - 0.175) < 0.01 and abs(bits(north) - 0.175) < 0.01
    assert not (np.asarray(east) & np.asarray(north)).any()


def _readers():
    import glob
    return {os.path.basename(p)[:-3]: harness.load_module(harness.Path(p))
            for p in glob.glob(os.path.join(ROOT, "bench", "metrics", "*.py"))}


def test_every_per_layer_metric_has_a_reader():
    bm = harness.load_json(harness.ROOT / "BENCHMARK.json")
    readers = _readers()
    for m in bm["per_layer"]:
        assert m["name"] in readers, m["name"]


def test_readers_return_nothing_without_a_trace():
    for name, mod in _readers().items():
        assert mod.read({"trace": None, "spans": None,
                         "counts": {"site_updates": 0, "ca_steps": 0,
                                    "rounds": 0, "chips": 1}}) is None, name


def test_exposed_share_reads_the_worst_chip():
    from bench import trace
    mod = _readers()["exchange.exposed_share.lattice"]
    devs = [trace.Device(0, [("%collective-permute-done.1", 0, 10),
                             ("fusion.2", 2, 5)]),
            trace.Device(1, [("%collective-permute-done.1", 0, 10),
                             ("fusion.2", 0, 9)])]
    red = trace.Reduction(window=(0.0, 1e9 / 2), devices=devs, spans=[])
    # 7 ns bare on chip 0, 1 ns on chip 1, of a 0.5 s window.
    assert mod.read({"trace": red}) == pytest.approx(7e-9 / 0.5)
    alone = trace.Reduction(window=(0.0, 1e9), spans=[],
                            devices=[trace.Device(0, [("fusion.2", 0, 9)])])
    assert mod.read({"trace": alone}) is None


def test_bookkeeping_share_from_spans():
    mod = _readers()["serve.bookkeeping_share.sweep"]
    spans = {"serve.round": {"total_s": 2.0}, "serve.admit": {"total_s": 0.2},
             "serve.audit": {"total_s": 0.3}, "serve.kernel": {"total_s": 1.0},
             "serve.checkpoint": {"total_s": 0.5}}
    assert mod.read({"spans": spans, "counts": {}}) == pytest.approx(0.5)
