"""The chip benchmark's harness: BENCHMARK.json keeps to its format,
run_cell refuses without a TPU, and a cell is added by files and an
entry alone."""
import json
import os
import re
import shutil
import subprocess
import sys


ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_its_format():
    b = bm()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for path in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path)), path
    assert any(os.path.join(ROOT, p, "run_cell.py") ==
               os.path.join(ROOT, b["command"][-1]) for p in b["paths"])
    assert 1 <= b["run_seconds"] <= 51
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        for key in c["reduced"]:
            assert NAME.match(key)
    pairs = set()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        reported = harness.cell_metrics(b, w["name"], trace=False)
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2, w["name"]
        assert harness.cell_metrics(b, w["name"], trace=True), w["name"]


def test_run_cell_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         "fhp2-flow.lattice", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU found" in proc.stderr


def test_run_cell_fails_with_only_the_benchmark_files(tmp_path):
    """A checkout that holds BENCHMARK.json and the benchmark's paths but
    not the program: the run (its look for a chip skipped, as no chip is
    here) fails, printing no result."""
    b = bm()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in b["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    script = (
        "import sys, pathlib; root = pathlib.Path.cwd(); "
        "sys.path[:0] = [str(root / 'src'), str(root)]; "
        "from bench import harness; "
        f"args = harness.parse(['--workload', '{b['workloads'][0]['name']}',"
        " '--seed', '1', '--seconds', '1']); "
        "print(harness.run_cell(args, require_tpu=False, root=root))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300,
                          env=dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "No module named 'repro'" in proc.stderr


def test_a_cell_is_added_by_files_and_an_entry(tmp_path, monkeypatch):
    from repro.kernels.fhp_step import ops
    monkeypatch.setattr(ops, "autotune_launch",
                        lambda h, wd, **kw: (h, wd, 1))
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "configs" / "bml-small.json").write_text(
        json.dumps({"source": "test", "rule": "bml", "p_force": 0.0,
                    "lattice": {"height": 16, "width": 2048, "east": 0.2,
                                "north": 0.1},
                    "guarantees": {"conserved": [[0], [1]]}}))
    (tmp_path / "bench" / "traffic" / "short.json").write_text(
        json.dumps({"kind": "lattice", "steps_per_call": 2,
                    "check_bands": 1}))
    b = bm()
    b["configs"].append({"name": "bml-small", "source": "test",
                         "file": "bench/configs/bml-small.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "bml-small.short", "config": "bml-small",
                           "traffic": "short", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "site_updates_per_s":
            m["workloads"].append("bml-small.short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    args = harness.parse(["--workload", "bml-small.short", "--seed", "5",
                          "--seconds", "0.3"])
    result = harness.run_cell(args, require_tpu=False, root=tmp_path)
    assert result["correct"]
    assert set(result["metrics"]) == {"site_updates_per_s", "setup_s"}
    assert result["device"]["count"] == 1
    for name, entry in result["checks"].items():
        assert set(entry) == {"value", "limit"}, name
    assert list(result)[-2:] == ["checks", "_outcome"]
