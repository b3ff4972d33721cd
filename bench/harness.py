"""Run one cell of ``BENCHMARK.json`` and print its result line.

Everything a cell needs is found by name:

* the cell (``workloads`` entry) names a configuration, a traffic mix
  and the chips it needs;
* ``bench/configs/<config>.json`` is the deployment;
* ``bench/traffic/<traffic>.json`` is the mix; its ``kind`` names the
  driver ``bench/drivers/<kind>.py``, which sets the cell up, measures
  the window and checks what the timed path produced;
* each per-layer metric is read by ``bench/metrics/<metric>.py``.

So a cell, configuration, traffic mix or metric is added by adding
files and entries; no file here changes.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back.  ``end_to_end`` holds every end-to-end
    metric the driver measures; ``readings`` what the per-layer metric
    readers take (``trace``: a ``bench.trace.Reduction`` or None;
    ``spans``: the program's span totals or None; ``counts``: counts
    made inside the traced window)."""
    end_to_end: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    readings: Dict[str, object] = dataclasses.field(default_factory=dict)
    control: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Cell:
    """One run's inputs, passed to the driver."""
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    started: float                 # perf_counter at process start
    workdir: str                   # run-local scratch (checkpoints, trace)
    devices: list
    control: bool = False
    log: Callable[[str], None] = lambda msg: print(msg, file=sys.stderr,
                                                   flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import the file ``path`` (names may hold dots, so not by import)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell_metrics(bm: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: with ``trace`` the
    per-layer ones that list it, or that list no cells and move an
    end-to-end metric the cell reports; else its end-to-end ones."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def require_chips(devices, chips: int) -> None:
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {len(devices)} {platform} "
                     f"device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devices)}")


class Profiler:
    """``jax.profiler`` trace of one window, reduced on exit; a no-op
    when ``on`` is false."""

    def __init__(self, on: bool, directory: str):
        self.on, self.directory = on, directory
        self.reduction = None

    def __enter__(self):
        if self.on:
            import jax
            jax.profiler.start_trace(self.directory)
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            jax.profiler.stop_trace()
        return False

    def reduce(self):
        if self.on and self.reduction is None:
            from bench import trace
            self.reduction = trace.load(self.directory)
        return self.reduction


def run_cell(args, *, require_tpu: bool = True, root: Path = ROOT,
             overrides: Optional[dict] = None, started: float = None,
             control: bool = False) -> dict:
    """Run the cell ``args.workload``; return the result line as a dict,
    with the driver's whole ``Outcome`` under ``_outcome``."""
    started = time.perf_counter() if started is None else started
    bench = root / "bench"
    bm = load_json(root / "BENCHMARK.json")
    entry = find(bm["workloads"], args.workload, "workload")
    config = load_json(bench / "configs" / f"{entry['config']}.json")
    traffic = load_json(bench / "traffic" / f"{entry['traffic']}.json")
    for key, extra in (overrides or {}).items():
        {"config": config, "traffic": traffic}[key].update(extra)
    chips = int(entry["chips"])

    import jax
    devices = jax.devices()
    if require_tpu:
        require_chips(devices, chips)
    devices = devices[:chips]
    kind = devices[0].device_kind
    if require_tpu:
        from bench import peaks
        hbm = peaks.peaks_for(kind)["hbm_bytes"]
        from repro.launch import compile_cache
        cache = compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(f"compile cache: {cache}", file=sys.stderr, flush=True)
    print(f"devices: {len(devices)} x {kind} ({devices[0].platform}); "
          f"jax {jax.__version__}", file=sys.stderr, flush=True)

    driver = load_module(bench / "drivers" / f"{traffic['kind']}.py")
    with tempfile.TemporaryDirectory(prefix="bench-") as workdir:
        cell = Cell(name=args.workload, config=config, traffic=traffic,
                    chips=chips, seed=int(args.seed),
                    seconds=float(args.seconds), trace=bool(args.trace),
                    started=started, workdir=workdir, devices=devices,
                    control=control)
        out: Outcome = driver.run(cell)

    metrics = {}
    for m in cell_metrics(bm, args.workload, bool(args.trace)):
        if args.trace:
            reader = load_module(bench / "metrics" / f"{m['name']}.py")
            value = reader.read(out.readings)
            if value is None:
                continue
        else:
            value = out.end_to_end[m["name"]]
        if not math.isfinite(value):
            raise ValueError(f"{m['name']} is {value}: not a number to "
                             f"report")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    red = out.readings.get("trace")
    if args.trace and red is not None:
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    if require_tpu:
        print(f"memory: peak {out.memory_peak_bytes} B of {hbm} B HBM "
              f"({100.0 * out.memory_peak_bytes / hbm:.2f}%)",
              file=sys.stderr, flush=True)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    result["_outcome"] = out
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, started: float = None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args, started=started)
    except NoChip as e:
        print(f"run_cell: {e}; nothing was run", file=sys.stderr)
        return 2
    result.pop("_outcome")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
