"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Reads the file with ``jax.profiler.ProfileData`` alone.  Per device of
the trace: the union of the intervals in which an operation ran (busy),
the kernel's events, the collective events and the part of each that no
other operation overlaps (exposed).  From the host: the benchmark's own
spans (``jax.profiler.TraceAnnotation`` names starting ``bench.``),
which bound the traced window and label the device's idle gaps.

All times are nanoseconds on the trace's clock, which host and device
planes share.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# The kernel's events carry the HLO text of the custom call, which names
# the jitted function around the ``pallas_call``:
# "%fhp_step_pallas.7 = u32[...] custom-call(...), custom_call_target=
# "tpu_custom_call" ...".
KERNEL = r"^%fhp_step_pallas[.\d]* = .*tpu_custom_call"
# A collective's own name, plain or as the start of its HLO text
# ("%collective-permute-done.3 = u32[...] collective-permute-done(...)");
# an op that only takes a collective's result names it further on.
COLLECTIVE = re.compile(r"^%?(?:collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|ppermute)[\w.-]*(?: |$)",
                        re.I)


def find_xplane(directory: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler.trace`` wrote under
    ``directory``."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory},"
                                f" found {len(found)}")
    return found[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals: Iterable[Interval], cover: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of ``intervals`` that ``cover`` (a union) leaves bare."""
    out = []
    for a, b in union(intervals):
        cur = a
        for c, d in cover:
            if d <= cur:
                continue
            if c >= b:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


@dataclasses.dataclass
class Device:
    """One device's operations inside the traced window."""
    ordinal: int
    ops: List[Tuple[str, float, float]]

    def busy(self) -> List[Interval]:
        return union((a, b) for _, a, b in self.ops)

    def leaves(self) -> List[Tuple[str, float, float]]:
        """The ops that hold no other op (one core runs one op at a time,
        so an op that another starts inside is a container)."""
        ops = sorted(self.ops, key=lambda op: (op[1], -op[2]))
        return [op for op, nxt in zip(ops, ops[1:] + [None])
                if nxt is None or nxt[1] >= op[2]]

    def events(self, pattern: str) -> List[Tuple[str, float, float]]:
        rx = re.compile(pattern)
        return [op for op in self.ops if rx.search(op[0])]

    def exposed_collectives(self) -> List[Interval]:
        """Collective time during which no other operation runs.  Only
        ops that hold no other op count as cover: a container such as
        the ``while`` around a round spans its collectives too."""
        coll = [(a, b) for n, a, b in self.ops if COLLECTIVE.search(n)]
        other = union((a, b) for n, a, b in self.leaves()
                      if not COLLECTIVE.search(n))
        return subtract(coll, other)


@dataclasses.dataclass
class Reduction:
    """A traced window: its bounds, each device's ops, the host spans."""
    window: Interval
    devices: List[Device]
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(length(d.busy()) for d in self.devices) / 1e9 / max(
            len(self.devices), 1)

    def idle_share(self) -> float:
        """1 - busy / window, averaged over the devices."""
        return 1.0 - self.busy_s() / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device operations that took most time, summed by name over
        the devices, in seconds; a control-flow op that holds others
        (a ``while`` around the kernel) is left out, its body counted."""
        tot: Dict[str, float] = {}
        for d in self.devices:
            for name, a, b in d.leaves():
                tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of device 0 in the window, each named by
        the innermost benchmark span active at its midpoint."""
        if not self.devices:
            return []
        busy = self.devices[0].busy()
        gaps = subtract([self.window], busy)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) / 2
            active = [s for s in self.spans if s[1] <= mid < s[2]
                      and s[0] != WINDOW_SPAN]
            label = (min(active, key=lambda s: s[2] - s[1])[0]
                     if active else "outside any benchmark span")
            out.append((label, (b - a) / 1e9))
        return out


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce(profile, window_span: str = WINDOW_SPAN) -> Reduction:
    """Reduce a ``ProfileData`` to the window bounded by the host span
    ``window_span`` (the whole device activity when the trace has none)."""
    spans: List[Tuple[str, float, float]] = []
    devices: List[Device] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += list(_events(line))
            devices.append(Device(int(m.group(1)), ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [s for s in _events(line)
                          if s[0].startswith(SPAN_PREFIX)]
    windows = [(a, b) for n, a, b in spans if n == window_span]
    if windows:
        window = (min(a for a, _ in windows), max(b for _, b in windows))
    else:
        all_ops = [(a, b) for d in devices for _, a, b in d.ops]
        if not all_ops:
            raise ValueError("the trace holds no device operation")
        window = (min(a for a, _ in all_ops), max(b for _, b in all_ops))
    for d in devices:
        d.ops = [(n, a2, b2) for n, a, b in d.ops
                 for a2, b2 in clip([(a, b)], *window)]
    devices.sort(key=lambda d: d.ordinal)
    return Reduction(window=window, devices=devices, spans=spans)


def load(path: str) -> Reduction:
    """Reduce the ``.xplane.pb`` at ``path`` (a file or the directory
    ``jax.profiler.trace`` wrote into)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce(ProfileData.from_file(path))


def idle_share(readings):
    """The metric readers' idle share: ``Reduction.idle_share`` of the
    traced run, or None without a trace."""
    red = readings.get("trace")
    if red is None or not red.devices:
        return None
    return red.idle_share()


def kernel_seconds(dev: Device, pattern: str) -> float:
    """Summed device time of the events whose name matches ``pattern``."""
    return sum(b - a for _, a, b in dev.events(pattern)) / 1e9
