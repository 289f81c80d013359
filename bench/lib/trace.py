"""Reduce a profiler trace (`*.xplane.pb`) to the numbers the per-layer
metrics read.

* Busy time: the union of the intervals of the device's operations (the
  "XLA Ops" line of each `/device:TPU:n` plane), averaged over devices.
  The window is the span of every event of the trace, host and device.
* Device time by op: grouped by `kernels.op_name` (a GEMM kernel's name,
  or the HLO instruction's without its numeric suffix); on a TPU an op's
  event name is its HLO text.  A `while` loop's event spans its body's
  ops, so it counts as busy but has no time of its own here.
* Idle gaps: the stretches between the busy intervals; the longest are
  named by the innermost host event that covers their middle, or else
  "after <the host event that ended last before it>".
* Idle by second: the idle share of each whole second of the window
  (device 0), to show how far one slice of it stands for the rest.

It reads the file with `jax.profiler.ProfileData`, which needs no chip.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import pathlib
from typing import Dict, List, Optional, Tuple

from bench.lib import kernels

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 1_000          # shorter gaps are the device's own seams
NAMED_GAPS = 200            # how many of the longest gaps are named
#: ops whose event spans the ops of their body: busy, but not their own time
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: int              # ns
    end: int


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    n_events: int
    op_s: Dict[str, float]                  # device time by op name
    ops: List[Event]                        # device ops of device 0
    gaps: List[Tuple[str, float]]           # the longest, named, seconds
    idle_s: float                           # all gaps of device 0
    idle_by_s: List[float]                  # idle share of each second

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}

    def gaps_by_host(self) -> List[Tuple[str, float]]:
        by = collections.Counter()
        for host, s in self.gaps:
            by[host] += s
        return by.most_common()


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(e.name, start, start + int(e.duration_ns)))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def idle_shares(busy: List[Tuple[int, int]], lo: int, hi: int,
                bin_ns: int = 1_000_000_000) -> List[float]:
    """The idle share of each whole bin of [lo, hi), given the union of
    busy intervals."""
    out = []
    for a in range(lo, hi - bin_ns + 1, bin_ns):
        b = a + bin_ns
        used = sum(min(b, y) - max(a, x) for x, y in busy if x < b and y > a)
        out.append(1.0 - used / bin_ns)
    return out


def _name_gap(a: int, b: int, host: List[Event]) -> str:
    mid = (a + b) // 2
    cover = [e for e in host if e.start <= mid < e.end]
    if cover:
        return min(cover, key=lambda e: e.end - e.start).name
    before = [e for e in host if e.end <= a]
    if before:
        return "after " + max(before, key=lambda e: e.end).name
    return "no host event"


def reduce_planes(planes) -> Reduced:
    """planes: iterable of objects with `.name` and `.lines` (each line
    with `.name` and `.events`), as `ProfileData` gives them."""
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    lo, hi = None, None
    for plane in planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            evs = _events(line)
            if not evs:
                continue
            lo = min(e.start for e in evs) if lo is None else \
                min(lo, min(e.start for e in evs))
            hi = max(e.end for e in evs) if hi is None else \
                max(hi, max(e.end for e in evs))
            if is_dev and line.name == OPS_LINE:
                devices.setdefault(plane.name, []).extend(evs)
            elif not is_dev:
                host.extend(evs)
    window = (hi - lo) if lo is not None else 0
    busy = []
    op_s: Dict[str, float] = collections.Counter()
    spans: List[Tuple[int, int]] = []
    by_s: List[float] = []
    first: Optional[str] = min(devices) if devices else None
    for name, evs in devices.items():
        u = union([(e.start, e.end) for e in evs])
        busy.append(sum(b - a for a, b in u))
        if name != first:
            continue
        for e in evs:
            name = kernels.op_name(e.name)
            if name not in CONTAINERS:
                op_s[name] += (e.end - e.start) / 1e9
        by_s = idle_shares(u, lo, hi)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        spans = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                 if b - a >= MIN_GAP_NS]
    spans.sort(key=lambda ab: ab[0] - ab[1])
    gaps = [(_name_gap(a, b, host), (b - a) / 1e9)
            for a, b in spans[:NAMED_GAPS]]
    return Reduced(
        busy_s=(sum(busy) / len(busy) / 1e9) if busy else 0.0,
        window_s=window / 1e9,
        n_events=sum(len(v) for v in devices.values()),
        op_s=dict(op_s), ops=devices.get(first, []) if first else [],
        gaps=gaps, idle_s=sum(b - a for a, b in spans) / 1e9,
        idle_by_s=by_s)


def reduce_file(path) -> Reduced:
    """A `*.xplane.pb` file, or one compressed with gzip (`*.gz`)."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    return reduce_planes(data.planes)


def reduce_dir(directory) -> Reduced:
    """The newest `*.xplane.pb` under a `jax.profiler.start_trace`
    directory."""
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return reduce_file(files[-1])
