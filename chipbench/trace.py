"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

What is read:

- device operations: the events of each device plane's ``XLA Ops`` line
  (``/device:TPU:<i>``).  On a host with no device plane (the CPU backend)
  the events that carry an ``hlo_op`` statistic stand in, one device per
  ``device_ordinal``: that is what lets the reduction be checked on a CPU;
- the harness's host spans: events named ``bench.*`` (``TraceAnnotation``),
  among them ``bench.window`` around the measured loop.

What is computed, inside the window: each device's busy time (the union of
its operation intervals), the time of operations whose names match a
kernel's pattern, the device operations that took most
time, and the longest idle gaps of the first device, each named by the host
span that overlaps it most.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10


class Event(NamedTuple):
    name: str
    text: str       # the name and every string statistic, for matching
    start: float    # seconds
    end: float


class Reduction(NamedTuple):
    devices: int
    window_s: float
    busy_s: float                  # mean over devices
    kernel_s: Dict[str, float]     # pattern name -> mean seconds per device
    device_ops: List[List]         # [[name, seconds summed over devices]]
    idle_gaps: List[List]          # [[host span, seconds]] of device 0


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(paths)}")
    return paths[0]


def _event(e) -> Event:
    """One event, named by its operation alone: a TPU op's name is its whole
    HLO instruction (``%fusion.6 = f32[...] fusion(...)``), which stays in
    ``text`` for matching."""
    texts = [e.name] + [str(v) for _, v in e.stats if isinstance(v, str)]
    start = e.start_ns * 1e-9
    return Event(e.name.split(" = ", 1)[0], " ".join(texts), start,
                 start + e.duration_ns * 1e-9)


def read(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device operations by device, harness host spans) of one trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = collections.defaultdict(list)
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name].extend(_event(e) for e in line.events)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(_event(e))
                elif not any(p.startswith("/device:") for p in ops):
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        ops[f"cpu:{stats.get('device_ordinal', 0)}"].append(
                            _event(e))
    return dict(ops), spans


def _clip(evs: List[Event], lo: float, hi: float) -> List[Tuple[float, float, Event]]:
    return [(max(e.start, lo), min(e.end, hi), e) for e in evs
            if e.end > lo and e.start < hi]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _label(a: float, b: float, spans: List[Event]) -> str:
    best, most = "host: outside any span", 0.0
    for s in spans:
        if s.name == WINDOW:
            continue
        over = min(b, s.end) - max(a, s.start)
        if over > most:
            best, most = s.name, over
    return best


def reduce(ops: Dict[str, List[Event]], spans: List[Event],
           kernels: Optional[Dict[str, str]] = None) -> Reduction:
    """The window's numbers.  ``kernels`` maps a name to a regular
    expression searched in each operation's name and string statistics."""
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    if not ops:
        raise ValueError("the trace holds no device operations")
    pats = {k: re.compile(v) for k, v in (kernels or {}).items()}
    busy = []
    kern = {k: 0.0 for k in pats}
    by_name: Dict[str, float] = collections.defaultdict(float)
    gaps: List[List] = []
    for i, dev in enumerate(sorted(ops)):
        clipped = _clip(ops[dev], lo, hi)
        merged = _union([(a, b) for a, b, _ in clipped])
        busy.append(sum(b - a for a, b in merged))
        for k, p in pats.items():
            kern[k] += sum(b - a for a, b, e in clipped if p.search(e.text))
        for a, b, e in clipped:
            by_name[e.name] += b - a
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append([_label(a, b, spans), b - a])
    n = len(ops)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduction(
        devices=n, window_s=hi - lo, busy_s=sum(busy) / n,
        kernel_s={k: v / n for k, v in kern.items()},
        device_ops=[[k, v] for k, v in top],
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:TOP])
