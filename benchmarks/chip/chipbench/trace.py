"""Reduce a profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into plain
lists: per device, the ``XLA Ops`` events (name, start ns, duration ns);
on the host, the benchmark's own spans (``bench.*`` annotations).
``reduce`` works on those lists alone, so it can be checked on a small
recorded trace:

* ``busy_s``: per device, the union of its op intervals inside the window;
* ``op_s``: self seconds per op (its short name, the HLO text before
  `` = ``) inside the window -- the time in which it is the innermost op
  running, so an op's time less that of the ops nested in it, as a
  ``while`` holds its body's -- mean over devices; they add up to
  ``busy_s``;
* ``gaps``: the longest idle stretches on the first device, each labelled
  by the benchmark span it fell in and the op that ran before it.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start ns, duration ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"want one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str) -> dict:
    """``{"devices": {id: [Event]}, "spans": [Event]}`` from an xplane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[dev] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def _merge(intervals) -> List[List[float]]:
    """Union of [start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Reduction:
    window_s: float
    busy_s: Dict[int, float]             # per device
    op_s: Dict[str, float]               # per op name, mean over devices
    gaps: List[Tuple[str, float]]        # (what the host was in, seconds)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)


def reduce(data: dict, window: Optional[Tuple[float, float]] = None,
           devices: Optional[List[int]] = None,
           n_gaps: int = 10) -> Reduction:
    """Reduce ``load``'s lists over ``window`` = (start ns, end ns), by
    default the ``bench.window`` span.  Ops are clipped to the window;
    ``devices`` limits the devices read (default: all in the trace)."""
    if window is None:
        marks = [(s, s + d) for name, s, d in data["spans"]
                 if name == WINDOW_SPAN]
        if len(marks) != 1:
            raise ValueError(f"want one {WINDOW_SPAN} span, found "
                             f"{len(marks)}")
        window = marks[0]
    lo, hi = window
    ids = sorted(data["devices"]) if devices is None else list(devices)
    if not ids:
        raise ValueError("the trace holds no device ops")
    events: Dict[int, List[Event]] = {}
    busy: Dict[int, float] = {}
    op_ns: Dict[str, float] = {}
    for dev in ids:
        evs = []
        for name, s, d in data["devices"].get(dev, []):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                evs.append((name, a, b - a))
        events[dev] = evs
        for name, ns in _self_ns(evs).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
        busy[dev] = sum(e - s for s, e in
                        _merge((s, s + d) for _, s, d in evs)) * 1e-9
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy,
        op_s={k: v * 1e-9 / len(ids) for k, v in op_ns.items()},
        gaps=_gaps(events[ids[0]], data["spans"], lo, hi, n_gaps))


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_ns(events) -> Dict[str, float]:
    """Self time per short op name: the time in which the op is the latest
    started of the ops running.  A nested op takes its interval out of the
    op around it, as a ``while`` holds its body's; one that runs on past
    the end of the op it started in (a loop's last body fusion can end
    after its ``while`` in a TPU trace) takes the overlap too.  So the self
    times add up to the union of the intervals."""
    out: Dict[str, float] = {}
    running: List[Tuple[float, str]] = []        # (end, short name)
    t = -math.inf

    def advance(to):
        nonlocal t
        while running and t < to:
            end, short = running[-1]
            if end <= t:
                running.pop()
                continue
            step = min(end, to)
            out[short] += step - t
            t = step
        t = max(t, to)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        advance(s)
        short = short_name(name)
        out.setdefault(short, 0.0)
        running.append((s + d, short))
    advance(math.inf)
    return out


def _span_at(spans, t) -> str:
    """Innermost (shortest) benchmark span covering ``t``."""
    inside = [(d, name) for name, s, d in spans if s <= t < s + d]
    return min(inside)[1] if inside else "outside the benchmark's spans"


def _gaps(events, spans, lo, hi, n) -> List[Tuple[str, float]]:
    """The ``n`` longest idle stretches in [lo, hi), longest first."""
    found = []
    edge = lo
    for s, e in _merge((s, s + d) for _, s, d in events) + [[hi, hi]]:
        if s > edge:
            found.append((s - edge, edge, s))
        edge = max(edge, e)
    found.sort(reverse=True)
    ends = sorted((s + d, name) for name, s, d in events)
    end_times = [t for t, _ in ends]
    out = []
    for length, a, b in found[:n]:
        i = bisect.bisect_right(end_times, a) - 1
        before = short_name(ends[i][1]) if i >= 0 else "the window's start"
        out.append((f"{_span_at(spans, (a + b) / 2)}, after {before}",
                    length * 1e-9))
    return out
