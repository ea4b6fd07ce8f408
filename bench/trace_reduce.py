"""Reduce a profiler trace to device busy and idle time, op time by name,
kernel time, and idle gaps labelled by the host span open during each.

The reduction works on plain events (plane, line, name, start, duration in
ns), so a test can hand-build a trace; :func:`load` reads them from an
``.xplane.pb`` with ``jax.profiler.ProfileData``.  On a TPU the device
planes are named ``/device:TPU:<n>``; each program run is an event on their
``XLA Modules`` line and each HLO op one on ``XLA Ops``.  Host spans
(``jax.profiler.TraceAnnotation``) are events on the ``/host:CPU`` plane.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float     # ns, on the trace's one clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    modules: Dict[str, List[Event]]   # device plane -> program runs
    ops: Dict[str, List[Event]]       # device plane -> HLO ops
    host: List[Event]                 # host spans of interest


def load(path: str, spans: Sequence[str]) -> Trace:
    """Device events and the named host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    want = set(spans)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in want)
    return Trace(modules, ops, host)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], t0: float, t1: float
         ) -> List[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def gaps(busy: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def label(gap: Interval, host: Sequence[Event]) -> str:
    """The host span that covers most of the gap (innermost on a tie)."""
    best, best_cover, best_dur = "no span", 0.0, float("inf")
    for ev in host:
        cover = min(gap[1], ev.end) - max(gap[0], ev.start)
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and ev.dur < best_dur):
            best, best_cover, best_dur = ev.name, cover, ev.dur
    return best


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its self time: its duration less that of the
    events nested in it (a loop op holds the ops of its body)."""
    out: List[Tuple[Event, float]] = []
    stack: List[List] = []                 # [event, self time]
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end, stack[-1][0].end) - e.start
        stack.append([e, e.dur])
    out += [tuple(x) for x in reversed(stack)]
    return out


def op_name(full: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return full.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # mean over the device planes
    module_s: Dict[str, float]         # program name -> device seconds
    module_runs: Dict[str, int]
    op_s: Dict[str, float]             # op name -> device self seconds
    kernel_s: float                    # kernel ops in the counted runs
    gaps: List[Tuple[str, float]]      # (host span, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(tr: Trace, t0: float, t1: float,
           is_kernel: Callable[[str], bool] = lambda name: False) -> Reduced:
    """Reduce the window ``[t0, t1]`` (ns) of a trace."""
    if t1 <= t0:
        raise ValueError("empty trace window")
    planes = sorted(tr.modules) or sorted(tr.ops)
    if not planes:
        raise ValueError("the trace holds no device events")
    busy_total = 0.0
    module_s: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    kernel = 0.0
    gap_list: List[Tuple[str, float]] = []
    for plane in planes:
        runs = tr.modules.get(plane) or tr.ops.get(plane, [])
        busy = merge(clip(((e.start, e.end) for e in runs), t0, t1))
        busy_total += sum(e - s for s, e in busy)
        runs_in = []                       # program runs wholly inside
        for e in tr.modules.get(plane, []):
            if e.start >= t0 and e.end <= t1:
                name = e.name.split("(", 1)[0]
                module_s[name] = module_s.get(name, 0.0) + e.dur * 1e-9
                module_runs[name] = module_runs.get(name, 0) + 1
                runs_in.append((e.start, e.end))
        runs_in = merge(runs_in)
        starts = [s for s, _ in runs_in]
        inside = [e for e in tr.ops.get(plane, [])
                  if e.start >= t0 and e.end <= t1]
        for e, own in self_times(inside):
            name = op_name(e.name)
            op_s[name] = op_s.get(name, 0.0) + own * 1e-9
            k = bisect.bisect_right(starts, e.start) - 1
            if is_kernel(e.name) and k >= 0 and e.start < runs_in[k][1]:
                kernel += e.dur * 1e-9      # kernels of the counted runs
        gap_list += [(label(g, tr.host), (g[1] - g[0]) * 1e-9)
                     for g in gaps(busy, t0, t1)]
    n = len(planes)
    gap_list.sort(key=lambda x: -x[1])
    return Reduced((t1 - t0) * 1e-9, busy_total * 1e-9 / n, module_s,
                   module_runs, op_s, kernel / n, gap_list)


def window(tr: Trace, span: str) -> Optional[Interval]:
    """The extent of the host span ``span`` (the traced window)."""
    evs = [e for e in tr.host if e.name == span]
    if not evs:
        return None
    return min(e.start for e in evs), max(e.end for e in evs)


def breakdown(red: Reduced, top: int = 10) -> Dict[str, list]:
    ops = sorted(red.op_s.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gaps[:top]]}
