"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

A TPU trace holds one plane per chip, named `/device:TPU:<i>`, with a
line `XLA Modules` (one event per program execution, named
`<module>(<id>)`) and a line `XLA Ops` (one event per operation).  The
host plane `/host:CPU` holds the host threads' events, among them the
`TraceAnnotation`s that `repro.obs` opens around its spans when its
tracer runs with `jax_annotations=True`, and the harness's own
`bench.window` annotation, which marks the traced window.

Everything is computed on the trace's own clock:

* busy: the union of the intervals in which an operation ran on the
  chip, inside the window (falling back to the module events where a
  device plane has no op line);
* module time: the summed durations of a module's executions, by name
  with the execution id stripped;
* breakdown: the operations that took most time, and the idle gaps,
  summed by the innermost host annotation open at their midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_MARK = "bench.window"
NAME_CHARS = 120            # an operation's name is its whole HLO line
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """The parts of one trace that the reduction reads."""
    devices: Dict[str, Dict[str, List[Event]]]   # plane -> line -> events
    host: List[Event]                             # host-thread events
    window: Optional[Tuple[float, float]]         # bench.window, ns


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    lines[line.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    marks = [e for e in host if e.name == WINDOW_MARK]
    window = ((min(e.start_ns for e in marks),
               max(e.end_ns for e in marks)) if marks else None)
    return Trace(devices, host, window)


def module_name(event_name: str) -> str:
    return _ID_SUFFIX.sub("", event_name)


def _clip(iv: Iterable[Tuple[float, float]], lo: float, hi: float):
    for a, b in iv:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _busy_events(lines: Dict[str, List[Event]]) -> List[Event]:
    return lines.get("XLA Ops") or lines.get("XLA Modules") or []


def _window(tr: Trace) -> Tuple[float, float]:
    if tr.window is not None:
        return tr.window
    evs = [e for lines in tr.devices.values()
           for e in _busy_events(lines)]
    if not evs:
        raise ValueError("trace has neither a window mark nor device "
                         "events")
    return (min(e.start_ns for e in evs), max(e.end_ns for e in evs))


def busy_intervals(tr: Trace, plane: str) -> List[Tuple[float, float]]:
    lo, hi = _window(tr)
    evs = _busy_events(tr.devices[plane])
    return union(_clip(((e.start_ns, e.end_ns) for e in evs), lo, hi))


def busy_window(tr: Trace, planes: Optional[Sequence[str]] = None
                ) -> Tuple[float, float]:
    """(busy seconds averaged over the chips, window seconds)."""
    lo, hi = _window(tr)
    planes = list(planes) if planes is not None else sorted(tr.devices)
    if not planes:
        raise ValueError("trace holds no TPU device plane")
    busy = [sum(b - a for a, b in busy_intervals(tr, p)) for p in planes]
    return sum(busy) / len(busy) * 1e-9, (hi - lo) * 1e-9


def module_seconds(tr: Trace, planes: Optional[Sequence[str]] = None
                   ) -> Dict[str, float]:
    """Seconds per module name inside the window, averaged over chips."""
    lo, hi = _window(tr)
    planes = list(planes) if planes is not None else sorted(tr.devices)
    out: Dict[str, float] = {}
    for p in planes:
        for e in tr.devices[p].get("XLA Modules", []):
            for a, b in _clip([(e.start_ns, e.end_ns)], lo, hi):
                name = module_name(e.name)
                out[name] = out.get(name, 0.0) + (b - a) * 1e-9 / len(planes)
    return out


def top_ops(tr: Trace, plane: str, n: int = 10) -> List[List]:
    """The n operations with the most device seconds in the window (the
    n modules, where the trace recorded no operations)."""
    lo, hi = _window(tr)
    lines = tr.devices[plane]
    ops = "XLA Ops" in lines and bool(lines["XLA Ops"])
    tot: Dict[str, float] = {}
    for e in lines["XLA Ops"] if ops else lines.get("XLA Modules", []):
        name = e.name[:NAME_CHARS] if ops else module_name(e.name)
        for a, b in _clip([(e.start_ns, e.end_ns)], lo, hi):
            tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host(tr: Trace, plane: str, labels: Sequence[str],
                 n: int = 10) -> List[List]:
    """Idle seconds of the chip in the window, summed by the innermost
    host event whose name is in `labels` open at each gap's midpoint
    ("no span" where none is)."""
    lo, hi = _window(tr)
    busy = busy_intervals(tr, plane)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    wanted = set(labels)
    spans = sorted((e for e in tr.host if e.name in wanted),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        best = None
        for e in spans[:bisect.bisect_right(starts, mid)]:
            if e.end_ns > mid and (best is None
                                   or e.start_ns >= best.start_ns):
                best = e
        key = best.name if best is not None else "no span"
        tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
