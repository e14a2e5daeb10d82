"""Arithmetic shared by the per-layer metric readers in `metrics/`.

Each reader is `read(run) -> float | None` (`run.Run` says what `run`
holds); it returns None where it finds nothing to read, and the harness
then leaves the metric out of the line.
"""
from __future__ import annotations

from typing import Iterable, Optional

from bench import work


def span_durations(run, name: str):
    return [s["dur"] for s in run.spans if s["name"] == name]


def mean_span_ms(run, name: str) -> Optional[float]:
    d = span_durations(run, name)
    return sum(d) / len(d) * 1e3 if d else None


def module_ms_per_query(run, modules: Iterable[str]) -> Optional[float]:
    """Device milliseconds of the named XLA modules per answered query."""
    found = [run.modules[m] for m in modules if m in run.modules]
    if not found or not run.traced_queries:
        return None
    return sum(found) / run.traced_queries * 1e3


def idle_share(run) -> Optional[float]:
    if not run.window_s:
        return None
    return (1.0 - run.busy_s / run.window_s) * 100.0


def roofline_share(run, modules: Iterable[str]) -> Optional[float]:
    """Least time of the verification work of the traced window's
    requests (the mean over all answered requests, times the traced
    ones) over the device time of the named modules, in percent."""
    t = sum(run.modules.get(m, 0.0) for m in modules)
    if t <= 0 or not run.stats or not run.traced_queries:
        return None
    c = run.config
    nbytes = flops = 0.0
    for qlen, st in run.stats:
        b, f = work.verify_work(st, qlen, c["gamma"], c["measure"],
                                c.get("r", 0))
        nbytes, flops = nbytes + b, flops + f
    least, _ = work.least_seconds(nbytes, flops, run.peaks)
    return least / len(run.stats) * run.traced_queries / t * 100.0
