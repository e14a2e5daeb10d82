"""repro.obs — unified tracing, pruning telemetry, and metrics export.

One substrate, three surfaces (DESIGN.md §12):

  * ``get_tracer()`` / ``span(...)`` — the process-wide sampling
    `Tracer`.  Engine and server call ``span()``, ``wait_span()`` and
    ``device_stage()`` unconditionally; each is a near-free no-op until
    someone calls ``get_tracer().configure(enabled=True)``.  While it
    is on, every compile JAX reports is recorded as a ``jax.compile``
    span under the span open on the compiling thread.
  * ``get_registry()`` — the process-wide `MetricsRegistry` that
    `ServeMetrics` mirrors into and `record_search_stats` feeds, with
    Prometheus text / JSON snapshot exporters.
  * ``record_search_stats(stats, backend=...)`` — fold one query's
    `SearchStats` into the registry as ``ulisse_engine_*`` counters.

The engine populates a single `SearchStats` schema on every backend
(host, device, distributed-per-shard); this module is where those
numbers become scrapeable.
"""
from __future__ import annotations

import time

import jax.monitoring

from .registry import DEFAULT_BUCKETS, MetricsRegistry
from .tracer import Span, Tracer

__all__ = [
    "DEFAULT_BUCKETS",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "device_stage",
    "get_registry",
    "get_tracer",
    "record_page_stats",
    "record_search_stats",
    "set_registry",
    "set_tracer",
    "span",
    "wait_span",
]

_tracer = Tracer()
_registry = MetricsRegistry()


def get_tracer() -> Tracer:
    """The process-wide tracer (disabled until configured)."""
    return _tracer


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-wide tracer (tests); returns the previous one."""
    global _tracer
    prev, _tracer = _tracer, tracer
    return prev


def span(name: str, **attrs):
    """Open a span on the process-wide tracer — the one call sites use."""
    return _tracer.span(name, **attrs)


def wait_span(name: str, **attrs):
    """Open a wait span (outside the sampling draw) on the process-wide
    tracer."""
    return _tracer.wait_span(name, **attrs)


def device_stage(name: str, **attrs):
    """Mark the launch of a device stage on the process-wide tracer;
    ``.ready(out)`` on the result ends it when `out` is ready."""
    return _tracer.device_stage(name, **attrs)


# JAX reports each phase of a compile (jaxpr trace, lowering to MLIR,
# backend compile) as "/jax/core/compile/<phase>_duration" with the
# function's name, on the compiling thread, as the phase ends
_COMPILE_EVENT = "/jax/core/compile/"


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    if not _tracer.enabled or not event.startswith(_COMPILE_EVENT):
        return
    t1 = time.perf_counter()
    phase = event[len(_COMPILE_EVENT):].removesuffix("_duration")
    _tracer.record_interval("jax.compile", t1 - duration, t1,
                            fun_name=str(kw.get("fun_name", "")),
                            phase=phase)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def get_registry() -> MetricsRegistry:
    """The process-wide metrics registry."""
    return _registry


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    """Swap the process-wide registry (tests); returns the previous one."""
    global _registry
    prev, _registry = _registry, registry
    return prev


# SearchStats counter fields exported per query.  Everything here is a
# monotone per-query count, so summing across queries stays meaningful.
_STATS_COUNTERS = (
    ("envelopes_total", "Envelopes in scope across queries"),
    ("envelopes_checked", "Envelopes surviving LB pruning"),
    ("envelopes_pruned", "Envelopes cut by LB/bsf inside visited chunks"),
    ("lb_computations", "Envelope lower-bound evaluations"),
    ("true_dist_computations", "True-distance window verifications"),
    ("dtw_lb_keogh", "DTW LB_Keogh band evaluations"),
    ("dtw_full", "Full DTW dynamic programs run"),
    ("chunks_visited", "Scan chunks actually executed"),
    ("chunks_planned", "Scan chunks in the dispatch plan"),
    ("escalations", "verify_top escalation rounds"),
    ("range_overflows", "Device range hits past capacity (host tail)"),
)


def _check_stats_schema() -> None:
    """Pin the exporter to the device stats schema (analysis rule R5).

    PR 7 widened the device stats vector 5 -> 6 and this exporter
    tracked it by hand; now the width/column source of truth is
    `executor.STATS_COLUMNS` and a drift (a device counter column with
    no exporter field) fails at import time instead of silently
    exporting a truncated schema."""
    from repro.core.executor import STATS_COLUMNS, STATS_WIDTH
    exported = {f for f, _ in _STATS_COUNTERS}
    missing = [c for c in STATS_COLUMNS if c not in exported]
    assert len(STATS_COLUMNS) == STATS_WIDTH and not missing, (
        f"obs exporter is missing device stats columns {missing}; "
        "extend _STATS_COUNTERS when executor.STATS_COLUMNS grows")


_check_stats_schema()


def record_search_stats(stats, backend: str = "local",
                        registry: MetricsRegistry | None = None) -> None:
    """Fold one query's `SearchStats` into ``ulisse_engine_*`` counters,
    labelled by backend (host / device / distributed)."""
    reg = registry if registry is not None else _registry
    for field, help_text in _STATS_COUNTERS:
        v = getattr(stats, field, 0)
        if v:
            reg.inc("ulisse_engine_" + field, float(v),
                    help_text=help_text, backend=backend)
    reg.inc("ulisse_engine_queries", 1.0,
            help_text="Queries with recorded stats", backend=backend)


# Page-cache counter deltas exported by `record_page_stats`; cache_bytes
# is a gauge (current residency), everything else is monotone.
_PAGE_COUNTERS = (
    ("hits", "Page cache hits"),
    ("misses", "Page cache misses (shard faults)"),
    ("evicted_bytes", "Bytes evicted from the page cache"),
)


def record_page_stats(delta, cache_bytes: float,
                      registry: MetricsRegistry | None = None) -> None:
    """Fold a page-cache stats *delta* into ``ulisse_page_cache_*``.

    `delta` holds hit/miss/evicted_bytes increments since the caller's
    last snapshot (PayloadStore.stats() counters are cumulative, so the
    caller diffs); `cache_bytes` is the current resident byte count.
    The engine hot path stays registry-free (DESIGN.md §12) — the serve
    dispatcher mirrors the store's counters here after each batch."""
    reg = registry if registry is not None else _registry
    for field, help_text in _PAGE_COUNTERS:
        v = delta.get(field, 0)
        if v:
            reg.inc("ulisse_page_cache_" + field + "_total", float(v),
                    help_text=help_text)
    reg.set_gauge("ulisse_page_cache_bytes", float(cache_bytes),
                  help_text="Bytes currently resident in the page cache")
