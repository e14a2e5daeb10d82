"""Sampling span tracer: one query traced end-to-end, exportable as
Chrome ``trace_event`` JSON (DESIGN.md §12).

The paper's performance argument lives in quantities that only show up
*inside* one query — how long the LB pack took vs the device scan, how
much wall time the host continuation of an overflowed range query ate,
how long a request waited in its serving bucket before dispatch.  The
tracer records those as nested spans:

    with tracer.span("device_scan", bucket=128, batch=8):
        ...

Every recorded span has an ``id`` and the ``parent`` id of the span
open on its thread when it started, so the spans of one request (and
of the device work it launched) can be joined.

Design constraints, in order:

  1. **Disabled must be (nearly) free.**  Tracing is off by default;
     the engine hot path calls ``span()`` and ``device_stage()``
     unconditionally, so the disabled call is one attribute check
     returning a shared no-op object — no allocation, no lock, no
     clock read, no thread, no reference to a device array.  The cost
     when on is to be read on the chip (PERF.md §6): tracing-on runs
     of the served benchmark cell against tracing-off runs of the
     same seeds.
  2. **Bounded memory.**  Finished spans land in a ring buffer
     (``deque(maxlen=capacity)``); a long-running server traces
     forever without growing host state.
  3. **Sampling by trace, not by span.**  The sampling decision is
     made once per ROOT span (deterministic 1-in-N counter, no RNG on
     the hot path) and inherited by every nested span on that thread,
     so a sampled trace is always complete — a partial trace is worse
     than none.  Waits that belong to no request (``wait_span``) and
     externally-timed intervals stay outside the draw.
  4. **Alignment with XLA profiles.**  With ``jax_annotations=True``
     each live span also enters a ``jax.profiler.TraceAnnotation``
     scope carrying the span's ``id`` as a stat, so a host event of
     the profiler trace maps to exactly one recorded span.
  5. **Device time, not enqueue time.**  ``device_stage(name)`` marks
     the launch of a stage of device programs; ``.ready(out)`` hands
     the tracer one output of the stage.  One watcher thread (started
     lazily, only while tracing is on) waits on the outputs in launch
     order and records each stage from max(its launch, the previous
     stage's ready time) to its own ready time.  On one chip programs
     run in launch order, so these intervals tile the chip's time.

Span timestamps are ``time.perf_counter()`` relative to the tracer
epoch; the Chrome export emits microseconds, loadable in Perfetto /
``chrome://tracing``.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

# spans whose names start with this prefix time device work; the Chrome
# export puts them on a track of their own
DEVICE_PREFIX = "device."
# seconds the device watcher idles before its thread exits
_WATCH_IDLE_S = 5.0


class Span:
    """One finished span: name, [t0, t0+dur) in seconds since the
    tracer epoch, thread id, nesting depth, free-form attributes, its
    own id and its parent's (None for a root)."""

    __slots__ = ("name", "t0", "dur", "tid", "depth", "attrs", "id",
                 "parent")

    def __init__(self, name: str, t0: float, dur: float, tid: int,
                 depth: int, attrs: Optional[dict], id: int = 0,
                 parent: Optional[int] = None):
        self.name = name
        self.t0 = t0
        self.dur = dur
        self.tid = tid
        self.depth = depth
        self.attrs = attrs
        self.id = id
        self.parent = parent

    def as_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0, "dur": self.dur,
                "tid": self.tid, "depth": self.depth,
                "attrs": dict(self.attrs or {}), "id": self.id,
                "parent": self.parent}


class _NullSpan:
    """The shared no-op returned while disabled (or for unsampled
    traces): a context manager for ``span()`` and a stage handle for
    ``device_stage()``.  One instance, zero state."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        """Attribute recording is a no-op on an unsampled span."""

    def ready(self, out) -> None:
        """A stage mark off the trace keeps no reference to `out`."""


_NULL_SPAN = _NullSpan()


class _UnsampledRoot:
    """Placeholder for a root span that lost the sampling draw.  It
    must still occupy the thread's nesting state: without it, the spans
    nested under an unsampled root would see an empty stack, treat
    themselves as roots, and make fresh sampling decisions — recording
    partial traces, which the design forbids (§3 of the module doc)."""

    __slots__ = ("_local",)

    def __init__(self, local):
        self._local = local

    def __enter__(self) -> "_UnsampledRoot":
        self._local.suppress = getattr(self._local, "suppress", 0) + 1
        return self

    def __exit__(self, *exc) -> bool:
        self._local.suppress -= 1
        return False

    def set(self, **attrs) -> None:
        """Attribute recording is a no-op on an unsampled trace."""


class _LiveSpan:
    """An open span on a sampled trace (context manager)."""

    __slots__ = ("_tracer", "name", "attrs", "id", "parent", "_t0",
                 "_jax_ctx")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.id = 0
        self.parent = None
        self._t0 = 0.0
        self._jax_ctx = None

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. overflow counts
        known only after the device readback)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_LiveSpan":
        tr = self._tracer
        stack = tr._stack()
        self.id = next(tr._ids)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        if tr.jax_annotations:
            try:
                self._jax_ctx = TraceAnnotation(self.name, id=self.id)
                self._jax_ctx.__enter__()
            except Exception:              # noqa: BLE001 — tracing must
                self._jax_ctx = None       # never break the query path
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*exc)
        tr = self._tracer
        stack = tr._stack()
        depth = len(stack) - 1
        if stack and stack[-1] is self:
            stack.pop()
        tr._record(Span(self.name, self._t0 - tr._epoch,
                        t1 - self._t0, threading.get_ident(), depth,
                        self.attrs or None, self.id, self.parent))
        return False


class _DeviceStage:
    """A launched stage of device programs on a sampled trace: the
    launch time and the span open at launch, waiting for ``ready``."""

    __slots__ = ("_tracer", "name", "attrs", "t_launch", "parent",
                 "depth", "tid")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict,
                 parent: Optional[int], depth: int):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.parent = parent
        self.depth = depth
        self.tid = threading.get_ident()
        self.t_launch = time.perf_counter()

    def ready(self, out) -> None:
        """Hand the tracer one output of the stage: the stage ends when
        `out` is ready on the device."""
        self._tracer._watch(self, out)


class Tracer:
    """Sampling span tracer with a bounded in-memory ring buffer.

    ``enabled=False`` (the default) makes ``span()`` a near-free no-op.
    ``sample_every=N`` records every N-th root span (and all of its
    children); 1 records everything.
    """

    def __init__(self, enabled: bool = False, sample_every: int = 1,
                 capacity: int = 8192, jax_annotations: bool = False):
        self.configure(enabled=enabled, sample_every=sample_every,
                       capacity=capacity,
                       jax_annotations=jax_annotations)

    def configure(self, enabled: Optional[bool] = None,
                  sample_every: Optional[int] = None,
                  capacity: Optional[int] = None,
                  jax_annotations: Optional[bool] = None) -> "Tracer":
        """Reconfigure in place (None = keep).  Changing ``capacity``
        re-bounds the ring buffer, keeping the newest spans."""
        if not hasattr(self, "_lock"):
            self._lock = threading.Lock()
            self._local = threading.local()
            self._spans: deque = deque(maxlen=8192)
            self._epoch = time.perf_counter()
            self._seq = 0
            self._ids = itertools.count(1)
            # device watcher: stages waiting for their output, the
            # stages handed over and not yet recorded, the last ready
            # time, and the thread (None while none runs)
            self._wcond = threading.Condition()
            self._wqueue: deque = deque()
            self._wpending = 0
            self._wlast = 0.0
            self._watcher: Optional[threading.Thread] = None
            self.enabled = False
            self.sample_every = 1
            self.jax_annotations = False
        with self._lock:
            if sample_every is not None:
                if sample_every < 1:
                    raise ValueError("sample_every must be >= 1")
                self.sample_every = sample_every
            if capacity is not None:
                if capacity < 1:
                    raise ValueError("capacity must be >= 1")
                self._spans = deque(self._spans, maxlen=capacity)
            if jax_annotations is not None:
                self.jax_annotations = jax_annotations
            if enabled is not None:
                self.enabled = enabled
        return self

    # -- hot path ------------------------------------------------------

    def span(self, name: str, **attrs):
        """Open a span.  THE hot-path call: when disabled this is one
        attribute check + returning a shared singleton."""
        if not self.enabled:
            return _NULL_SPAN
        if getattr(self._local, "suppress", 0):
            return _NULL_SPAN              # inside an unsampled trace
        stack = self._stack()
        if not stack:                      # root span: sampling decision
            with self._lock:
                self._seq += 1
                if self._seq % self.sample_every:
                    return _UnsampledRoot(self._local)
        return _LiveSpan(self, name, attrs)

    def wait_span(self, name: str, **attrs):
        """Open a span for a wait that belongs to no request (the serve
        dispatcher's idle and hold waits): recorded whenever tracing is
        on, and never drawn for sampling, so it uses up no draw of the
        traces it sits between."""
        if not self.enabled:
            return _NULL_SPAN
        return _LiveSpan(self, name, attrs)

    def device_stage(self, name: str, **attrs):
        """Mark the launch of a stage of device programs; call
        ``.ready(out)`` on the result with one output of the stage once
        the stage is enqueued.  Off the trace (disabled or unsampled)
        the result is the shared no-op, which holds nothing."""
        if not self.enabled:
            return _NULL_SPAN
        if getattr(self._local, "suppress", 0):
            return _NULL_SPAN
        stack = self._stack()
        return _DeviceStage(self, name, attrs,
                            stack[-1].id if stack else None, len(stack))

    def record_interval(self, name: str, t0: float, t1: float,
                        **attrs) -> None:
        """Record an externally-timed span: [t0, t1) are
        ``time.perf_counter()`` readings taken by the caller (e.g. a
        queue wait measured between a submit on one thread and the
        dispatch on another).  Its parent is the span open on the
        calling thread.  Subject to `enabled` only — intervals bridge
        traces, so root-span sampling does not apply."""
        if not self.enabled:
            return
        stack = self._stack()
        self._record(Span(name, t0 - self._epoch, max(t1 - t0, 0.0),
                          threading.get_ident(), len(stack),
                          attrs or None, next(self._ids),
                          stack[-1].id if stack else None))

    # -- device watcher ------------------------------------------------

    def _watch(self, stage: _DeviceStage, out) -> None:
        with self._wcond:
            self._wqueue.append((stage, out))
            self._wpending += 1
            if self._watcher is None:
                self._watcher = threading.Thread(
                    target=self._watch_loop, name="ulisse-obs-device",
                    daemon=True)
                self._watcher.start()
            self._wcond.notify_all()

    def _watch_loop(self) -> None:
        while True:
            with self._wcond:
                while not self._wqueue:
                    if (not self._wcond.wait(_WATCH_IDLE_S)
                            and not self._wqueue):
                        self._watcher = None
                        return
                stage, out = self._wqueue.popleft()
            try:
                jax.block_until_ready(out)
                t_ready = time.perf_counter()
                t0 = max(stage.t_launch, self._wlast)
                self._wlast = t_ready
                self._record(Span(stage.name, t0 - self._epoch,
                                  max(t_ready - t0, 0.0), stage.tid,
                                  stage.depth, stage.attrs or None,
                                  next(self._ids), stage.parent))
            except Exception:              # noqa: BLE001 — a failed
                pass                       # program fails its query
            finally:
                out = None                 # hold no array while idle
                with self._wcond:
                    self._wpending -= 1
                    self._wcond.notify_all()

    def _flush(self) -> None:
        """Wait until the watcher has recorded every stage handed to
        it."""
        with self._wcond:
            while self._wpending:
                self._wcond.wait()

    # -- internals -----------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)

    # -- export --------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def drain(self) -> List[Span]:
        """Remove and return every buffered span (oldest first), once
        the device watcher has recorded what it holds."""
        self._flush()
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def snapshot(self) -> List[Span]:
        """Buffered spans without clearing (oldest first), once the
        device watcher has recorded what it holds."""
        self._flush()
        with self._lock:
            return list(self._spans)

    def chrome_trace(self, clear: bool = False) -> dict:
        """Chrome ``trace_event`` JSON object (complete 'X' events,
        microsecond timestamps, ``id``/``parent`` in each event's args)
        — loadable in Perfetto.  Device stages get a track of their
        own, named "device"."""
        spans = self.drain() if clear else self.snapshot()
        pid = os.getpid()
        tids: Dict[object, int] = {}
        events = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "ulisse"},
        }]
        for s in spans:
            device = s.name.startswith(DEVICE_PREFIX)
            key = DEVICE_PREFIX if device else s.tid
            if key not in tids:
                tids[key] = len(tids)
                if device:
                    events.append({"name": "thread_name", "ph": "M",
                                   "pid": pid, "tid": tids[key],
                                   "args": {"name": "device"}})
            args = dict(s.attrs or {})
            args["id"], args["parent"] = s.id, s.parent
            events.append({"name": s.name, "cat": "ulisse", "ph": "X",
                           "ts": round(s.t0 * 1e6, 3),
                           "dur": round(s.dur * 1e6, 3),
                           "pid": pid, "tid": tids[key], "args": args})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str, clear: bool = False) -> str:
        """Write the Chrome trace JSON to ``path``; returns the path."""
        doc = self.chrome_trace(clear=clear)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path
