"""repro.obs: tracer sampling/overhead contract, metrics registry +
exporters, SearchStats export, serve-tier mirroring, and the one-query
end-to-end trace the observability tier exists to produce
(DESIGN.md §12).
"""
import json

import numpy as np
import pytest

from repro import obs
from repro.obs import MetricsRegistry, Tracer
from repro.obs.tracer import _NULL_SPAN
from repro.serve.metrics import ServeMetrics


# -------------------------------------------------------------------------
# tracer
# -------------------------------------------------------------------------

def test_disabled_span_is_shared_null_singleton():
    """The hot-path contract: while disabled, span() allocates nothing
    — every call returns the same no-op object, and nothing records."""
    tr = Tracer()
    assert tr.span("a") is _NULL_SPAN
    assert tr.span("b", attr=1) is tr.span("c")
    with tr.span("a") as sp:
        sp.set(k=1)              # attribute set is a no-op, not an error
    tr.record_interval("w", 0.0, 1.0)
    assert len(tr) == 0


def test_nested_spans_record_depth_and_attrs():
    tr = Tracer(enabled=True)
    with tr.span("root", qlen=128) as r:
        with tr.span("child") as c:
            c.set(chunks=4)
        r.set(batch=2)
    spans = tr.drain()
    assert [s.name for s in spans] == ["child", "root"]  # close order
    child, root = spans
    assert child.depth == 1 and root.depth == 0
    assert root.attrs == {"qlen": 128, "batch": 2}
    assert child.attrs == {"chunks": 4}
    assert child.t0 >= root.t0
    assert child.dur <= root.dur
    assert len(tr) == 0          # drain cleared the ring


def test_sampling_decision_is_per_root_and_inherited():
    """1-in-N sampling keeps whole traces: an unsampled root's children
    are dropped with it, a sampled root's children all record."""
    tr = Tracer(enabled=True, sample_every=2)
    kept = []
    for i in range(6):
        with tr.span("root"):
            with tr.span("child"):
                pass
        kept.append(len(tr.drain()))
    # deterministic counter: every other root records, always with its
    # child (2 spans) — never a partial trace (1 span)
    assert sorted(set(kept)) == [0, 2]
    assert kept.count(2) == 3


def test_ring_buffer_capacity_keeps_newest():
    tr = Tracer(enabled=True, capacity=3)
    for i in range(7):
        with tr.span(f"s{i}"):
            pass
    names = [s.name for s in tr.snapshot()]
    assert names == ["s4", "s5", "s6"]


def test_record_interval_respects_enabled_only():
    tr = Tracer(enabled=True, sample_every=1000)   # roots unsampled
    tr.record_interval("queue_wait", 1.0, 1.5, bucket=128)
    (s,) = tr.snapshot()
    assert s.name == "queue_wait"
    assert s.dur == pytest.approx(0.5)
    assert s.attrs == {"bucket": "128"} or s.attrs == {"bucket": 128}


def test_configure_validates_and_rebounds():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.configure(sample_every=0)
    with pytest.raises(ValueError):
        tr.configure(capacity=0)
    tr.configure(enabled=True, capacity=2)
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    assert [s.name for s in tr.snapshot()] == ["s2", "s3"]


def test_chrome_trace_is_valid_json_with_microsecond_events():
    tr = Tracer(enabled=True)
    with tr.span("outer", qlen=96):
        with tr.span("inner"):
            pass
    doc = json.loads(json.dumps(tr.chrome_trace()))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert meta and meta[0]["args"]["name"] == "ulisse"
    assert {e["name"] for e in xs} == {"outer", "inner"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0       # microseconds
        assert e["cat"] == "ulisse"
    outer = next(e for e in xs if e["name"] == "outer")
    assert outer["args"]["qlen"] == 96


# -------------------------------------------------------------------------
# registry
# -------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    reg.inc("req_total", 2.0, help_text="requests", bucket=128)
    reg.inc("req_total", bucket=128)
    reg.inc("req_total", bucket=256)
    reg.set_gauge("depth", 7.0, bucket=128)
    reg.observe("lat_seconds", 0.004, buckets=(0.001, 0.01, 0.1))
    reg.observe("lat_seconds", 0.04, buckets=(0.001, 0.01, 0.1))
    assert reg.get("req_total", bucket=128) == 3.0
    assert reg.get("req_total", bucket=256) == 1.0
    assert reg.get("req_total", bucket=999) is None
    assert reg.get("depth", bucket=128) == 7.0
    snap = reg.snapshot()
    (h,) = snap["lat_seconds"]["series"]
    assert h["count"] == 2 and h["sum"] == pytest.approx(0.044)
    # non-cumulative internal counts: each observation lands in
    # exactly one bucket (0.004 -> le=0.01, 0.04 -> le=0.1)
    assert [b["count"] for b in h["buckets"]] == [0, 1, 1]
    json.loads(reg.json_text())                     # serializable


def test_registry_kind_clash_and_negative_counter_raise():
    reg = MetricsRegistry()
    reg.inc("x_total")
    with pytest.raises(ValueError, match="counter"):
        reg.observe("x_total", 1.0)
    with pytest.raises(ValueError, match="only go up"):
        reg.inc("y_total", -1.0)
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.inc("bad name")


def test_prometheus_text_exposition_format():
    """The scrape format the acceptance bar names: HELP/TYPE headers,
    labelled series, histograms expanded to cumulative le= buckets with
    +Inf, _sum and _count."""
    reg = MetricsRegistry()
    reg.inc("ulisse_serve_completed_total", 5, help_text="done",
            bucket=128)
    reg.observe("ulisse_serve_latency_seconds", 0.004,
                buckets=(0.001, 0.01, 0.1), bucket=128)
    reg.observe("ulisse_serve_latency_seconds", 0.05,
                buckets=(0.001, 0.01, 0.1), bucket=128)
    text = reg.prometheus_text()
    lines = text.strip().splitlines()
    assert "# HELP ulisse_serve_completed_total done" in lines
    assert "# TYPE ulisse_serve_completed_total counter" in lines
    assert 'ulisse_serve_completed_total{bucket="128"} 5' in lines
    assert "# TYPE ulisse_serve_latency_seconds histogram" in lines
    # cumulative buckets, le-ordered, +Inf == _count
    assert ('ulisse_serve_latency_seconds_bucket'
            '{bucket="128",le="0.001"} 0') in lines
    assert ('ulisse_serve_latency_seconds_bucket'
            '{bucket="128",le="0.01"} 1') in lines
    assert ('ulisse_serve_latency_seconds_bucket'
            '{bucket="128",le="0.1"} 2') in lines
    assert ('ulisse_serve_latency_seconds_bucket'
            '{bucket="128",le="+Inf"} 2') in lines
    assert 'ulisse_serve_latency_seconds_count{bucket="128"} 2' in lines
    assert any(line.startswith(
        'ulisse_serve_latency_seconds_sum{bucket="128"}')
        for line in lines)
    assert text.endswith("\n")


def test_record_search_stats_labels_by_backend():
    from repro.core.executor import SearchStats
    reg = MetricsRegistry()
    st = SearchStats(envelopes_total=10, envelopes_checked=6,
                     envelopes_pruned=4, lb_computations=10,
                     true_dist_computations=40, chunks_visited=2,
                     chunks_planned=3)
    obs.record_search_stats(st, backend="device", registry=reg)
    obs.record_search_stats(st, backend="host", registry=reg)
    assert reg.get("ulisse_engine_envelopes_pruned", backend="device") == 4
    assert reg.get("ulisse_engine_chunks_planned", backend="host") == 3
    assert reg.get("ulisse_engine_queries", backend="device") == 1


# -------------------------------------------------------------------------
# serve metrics mirroring + the mean_fill fix
# -------------------------------------------------------------------------

def test_total_mean_fill_counts_failed_dispatches():
    """Regression (satellite a): the total fold computed mean_fill as
    completed/dispatches, so a failed dispatch — whose requests were
    coalesced but never complete — silently deflated the batching
    efficiency.  It must fold the per-bucket fill histograms exactly
    like the per-bucket rows do."""
    m = ServeMetrics(registry=MetricsRegistry())
    m.record_dispatch(128, fill=4, waits=[0.001] * 4)
    m.record_failed(128, 4)                        # whole batch fails
    m.record_dispatch(256, fill=2, waits=[0.001] * 2)
    m.record_done(256, latencies=[0.01, 0.02])
    snap = m.snapshot()
    assert snap["total"]["dispatches"] == 2
    assert snap["total"]["completed"] == 2
    assert snap["total"]["failed"] == 4
    # (4 + 2) / 2 dispatches — NOT completed/dispatches == 1.0
    assert snap["total"]["mean_fill"] == 3.0
    assert snap["buckets"][128]["mean_fill"] == 4.0
    assert snap["buckets"][256]["mean_fill"] == 2.0


def test_serve_metrics_mirror_into_registry_and_reset_keeps_it():
    reg = MetricsRegistry()
    m = ServeMetrics(registry=reg)
    m.record_admit(128)
    m.record_dispatch(128, fill=2, waits=[0.001, 0.002])
    m.record_done(128, latencies=[0.01, 0.02])
    m.record_reject(128)
    m.record_failed(128, 1)
    assert reg.get("ulisse_serve_admitted_total", bucket=128) == 1
    assert reg.get("ulisse_serve_dispatches_total", bucket=128) == 1
    assert reg.get("ulisse_serve_completed_total", bucket=128) == 2
    assert reg.get("ulisse_serve_rejected_total", bucket=128) == 1
    assert reg.get("ulisse_serve_failed_total", bucket=128) == 1
    snap = reg.snapshot()
    (lat,) = snap["ulisse_serve_latency_seconds"]["series"]
    assert lat["count"] == 2
    m.reset()                    # local window restarts ...
    assert m.snapshot()["total"]["dispatches"] == 0
    assert reg.get("ulisse_serve_completed_total",   # ... registry is
                   bucket=128) == 2                  # monotone


# -------------------------------------------------------------------------
# end-to-end: one served query traced admission -> dispatch -> scan
# -------------------------------------------------------------------------

def test_one_served_query_traced_end_to_end(walk_collection):
    """The acceptance bar: a query through the serving tier produces a
    valid Chrome trace covering admission -> queue wait -> dispatch ->
    device scan -> merge, and metrics_text() emits parseable Prometheus
    text with per-bucket latency histograms AND engine pruning
    counters."""
    from repro.core import (Collection, EnvelopeParams, QuerySpec,
                            UlisseEngine)
    from repro.serve import ServeConfig, UlisseServer

    prev_tr = obs.set_tracer(Tracer(enabled=True))
    prev_reg = obs.set_registry(MetricsRegistry())
    try:
        p = EnvelopeParams(lmin=64, lmax=128, seg_len=16, card=64,
                           gamma=8, znorm=True)
        engine = UlisseEngine.from_collection(
            Collection.from_array(walk_collection), p, max_batch=2)
        server = UlisseServer(engine, QuerySpec(k=3),
                              ServeConfig(max_batch=2))
        q = walk_collection[0, 5:5 + 96]
        res = server.search(q, timeout=300)
        text = server.metrics_text()
        doc = json.loads(json.dumps(obs.get_tracer().chrome_trace()))
        server.close()

        assert res.stats.true_dist_computations > 0
        names = {e["name"] for e in doc["traceEvents"]}
        for required in ("serve.admission", "serve.queue_wait",
                         "serve.dispatch", "device_scan", "merge"):
            assert required in names, (required, sorted(names))
        # the engine spans nest inside the dispatch span's interval
        evs = {e["name"]: e for e in doc["traceEvents"]
               if e["ph"] == "X"}
        disp, scan = evs["serve.dispatch"], evs["device_scan"]
        assert disp["ts"] <= scan["ts"]
        assert scan["ts"] + scan["dur"] <= disp["ts"] + disp["dur"] + 1

        assert "ulisse_serve_latency_seconds_bucket" in text
        assert 'le="+Inf"' in text
        assert "ulisse_serve_completed_total" in text
        assert "ulisse_engine_true_dist_computations" in text
        assert "ulisse_engine_envelopes_checked" in text
        json.loads(server.metrics_json() and
                   obs.get_registry().json_text())
    finally:
        obs.set_tracer(prev_tr)
        obs.set_registry(prev_reg)


def test_quickstart_stats_surface():
    """examples/quickstart.py prints the unified stats after each
    query; the fields it reads must exist on every SearchResult."""
    from repro.core import (Collection, EnvelopeParams, QuerySpec,
                            UlisseEngine)
    rng = np.random.default_rng(0)
    data = np.cumsum(rng.normal(size=(8, 128)), -1).astype(np.float32)
    p = EnvelopeParams(lmin=48, lmax=64, gamma=8, seg_len=8, card=64,
                       znorm=True)
    engine = UlisseEngine.from_collection(Collection.from_array(data), p)
    res = engine.search(data[0, 3:3 + 48], QuerySpec(k=2))
    d = res.stats.as_dict()
    for field in ("pruning_power", "chunks_visited", "chunks_planned",
                  "envelopes_pruned", "true_dist_computations"):
        assert field in d
    assert 0.0 <= d["pruning_power"] <= 1.0
    assert d["chunks_planned"] >= d["chunks_visited"] >= 0


# -------------------------------------------------------------------------
# span identity, device stages, dispatcher waits, compiles
# -------------------------------------------------------------------------

def test_span_ids_and_parents_across_nesting():
    """Every span gets a fresh id and its parent is the span open on
    its thread when it started; an externally-timed interval takes the
    span open where it is recorded."""
    tr = Tracer(enabled=True)
    with tr.span("root"):
        with tr.span("child"):
            with tr.span("leaf"):
                pass
            tr.record_interval("wait", 0.0, 0.0, req=7)
    with tr.span("root2"):
        pass
    by = {s.name: s for s in tr.drain()}
    ids = [s.id for s in by.values()]
    assert len(set(ids)) == len(ids) and all(i > 0 for i in ids)
    assert by["root"].parent is None and by["root2"].parent is None
    assert by["child"].parent == by["root"].id
    assert by["leaf"].parent == by["child"].id
    assert by["wait"].parent == by["child"].id
    d = by["leaf"].as_dict()
    assert d["id"] == by["leaf"].id and d["parent"] == by["child"].id
    for key in ("name", "t0", "dur", "tid", "depth", "attrs"):
        assert key in d


def test_ids_are_per_thread_parents():
    """A span opened on another thread does not take this thread's open
    span as its parent."""
    import threading
    tr = Tracer(enabled=True)
    seen = {}

    def other():
        with tr.span("other") as sp:
            seen["id"] = sp.id

    with tr.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    by = {s.name: s for s in tr.drain()}
    assert by["other"].parent is None
    assert by["other"].id == seen["id"] != by["main"].id


def test_device_mark_off_path_holds_nothing():
    """Tracing off: span(), wait_span() and the device mark are all the
    shared no-op; the mark starts no thread and keeps no reference to
    the array it is handed."""
    import gc
    import threading
    import weakref

    import jax.numpy as jnp

    def watchers():
        return [t for t in threading.enumerate()
                if t.name == "ulisse-obs-device"]

    before = len(watchers())
    tr = Tracer()
    assert tr.span("x") is _NULL_SPAN
    assert tr.wait_span("serve.idle") is _NULL_SPAN
    mark = tr.device_stage("device.scan", qlen=8, batch=1)
    assert mark is _NULL_SPAN
    out = jnp.arange(5.0) * 2
    ref = weakref.ref(out)
    mark.ready(out)
    del out
    gc.collect()
    assert ref() is None
    assert len(watchers()) == before
    assert len(tr) == 0


def test_device_mark_in_unsampled_trace_is_null():
    tr = Tracer(enabled=True, sample_every=2)
    with tr.span("unsampled"):           # the draw keeps every 2nd root
        assert tr.device_stage("device.scan") is _NULL_SPAN
    with tr.span("sampled"):
        pass
    assert [s.name for s in tr.drain()] == ["sampled"]


def test_device_stages_tile_in_launch_order():
    """Stages recorded by the watcher: launch order, no overlap, each
    from max(launch, previous ready) to its own ready, parented to the
    span open at launch, all present once drain() returns."""
    import jax.numpy as jnp
    tr = Tracer(enabled=True)
    x = jnp.ones((64, 64))
    with tr.span("query") as q:
        for name in ("device.a", "device.b", "device.c"):
            mark = tr.device_stage(name, qlen=64, batch=1)
            x = x @ x / 64.0
            mark.ready(x)
    spans = tr.drain()
    dev = [s for s in spans if s.name.startswith("device.")]
    assert [s.name for s in dev] == ["device.a", "device.b", "device.c"]
    (root,) = [s for s in spans if s.name == "query"]
    for a, b in zip(dev, dev[1:]):
        assert b.t0 >= a.t0 + a.dur - 1e-9
    for s in dev:
        assert s.parent == q.id == root.id
        assert s.attrs == {"qlen": 64, "batch": 1}
        assert s.t0 >= root.t0 and s.dur >= 0


def test_wait_spans_use_no_sampling_draw():
    """wait_span records whenever tracing is on and leaves the 1-in-N
    root draw to the traces it sits between."""
    tr = Tracer(enabled=True, sample_every=2)
    for _ in range(6):
        with tr.wait_span("serve.idle"):
            pass
        with tr.span("root"):
            pass
    names = [s.name for s in tr.drain()]
    assert names.count("serve.idle") == 6
    assert names.count("root") == 3


def test_wait_span_becomes_annotation_with_id(monkeypatch):
    """With jax_annotations each live span enters a TraceAnnotation
    named after it, carrying its id as a scalar stat."""
    from repro.obs import tracer as tracer_mod
    made = []

    class Fake:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Fake)
    tr = Tracer(enabled=True, jax_annotations=True)
    with tr.wait_span("serve.idle") as w:
        pass
    with tr.span("serve.dispatch", reqs=[1, 2]) as d:
        pass
    assert made == [("serve.idle", {"id": w.id}),
                    ("serve.dispatch", {"id": d.id})]


def test_compile_recorded_under_traced_span():
    """A new shape inside a traced span records jax.compile spans (one
    per phase) parented to that span; with tracing off none record."""
    import jax
    import jax.numpy as jnp

    prev = obs.set_tracer(Tracer())
    try:
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        f(jnp.ones((3, 5)))                  # tracer off: nothing
        assert len(obs.get_tracer()) == 0
        obs.get_tracer().configure(enabled=True)
        with obs.span("outer") as sp:
            f(jnp.ones((7, 11)))             # a shape not seen yet
        spans = obs.get_tracer().drain()
    finally:
        obs.set_tracer(prev)
    comp = [s for s in spans if s.name == "jax.compile"]
    assert comp and all(s.parent == sp.id for s in comp)
    phases = {s.attrs["phase"] for s in comp}
    assert "backend_compile" in phases
    assert any("lambda" in s.attrs["fun_name"] for s in comp)


def test_chrome_trace_carries_ids_and_device_track():
    import jax.numpy as jnp
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        mark = tr.device_stage("device.scan", qlen=4, batch=1)
        mark.ready(jnp.zeros(4) + 1)
    doc = json.loads(json.dumps(tr.chrome_trace()))
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    outer, dev = xs["outer"], xs["device.scan"]
    assert dev["args"]["parent"] == outer["args"]["id"]
    assert outer["args"]["parent"] is None
    assert dev["tid"] != outer["tid"]
    (track,) = [e for e in doc["traceEvents"]
                if e["ph"] == "M" and e["name"] == "thread_name"]
    assert track["tid"] == dev["tid"] and track["args"]["name"] == "device"


def test_device_marks_from_many_threads_all_record():
    """Stress: more marking threads than cores, a short switch interval;
    every stage handed to the watcher is recorded once drain() returns,
    and ids stay unique."""
    import os
    import sys
    import threading

    import numpy as np

    tr = Tracer(enabled=True, capacity=1 << 16)
    n_threads = 2 * (os.cpu_count() or 2)
    per = 25
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(t):
            with tr.span("root", t=t):
                for i in range(per):
                    tr.device_stage("device.x", i=i).ready(np.ones(3))

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        spans = tr.drain()
    finally:
        sys.setswitchinterval(old)
    dev = [s for s in spans if s.name == "device.x"]
    assert len(dev) == n_threads * per
    assert len({s.id for s in spans}) == len(spans)
    roots = {s.id for s in spans if s.name == "root"}
    assert all(s.parent in roots and s.dur >= 0 for s in dev)
