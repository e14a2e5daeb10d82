"""repro.serve: the dynamic-batching serving tier (DESIGN.md §11).

The load-bearing claim is that coalescing never changes an answer:
whatever the dispatcher batches together, every response is bit-equal
to a serial `engine.search` on the same snapshot.  Admission control,
warmup pre-tracing, and the writer lane (append/compact between
dispatches) are exercised against that same exactness bar.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (Collection, EnvelopeParams, QuerySpec,
                        UlisseEngine)
from repro.core.search import brute_force_knn
from repro.serve import (AdmissionError, ServeConfig, ServerClosed,
                         UlisseServer)

PARAMS = dict(lmin=64, lmax=128, seg_len=16, card=64)
LENGTHS = [64, 96, 128]       # buckets 64, 128, 128: one dispatch may
                              # mix exact lengths inside bucket 128


@pytest.fixture(scope="module")
def engine(walk_collection):
    coll = Collection.from_array(walk_collection)
    p = EnvelopeParams(gamma=8, znorm=True, **PARAMS)
    return UlisseEngine.from_collection(coll, p, max_batch=4)


def _queries(data, rng, n=6):
    qs = []
    for i in range(n):
        qlen = LENGTHS[i % len(LENGTHS)]
        s = int(rng.integers(0, data.shape[0]))
        o = int(rng.integers(0, data.shape[1] - qlen + 1))
        qs.append(data[s, o:o + qlen]
                  + rng.normal(size=qlen).astype(np.float32) * 0.05)
    return qs


def _assert_same(res, ref):
    assert np.array_equal(res.dists, ref.dists)
    assert np.array_equal(res.series, ref.series)
    assert np.array_equal(res.offsets, ref.offsets)


@pytest.mark.parametrize("spec", [
    QuerySpec(k=3),
    QuerySpec(k=3, measure="dtw", r=5),
    QuerySpec(eps=5.0),
    QuerySpec(eps=5.0, measure="dtw", r=5),
], ids=["ed_knn", "dtw_knn", "ed_range", "dtw_range"])
def test_coalesced_bit_equal_vs_serial(engine, walk_collection, rng,
                                       spec):
    """A burst of mixed-length requests, coalesced into padded bucket
    dispatches, answers bit-equal to one-at-a-time engine.search —
    across ED/DTW x kNN/range."""
    qs = _queries(walk_collection, rng)
    refs = [engine.search(q, spec) for q in qs]
    server = UlisseServer(engine, spec,
                          ServeConfig(window_ms=50.0, max_batch=4))
    tickets = [server.submit(q) for q in qs]      # burst: forces fills
    results = [t.result(timeout=300) for t in tickets]
    server.close()
    for res, ref in zip(results, refs):
        _assert_same(res, ref)

    m = server.metrics.snapshot()
    assert m["total"]["admitted"] == len(qs)
    assert m["total"]["completed"] == len(qs)
    assert m["total"]["failed"] == 0
    # the burst must actually have coalesced (fill >= 2 somewhere)
    fills = [f for bm in m["buckets"].values()
             for f in bm["fill_hist"]]
    assert max(fills) >= 2


def test_admission_control(engine, walk_collection, rng):
    """Submits beyond max_pending shed with a typed AdmissionError;
    close(drain=True) still answers everything admitted."""
    qs = _queries(walk_collection, rng, n=3)
    refs = [engine.search(q, QuerySpec(k=3)) for q in qs]
    # a window too long to expire and a batch too large to fill: the
    # queue can only move when close() cuts the window short
    server = UlisseServer(engine, QuerySpec(k=3),
                          ServeConfig(window_ms=60_000.0, max_batch=8,
                                      max_pending=2))
    t0 = server.submit(qs[0])
    t1 = server.submit(qs[1])
    assert server.pending == 2
    with pytest.raises(AdmissionError) as exc:
        server.submit(qs[2])
    assert exc.value.pending == 2
    assert exc.value.max_pending == 2
    assert exc.value.bucket in (64, 128)
    m = server.metrics.snapshot()
    assert m["total"]["rejected"] == 1

    server.close(drain=True)          # answers the two admitted
    _assert_same(t0.result(0), refs[0])
    _assert_same(t1.result(0), refs[1])
    with pytest.raises(ServerClosed):
        server.submit(qs[0])


def test_close_without_drain_fails_queued(engine, walk_collection, rng):
    q = _queries(walk_collection, rng, n=1)[0]
    server = UlisseServer(engine, QuerySpec(k=3),
                          ServeConfig(window_ms=60_000.0, max_batch=8))
    t = server.submit(q)
    server.close(drain=False)
    with pytest.raises(ServerClosed):
        t.result(0)


def test_admission_validates_on_client_thread(engine):
    server = UlisseServer(engine, QuerySpec(k=3),
                          ServeConfig(window_ms=1.0, max_batch=4))
    with pytest.raises(ValueError):
        server.submit(np.zeros((2, 64), np.float32))     # not 1-D
    bad = np.ones(64, np.float32)
    bad[3] = np.nan
    with pytest.raises(ValueError):
        server.submit(bad)                               # non-finite
    with pytest.raises(ValueError):
        server.submit(np.ones(32, np.float32))           # < lmin
    with pytest.raises(ValueError):
        server.submit(np.ones(200, np.float32))          # > lmax
    server.close()


def test_warmup_removes_first_request_retrace(engine, walk_collection):
    """After warmup() every (bucket, pow2 fill) program is traced, so
    the first real request pays no compile.  Length 104 is used by no
    other test in this module: its programs are cold until warmup."""
    qlen = 104
    q = walk_collection[1, 11:11 + qlen].copy()
    server = UlisseServer(engine, QuerySpec(k=3),
                          ServeConfig(window_ms=0.0, max_batch=4))
    t0 = time.perf_counter()
    traced = server.warmup([qlen])
    dt_warm = time.perf_counter() - t0
    assert traced == 3                   # fills 1, 2, 4
    t0 = time.perf_counter()
    server.search(q, timeout=300)
    dt_first = time.perf_counter() - t0
    server.close()
    # the compile cost lives in warmup, not the first request: even on
    # a noisy runner tracing is an order of magnitude above a served
    # query, so a 2x margin is conservative
    assert dt_first < dt_warm / 2


def test_append_compact_while_querying(walk_collection, rng):
    """Live ingestion under concurrent query load: every answer is
    exact against brute force over the snapshot it reports, and writer
    ops bump the version monotonically."""
    p = EnvelopeParams(gamma=8, znorm=True, **PARAMS)
    engine = UlisseEngine.from_collection(
        Collection.from_array(walk_collection), p, max_batch=4)
    grown = np.cumsum(
        np.random.default_rng(77).normal(size=(8, 192)),
        axis=-1).astype(np.float32)
    datasets = {0: walk_collection}        # snapshot -> admitted set
    after = np.concatenate([walk_collection, grown])
    datasets[1] = datasets[2] = after      # compact keeps the content

    server = UlisseServer(engine, QuerySpec(k=3),
                          ServeConfig(window_ms=1.0, max_batch=4))
    server.warmup(LENGTHS)
    qs = _queries(walk_collection, rng, n=18)
    out = [None] * len(qs)

    def client(cid):
        for i in range(cid, len(qs), 3):
            t = server.submit(qs[i])
            out[i] = (t, t.result(timeout=300))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.03)
    append_ticket = server.append(grown)       # mid-traffic
    assert append_ticket.result(timeout=300) == 1
    compact_ticket = server.compact()
    assert compact_ticket.result(timeout=300) == 2
    for t in threads:
        t.join()
    assert server.version == 2
    server.close()

    snapshots = [ticket.snapshot for ticket, _ in out]
    assert all(s in datasets for s in snapshots)
    for q, (ticket, res) in zip(qs, out):
        coll = Collection.from_array(datasets[ticket.snapshot])
        ref = brute_force_knn(coll, q, k=3, znorm=True)
        # squared distances: the f32 oracle's cancellation noise lives
        # on d^2 (the engine's f64-polished side is the accurate one)
        np.testing.assert_allclose(res.dists ** 2, ref.dists ** 2,
                                   atol=1e-3, rtol=1e-3)


def test_adaptive_window_idle_fast_burst_batched(engine, walk_collection,
                                                 rng):
    """PR 9 satellite: the hold window adapts to load.  A dispatch that
    drains every queue drops the effective window to 0, so a lone
    request on an idle server answers immediately instead of donating
    the whole window_ms; a backlog restores the configured window and
    the held buckets still coalesce (some dispatch fill > 1)."""
    spec = QuerySpec(k=3)
    server = UlisseServer(engine, spec,
                          ServeConfig(window_ms=250.0, max_batch=4))
    server.warmup(LENGTHS)
    qs = _queries(walk_collection, rng, n=9)
    # the first dispatch pays the configured window (adaptation starts
    # there so a cold burst can coalesce) and leaves the queues empty
    _assert_same(server.search(qs[0]), engine.search(qs[0], spec))
    t0 = time.perf_counter()
    res = server.search(qs[1])
    dt = time.perf_counter() - t0
    _assert_same(res, engine.search(qs[1], spec))
    assert dt < 0.2, (f"idle-server request took {dt * 1e3:.0f}ms — "
                      "the 250ms hold window was not shrunk")
    # burst: more requests than max_batch land while the first of them
    # is being dispatched, so a later pick leaves a backlog behind and
    # the restored window coalesces it
    tickets = [server.submit(q) for q in qs]
    for q, t in zip(qs, tickets):
        _assert_same(t.result(timeout=300), engine.search(q, spec))
    server.close()
    snap = server.metrics.snapshot()
    max_fill = max(int(f) for row in snap["buckets"].values()
                   for f in row["fill_hist"])
    assert max_fill >= 2, f"burst never coalesced: {snap}"


@pytest.fixture
def traced():
    """The process tracer switched to a fresh enabled one; restored."""
    from repro import obs
    prev = obs.set_tracer(obs.Tracer(enabled=True))
    try:
        yield obs.get_tracer()
    finally:
        obs.set_tracer(prev)


def test_request_spans_share_its_id_and_name_waits(engine,
                                                   walk_collection, rng,
                                                   traced):
    """One request's admission (client thread), queue wait and dispatch
    (dispatcher thread) carry its id; the engine's spans and device
    stages descend from the dispatch; the dispatcher's waits are named
    serve.idle (nothing queued) and serve.hold (held for the window)."""
    import sys
    server = UlisseServer(engine, QuerySpec(k=3),
                          ServeConfig(window_ms=300.0, max_batch=4))
    qs = [walk_collection[i, 3:3 + 96]
          + rng.normal(size=96).astype(np.float32) * 0.05
          for i in range(3)]
    tickets = [server.submit(q) for q in qs]
    for t in tickets:
        t.result(timeout=300)
    server.close()
    spans = traced.drain()
    by_id = {s.id: s for s in spans}
    named = lambda n: [s for s in spans if s.name == n]  # noqa: E731

    reqs = [t.req for t in tickets]
    assert len(set(reqs)) == 3 and None not in reqs
    adm = {s.attrs["req"]: s for s in named("serve.admission")}
    qw = {s.attrs["req"]: s for s in named("serve.queue_wait")}
    (disp,) = named("serve.dispatch")
    assert set(adm) == set(qw) == set(disp.attrs["reqs"]) == set(reqs)
    for r in reqs:
        assert adm[r].tid != disp.tid          # client vs dispatcher
        assert qw[r].tid == disp.tid and qw[r].parent == disp.id

    def ancestors(s):
        while s.parent is not None:
            s = by_id[s.parent]
            yield s

    (q,) = named("query.exact_device")
    assert q.parent == disp.id
    dev = [s for s in spans if s.name.startswith("device.")]
    assert [s.name for s in dev] == ["device.approx_plan",
                                     "device.approx_scan", "device.plan",
                                     "device.scan"]
    # the watcher reads its clock once it gets the interpreter back
    late = 2 * sys.getswitchinterval() + 0.05
    for s in dev:
        assert disp in ancestors(s) and q in ancestors(s)
        assert s.attrs == {"qlen": 96, "batch": 4}
        assert s.t0 >= disp.t0
        assert s.t0 + s.dur <= disp.t0 + disp.dur + late
    for a, b in zip(dev, dev[1:]):
        assert b.t0 >= a.t0 + a.dur - 1e-9

    assert named("serve.idle") and named("serve.hold")
    for s in named("serve.idle") + named("serve.hold"):
        assert s.parent is None and s.tid == disp.tid
    # the three requests were held together for the 300 ms window
    assert sum(s.dur for s in named("serve.hold")) >= 0.2


def test_exact_device_counts_scan_steps_on_padded_batch(engine,
                                                        walk_collection,
                                                        rng, traced):
    """query.exact_device records the scan's loop trips (`steps`), its
    lanes (the padded batch), the real rows (`fill`) and the chunks the
    real rows visited — padding rows excluded."""
    qs = [walk_collection[i, 7:7 + 96]
          + rng.normal(size=96).astype(np.float32) * 0.05
          for i in (0, 5, 11)]
    res = engine.search(qs, QuerySpec(k=3, approx_first=False))
    (q,) = [s for s in traced.drain() if s.name == "query.exact_device"]
    visited = [r.stats.chunks_visited for r in res]
    assert q.attrs["lanes"] == 4 and q.attrs["fill"] == 3
    assert q.attrs["chunks"] == sum(visited) > 0
    # the padding lane repeats the last query, so it stops with it
    assert q.attrs["steps"] == max(visited)
