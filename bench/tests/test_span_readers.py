"""The readers of the program's own spans and counters (device stages,
dispatcher waits, scan lane counters, compiles), each on a hand-made
run whose answers are known, and on a run of a program that records
none of them.

    PYTHONPATH=src python -m pytest -q bench/tests/test_span_readers.py
"""
import json
from pathlib import Path

import pytest

import bench.run as run
from bench import work

NEW = ("plan_span_ms.poisson", "approx_span_ms.poisson",
       "scan_span_ms.poisson", "scan_lane_use_share.poisson",
       "idle_with_work_share.poisson", "jax_compile_ms.poisson")


def span(name, t0, dur, **attrs):
    return {"name": name, "t0": t0, "dur": dur, "attrs": attrs}


# window [0, 10) s; one dispatch of 2 queries inside it, and a later
# one outside; device stages of both
SPANS = [
    span("bench.trace_window", 0.0, 10.0),
    span("serve.dispatch", 1.0, 1.0, fill=2, reqs=[1, 2]),
    span("device.approx_plan", 1.10, 0.05, qlen=128, batch=2),
    span("device.approx_scan", 1.15, 0.02, qlen=128, batch=2),
    span("device.plan", 1.17, 0.10, qlen=128, batch=2),
    span("device.scan", 1.27, 0.20, qlen=128, batch=2),
    span("query.exact_device", 1.05, 0.9, qlen=128, batch=2, chunks=15,
         steps=10, lanes=2, fill=2),
    span("query.exact_device", 4.0, 0.5, qlen=256, batch=1, chunks=4,
         steps=4, lanes=1, fill=1),
    span("serve.dispatch", 12.0, 1.0, fill=4, reqs=[3, 4, 5, 6]),
    span("device.scan", 12.2, 0.5, qlen=128, batch=4),
    # waits: one straddles the window's start, one lies inside it
    span("serve.idle", -1.0, 1.5),
    span("serve.idle", 3.0, 2.0),
    span("serve.hold", 2.5, 0.002),
    # a compile, one nested inside it, one cut by the window's end
    span("jax.compile", 2.0, 0.1, fun_name="f", phase="backend_compile"),
    span("jax.compile", 2.02, 0.03, fun_name="g", phase="jaxpr_trace"),
    span("jax.compile", 9.95, 0.25, fun_name="h", phase="backend_compile"),
]
for i, s in enumerate(SPANS, 1):
    s["id"], s["parent"] = i, None


def make_run(spans):
    config = json.loads((Path(run.ROOT) / "bench/configs/rw256-znorm-ed.json")
                        .read_text())
    return run.Run(spans=spans, stats=[], trace=None, modules={},
                   busy_s=2.0, window_s=8.0, config=config, traffic={},
                   peaks=work.peaks("TPU v5 lite"),
                   traced_queries=run.traced_queries(spans))


def test_span_reader_values():
    r = make_run(SPANS)
    assert r.traced_queries == 2
    read = {name: run.load_reader(name)(r) for name in NEW}
    # (0.05 + 0.10) s over 2 queries; the stage after the window is out
    assert read["plan_span_ms.poisson"] == pytest.approx(75.0)
    assert read["approx_span_ms.poisson"] == pytest.approx(10.0)
    assert read["scan_span_ms.poisson"] == pytest.approx(100.0)
    # (15 + 4) chunks over 10 x 2 + 4 x 1 lane-steps
    assert read["scan_lane_use_share.poisson"] == pytest.approx(
        19 / 24 * 100)
    # idle 75% of the trace; serve.idle covers 0.5 + 2.0 of 10 s
    assert read["idle_with_work_share.poisson"] == pytest.approx(50.0)
    # union [2.0, 2.1) + [9.95, 10.0)
    assert read["jax_compile_ms.poisson"] == pytest.approx(150.0)


def test_no_compile_reads_zero():
    spans = [s for s in SPANS if s["name"] != "jax.compile"]
    assert run.load_reader("jax_compile_ms.poisson")(
        make_run(spans)) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_program_without_the_spans_reads_nothing(name):
    """A program that predates these spans (no ids, no device stages,
    no waits, no scan counters) gives no value, and no error."""
    old = [{k: v for k, v in s.items() if k not in ("id", "parent")}
           for s in SPANS
           if s["name"] in ("bench.trace_window", "serve.dispatch")]
    old.append(span("query.exact_device", 1.05, 0.9, qlen=128, batch=2,
                    chunks=15))
    assert run.load_reader(name)(make_run(old)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_declared(name):
    bench = json.loads((Path(run.ROOT) / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert m["workloads"] == ["ed-knn-poisson"]
    assert m["moves"] == "query_p50_ms"
