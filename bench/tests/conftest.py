"""Shared set-up of the harness tests.

`test_readers.py` runs every per-layer reader of `BENCHMARK.json` on
one hand-made run, `test_readers.SPANS`.  The spans that the program
records of its device stages, dispatcher waits, scan counters and
compiles are added to that run here, before its tests run (nothing of
what it held changes; its existing readers read none of these names).
`test_span_readers.py` checks the values their readers compute.
"""
import pytest

PROGRAM_SPANS = [
    {"name": "device.approx_plan", "t0": 1.1, "dur": 0.05,
     "attrs": {"qlen": 128, "batch": 2}, "id": 101, "parent": None},
    {"name": "device.approx_scan", "t0": 1.15, "dur": 0.02,
     "attrs": {"qlen": 128, "batch": 2}, "id": 102, "parent": None},
    {"name": "device.plan", "t0": 1.17, "dur": 0.1,
     "attrs": {"qlen": 128, "batch": 2}, "id": 103, "parent": None},
    {"name": "device.scan", "t0": 1.27, "dur": 0.2,
     "attrs": {"qlen": 128, "batch": 2}, "id": 104, "parent": None},
    {"name": "query.exact_device", "t0": 1.05, "dur": 0.9,
     "attrs": {"qlen": 128, "batch": 2, "chunks": 15, "steps": 10,
               "lanes": 2, "fill": 2}, "id": 105, "parent": None},
    {"name": "serve.idle", "t0": 3.0, "dur": 2.0, "attrs": {},
     "id": 106, "parent": None},
    {"name": "jax.compile", "t0": 2.0, "dur": 0.1,
     "attrs": {"fun_name": "f", "phase": "backend_compile"}, "id": 107,
     "parent": None},
]


@pytest.fixture(autouse=True)
def _program_spans(request):
    spans = getattr(request.module, "SPANS", None)
    if (request.module.__name__.endswith("test_readers")
            and not any(s["name"] == "device.scan" for s in spans)):
        spans.extend(PROGRAM_SPANS)
    yield
