"""Every per-layer metric in BENCHMARK.json has a reader under
`bench/metrics/`, and each reads the numbers it is meant to from a
small hand-made run.

    PYTHONPATH=src python -m pytest -q bench/tests/test_readers.py
"""
import json
from pathlib import Path

import pytest

import bench.run as run
from bench import work

BENCH = json.loads((Path(run.ROOT) / "BENCHMARK.json").read_text())

SPANS = [
    {"name": "bench.trace_window", "t0": 0.0, "dur": 10.0, "attrs": {}},
    {"name": "serve.queue_wait", "t0": 0.5, "dur": 0.1, "attrs": {}},
    {"name": "serve.queue_wait", "t0": 0.6, "dur": 0.3, "attrs": {}},
    {"name": "serve.dispatch", "t0": 1.0, "dur": 1.0, "attrs": {"fill": 2}},
    {"name": "serve.dispatch", "t0": 12.0, "dur": 1.0,
     "attrs": {"fill": 4}},                  # after the traced window
    {"name": "merge", "t0": 1.9, "dur": 0.002, "attrs": {}},
    {"name": "merge", "t0": 12.9, "dur": 0.004, "attrs": {}},
]
STATS = [(128, {"envelopes_checked": 1000, "dtw_full": 0,
                "chunks_visited": 10, "chunks_planned": 100})] * 6


def make_run():
    config = json.loads((Path(run.ROOT) / "bench/configs/rw256-znorm-ed.json")
                        .read_text())
    return run.Run(spans=SPANS, stats=STATS, trace=None,
                   modules={"jit__unknown": 0.5, "jit_device_scan_pack": 0.1,
                            "jit_env_lower_bounds_batch": 0.1},
                   busy_s=2.0, window_s=8.0, config=config, traffic={},
                   peaks=work.peaks("TPU v5 lite"),
                   traced_queries=run.traced_queries(SPANS))


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader(name):
    value = run.load_reader(name)(make_run())
    assert value is not None and value >= 0


def test_reader_values():
    r = make_run()
    assert r.traced_queries == 2
    read = {m["name"]: run.load_reader(m["name"])(r)
            for m in BENCH["per_layer"]}
    assert read["serve_queue_wait_ms.poisson"] == pytest.approx(200.0)
    assert read["engine_merge_ms.poisson"] == pytest.approx(1.0)
    assert read["plan_device_ms.poisson"] == pytest.approx(100.0)
    assert read["scan_device_ms.poisson"] == pytest.approx(250.0)
    assert read["scan_chunks_visited_share.poisson"] == pytest.approx(10.0)
    assert read["device_idle_share.poisson"] == pytest.approx(75.0)
    # 6 queries of 1000 rows x (128 + 16) x 4 bytes + 128 x 4 each,
    # memory-bound; 2 of them traced against 0.5 s of scan program
    least = (1000 * 144 * 4 + 512) / 819e9
    assert read["verify_roofline_share.poisson"] == pytest.approx(
        least * 2 / 0.5 * 100)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
