"""The trace reduction (`bench/trace.py`) on a small trace whose answers
are known (one TPU plane with one module execution and two operations,
a host plane with the window mark and one span), and on a small trace
recorded on the chip.

    PYTHONPATH=src python -m pytest -q bench/tests/test_trace.py
"""
import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace

# times in ns: window [0, 10000); module [1000, 6000); ops [1000, 3000)
# and [4000, 6000); host span "merge" [3500, 4500) covers the middle of
# the gap [3000, 4000)
SMALL = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_scan(123)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "custom-call.2" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "merge" } }
}
'''


@pytest.fixture
def small():
    return trace.from_profile(ProfileData.from_text_proto(SMALL))


def test_window_and_busy(small):
    assert small.window == (0.0, 10000.0)
    # overlapping ops count once: [1000, 3000) + [4000, 6000)
    assert trace.busy_intervals(small, "/device:TPU:0") == [
        (1000.0, 3000.0), (4000.0, 6000.0)]
    busy, window = trace.busy_window(small)
    assert busy == pytest.approx(4000e-9)
    assert window == pytest.approx(10000e-9)


def test_module_seconds_strip_execution_id(small):
    assert trace.module_seconds(small) == {
        "jit_scan": pytest.approx(5000e-9)}


def test_breakdown(small):
    ops = trace.top_ops(small, "/device:TPU:0")
    assert ops[0][0] == "fusion.1"
    assert ops[0][1] == pytest.approx(3000e-9)
    assert ops[1] == ["custom-call.2", pytest.approx(2000e-9)]
    idle = dict(trace.idle_by_host(small, "/device:TPU:0", ["merge"]))
    # gaps: [0, 1000) and [6000, 10000) under no span, [3000, 4000)
    # under merge
    assert idle["merge"] == pytest.approx(1000e-9)
    assert idle["no span"] == pytest.approx(5000e-9)


def test_window_clips_events(small):
    small.window = (2000.0, 5000.0)
    busy, window = trace.busy_window(small)
    assert busy == pytest.approx(2000e-9)     # [2000,3000) + [4000,5000)
    assert window == pytest.approx(3000e-9)
    assert trace.module_seconds(small)["jit_scan"] == pytest.approx(3000e-9)


def test_no_device_plane_is_an_error():
    tr = trace.Trace(devices={}, host=[], window=(0.0, 1.0))
    with pytest.raises(ValueError):
        trace.busy_window(tr)


def test_modules_stand_in_where_no_operation_was_traced(small):
    del small.devices["/device:TPU:0"]["XLA Ops"]
    assert trace.busy_intervals(small, "/device:TPU:0") == [
        (1000.0, 6000.0)]
    assert trace.top_ops(small, "/device:TPU:0") == [
        ["jit_scan", pytest.approx(5000e-9)]]


# A trace recorded on one TPU v5e: one exact ED k-NN dispatch of eight
# queries of length 192 over 2^18 random-walk series, with the
# `repro.obs` spans as annotations and the `bench.window` mark.
RECORDED = Path(__file__).with_name("trace_ed_b8.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    raw = gzip.decompress(RECORDED.read_bytes())
    return trace.from_profile(ProfileData.from_serialized_xspace(raw))


def test_recorded_trace_reduction(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    busy, window = trace.busy_window(recorded)
    assert busy == pytest.approx(4.737351193)
    assert window == pytest.approx(4.74412648)
    mods = trace.module_seconds(recorded)
    # the module names the per-layer readers look for
    assert mods["jit__unknown"] == pytest.approx(3.430996972)
    assert mods["jit_env_lower_bounds_batch"] == pytest.approx(0.67061108)
    assert mods["jit_device_scan_pack"] == pytest.approx(0.635408003)
    assert {"jit_block_lower_bounds_batch",
            "jit_device_leaf_pack"} <= set(mods)
    idle = dict(trace.idle_by_host(recorded, "/device:TPU:0",
                                   ["device_scan", "approx_pass"]))
    assert sum(idle.values()) == pytest.approx(window - busy)
    ops = trace.top_ops(recorded, "/device:TPU:0")
    assert len(ops) == 10 and ops[0][0].startswith("%while")
