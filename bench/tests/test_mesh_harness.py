"""The harness over a cell whose configuration shards the collection.

A configuration with `"shards": 4` is served by `UlisseEngine.distributed`
over a ("data",) mesh of four chips, its collection made row-sharded over
the same mesh; memory is read over every chip of the cell; a cell whose
`chips` is not its configuration's `shards` exits 2 before any work.

The tests that need four devices run in subprocesses, because
`--xla_force_host_platform_device_count` must be set before JAX starts
and this process keeps its one CPU device.

    PYTHONPATH=src python -m pytest -q bench/tests/test_mesh_harness.py
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import bench.run as run

ROOT = Path(__file__).resolve().parents[2]
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=4",
           PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))


def run_sub(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=ENV, capture_output=True, text=True,
                         timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_collection_is_bitwise_the_one_device_one():
    run_sub("""
        import jax, numpy as np
        from bench import gen, run
        config = dict(run.load_spec("ed-knn-poisson")[2], num_series=64,
                      shards=4)
        mesh = run.cell_mesh(config)
        one = gen.make_collection(2**33 + 9, 64, 256)
        four = gen.make_collection(2**33 + 9, 64, 256, mesh)
        assert len(one.sharding.device_set) == 1
        shards = four.addressable_shards
        assert len({s.device for s in shards}) == 4, shards
        assert {s.data.shape for s in shards} == {(16, 256)}
        assert np.array_equal(np.asarray(one), np.asarray(four))

        traffic = dict(run.load_spec("ed-knn-poisson")[3], rate_per_s=3.0)
        _, q1, due1 = gen.make_work(config, traffic, 2**34 + 1, 4.0)
        _, q4, due4 = gen.make_work(config, traffic, 2**34 + 1, 4.0, mesh)
        assert np.array_equal(due1, due4)
        assert [(q.series, q.offset) for q in q1] == \\
            [(q.series, q.offset) for q in q4]
        assert all(np.array_equal(a.values, b.values)
                   for a, b in zip(q1, q4))
    """)


def test_build_engine_over_four_shards_answers_as_one_chip():
    run_sub("""
        import numpy as np
        from bench import gen, run
        config = dict(run.load_spec("ed-knn-poisson")[2], num_series=64)
        data = gen.make_collection(2**33 + 11, 64, 256)
        one = run.build_engine(config, data)
        four = run.build_engine(dict(config, shards=4), data)
        assert not one.is_distributed and four.is_distributed
        for name, a in four.device_arrays().items():
            assert len(a.sharding.device_set) == 4, name
        spec = run.query_spec(config)
        queries = gen.make_queries(data, 5, [128, 192, 256, 160], 0.02)
        for q in queries:
            a = one.search(q.values, spec)
            b = four.search(q.values, spec)
            assert list(zip(a.series, a.offsets)) == \\
                list(zip(b.series, b.offsets)), (a, b)
            assert np.allclose(a.dists, b.dists, rtol=0, atol=1e-6), (a, b)
    """)


class FakeChip:
    def __init__(self, i, stats):
        self.id, self._stats = i, stats

    def memory_stats(self):
        return self._stats


def test_memory_of_one_chip_reads_as_before():
    chip = FakeChip(0, {"bytes_in_use": 505_727_488,
                        "peak_bytes_in_use": 843_855_360})
    # the one-chip reading the harness took before it read every chip
    before = (chip.memory_stats() or {}).get("bytes_in_use", 0)
    assert run.chip_bytes([chip]) == [before]
    assert max(run.chip_bytes([chip], "peak_bytes_in_use")) == 843_855_360
    assert run.chip_bytes([FakeChip(0, None)]) == [0]


def test_memory_of_four_chips_is_summed_and_the_peak_is_the_fullest():
    chips = [FakeChip(i, {"bytes_in_use": 3_823_108_096 + i,
                          "peak_bytes_in_use": 6_897_377_280 + 7 * i})
             for i in range(4)]
    assert sum(run.chip_bytes(chips)) == 4 * 3_823_108_096 + 6
    assert max(run.chip_bytes(chips, "peak_bytes_in_use")) == \
        6_897_377_280 + 21


def test_sharded_run_is_correct_and_reads_every_chip():
    out = run_sub("""
        import json
        import jax
        from bench import run
        bench, cell, config, traffic = run.load_spec("ed-knn-poisson")
        cell = dict(cell, chips=4)
        config = dict(config, num_series=256, shards=4)
        config["check"] = dict(config["check"], sample=4)
        traffic = dict(traffic, rate_per_s=3.0, lengths=[128],
                       drain_s=20)
        # the CPU keeps no memory stats: each chip reads 1000 x its
        # number before the collection, twice that after, and 5000 plus
        # its number at its peak
        reads = {"bytes_in_use": 0}
        def chip_bytes(devices, key="bytes_in_use"):
            if key == "bytes_in_use":
                reads[key] += 1
                return [1000 * reads[key] * (d.id + 1) for d in devices]
            return [5000 + d.id for d in devices]
        run.chip_bytes = chip_bytes
        res = run.run_cell(bench, cell, config, traffic, 2**35 + 77, 3.0,
                           False, jax.devices()[:4])
        print(json.dumps(res))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 9
    assert res["device"]["count"] == 4
    assert res["device"]["memory_peak_bytes"] == 5003
    assert res["metrics"]["hbm_bytes_per_data_byte"]["value"] == \
        (2 - 1) * 1000 * (1 + 2 + 3 + 4) / (256 * 256 * 4)


@pytest.mark.parametrize("chips,shards", [(4, None), (1, 4), (4, 2)])
def test_chips_other_than_shards_exits_2_before_any_work(tmp_path, chips,
                                                         shards):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    config.pop("shards", None)
    if shards is not None:
        config["shards"] = shards
    (tmp_path / "bench" / "configs" / "draft.json").write_text(
        json.dumps(config))
    bench["configs"] = [dict(bench["configs"][0], name="draft",
                             file="bench/configs/draft.json")]
    bench["workloads"] = [dict(bench["workloads"][0], name="draft-cell",
                               config="draft", chips=chips)]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # any use of JAX would fail on this platform with another code
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "draft-cell",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout == ""
    assert "shards over" in out.stderr
    assert "jax" not in out.stderr.lower()
