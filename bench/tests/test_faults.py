"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of a run (`run.run_cell`: collection, engine,
server, the open-loop window, the reference comparison) at a small size
on the CPU, skipping only the look for a chip, with one fault planted
where the answers are produced.  The sound run beside them must come
out correct.  The control (the reference in bfloat16 in the program's
place) must come out not correct too.

    PYTHONPATH=src python -m pytest -q bench/tests/test_faults.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import bench.run as run
from bench import check, control, reference

SEED = 2**35 + 77


def small_cell(workload):
    bench, cell, config, traffic = run.load_spec(workload)
    config = dict(config, num_series=512)
    config["check"] = dict(config["check"], sample=4)
    traffic = dict(traffic, rate_per_s=3.0, lengths=[128], drain_s=20)
    return bench, cell, config, traffic


def run_small(workload="ed-knn-poisson", seconds=3.0):
    bench, cell, config, traffic = small_cell(workload)
    return run.run_cell(bench, cell, config, traffic, SEED, seconds,
                        False, jax.devices()[:1])


def test_sound_run_is_correct():
    res = run_small()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] == 9


def _patch_search(monkeypatch, alter):
    from repro.core.engine import UlisseEngine
    orig = UlisseEngine.search

    def search(self, queries, spec=None):
        out = orig(self, queries, spec)
        return alter(out) if isinstance(out, list) else out

    monkeypatch.setattr(UlisseEngine, "search", search)


def test_answer_altered_where_produced(monkeypatch):
    def alter(results):
        return [dataclasses.replace(r, offsets=r.offsets + 1)
                for r in results]
    _patch_search(monkeypatch, alter)
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["claim_gap"]["value"] > \
        res["checks"]["claim_gap"]["limit"]


def test_half_the_batch_answered_with_another_request(monkeypatch):
    def alter(results):
        half = len(results) // 2
        if half == 0:
            return results
        return results[:len(results) - half] + results[:half]
    _patch_search(monkeypatch, alter)
    # a load that queues, so that dispatches coalesce several requests
    bench, cell, config, traffic = small_cell("ed-knn-poisson")
    traffic["rate_per_s"] = 60.0
    config["check"]["sample"] = 12
    res = run.run_cell(bench, cell, config, traffic, SEED, 2.0, False,
                       jax.devices()[:1])
    assert not res["correct"]


def test_half_the_batch_never_answered(monkeypatch):
    from repro.serve import server as server_mod
    orig = server_mod.UlisseServer._dispatch

    def dispatch(self, bucket, batch):
        keep = batch[:(len(batch) + 1) // 2]
        orig(self, bucket, keep)

    monkeypatch.setattr(server_mod.UlisseServer, "_dispatch", dispatch)
    bench, cell, config, traffic = small_cell("ed-knn-poisson")
    traffic.update(rate_per_s=60.0, drain_s=3)
    res = run.run_cell(bench, cell, config, traffic, SEED, 2.0, False,
                       jax.devices()[:1])
    assert not res["correct"]
    assert res["checks"]["unanswered"]["value"] > 0
    assert res["failed"] > 0


def test_scan_returns_its_state_unchanged(monkeypatch):
    from repro.core import executor
    orig = executor.device_exact_scan

    def scan(collection, sids, anchors, n_master, lbs2, qs, dtw_lo,
             dtw_hi, seed_d2, seed_sid, seed_off, **kw):
        d2, sid, off, st = orig(collection, sids, anchors, n_master, lbs2,
                                qs, dtw_lo, dtw_hi, seed_d2, seed_sid,
                                seed_off, **kw)
        if kw.get("chunk_size") == 512:          # the exact scan
            return seed_d2, seed_sid, seed_off, st
        return d2, sid, off, st                  # the approximate pass

    monkeypatch.setattr(executor, "device_exact_scan", scan)
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["dist_gap"]["value"] > \
        res["checks"]["dist_gap"]["limit"]


def test_bfloat16_control_is_not_correct():
    bench, cell, config, traffic = small_cell("ed-knn-poisson")
    data, sample = control.sample_queries(config, traffic, SEED, 3.0)
    answers = control.control_answers(config, data, sample, jnp.bfloat16)
    checks = check.judge(check.readings(config, data, answers, reference),
                         config, 0)
    assert not check.is_correct(checks), checks
