"""The envelopes' breakpoint intervals the index holds on the device.

The lower bounds read [beta_l(iSAX(L)), beta_u(iSAX(U))] of every
candidate envelope.  The index computes them once per build, append,
compact or open (`UlisseIndex.intervals`), keeps its iSAX symbols on
the host, and the per-dispatch plan gathers nothing from the breakpoint
table.  These tests pin the held intervals to the paper's formula, the
plan's bounds to the old per-dispatch computation bit for bit, and the
counter that says how often the intervals are built.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.analysis.jaxpr_walk import walk
from repro.core import (Collection, EnvelopeParams, EnvelopeSet, QuerySpec,
                        UlisseEngine, bounds, isax, planner)

COUNTER = "ulisse_envelope_interval_builds_total"
PARAMS = EnvelopeParams(lmin=64, lmax=128, gamma=8, seg_len=16, card=64,
                        znorm=True)


def _builds() -> float:
    return obs.get_registry().get(COUNTER) or 0.0


def _engine(walk_collection):
    return UlisseEngine.from_collection(
        Collection.from_array(walk_collection[:16]), PARAMS, block_size=16,
        num_levels=2)


def _assert_held_intervals(index):
    env = index.search_envelopes()
    for f in ("sym_lo", "sym_hi"):
        assert isinstance(getattr(env, f), np.ndarray), f
        assert isinstance(getattr(index.envelopes, f), np.ndarray), f
    want_lo, want_hi = bounds.envelope_breakpoint_bounds(env,
                                                         index.breakpoints)
    got_lo, got_hi = index.intervals
    assert isinstance(got_lo, jax.Array) and isinstance(got_hi, jax.Array)
    assert got_lo.shape == (env.size, PARAMS.w) == got_hi.shape
    assert np.array_equal(np.asarray(got_lo), np.asarray(want_lo))
    assert np.array_equal(np.asarray(got_hi), np.asarray(want_hi))


def _step(engine, event, walk_collection, tmp_path):
    """Apply one index-changing event (after what it needs: a compact
    or an open of a delta-carrying save first appends); returns the
    engine to check and the interval builds the event itself made."""
    if event in ("compact", "open"):
        engine.append(walk_collection[16:20])
    before = _builds()
    if event == "append":
        engine.append(walk_collection[16:20])
    elif event == "compact":
        engine.compact()
        assert engine.delta_size == 0
    elif event == "open":
        engine = UlisseEngine.open(engine.save(str(tmp_path / "idx")),
                                   mmap=False)
        assert engine.delta_size > 0
    return engine, _builds() - before


@pytest.mark.parametrize("event", ["build", "append", "compact", "open"])
def test_held_intervals_equal_breakpoint_bounds(event, walk_collection,
                                                tmp_path):
    engine, _ = _step(_engine(walk_collection), event, walk_collection,
                      tmp_path)
    _assert_held_intervals(engine.index)
    q = walk_collection[3, 7:7 + 96]
    for spec in (QuerySpec(k=3), QuerySpec(k=3, scan_backend="host")):
        res = engine.search(q, spec)
        assert res.series[0] == 3 and res.offsets[0] == 7


@pytest.mark.parametrize("event", ["build", "append", "compact", "open"])
def test_interval_build_counter(event, walk_collection, tmp_path):
    before = _builds()
    engine = _engine(walk_collection)
    assert _builds() - before == 1
    if event != "build":
        engine, rise = _step(engine, event, walk_collection, tmp_path)
        assert rise == 1
    # queries read the held intervals and never build them
    before = _builds()
    qs = [walk_collection[i, 3:3 + 64 + 16 * (i % 3)] for i in range(6)]
    engine.search(qs, QuerySpec(k=2))
    engine.search(qs[:2], QuerySpec(k=2, measure="dtw", r=4))
    engine.search(qs[:2], QuerySpec(eps=2.0))
    engine.search(qs[0], QuerySpec(k=2, scan_backend="host"))
    assert _builds() == before


def _random_envelopes(seed: int, n: int = 300, w: int = 8,
                      card: int = 64):
    rng = np.random.default_rng(seed)
    bp = isax.gaussian_breakpoints(card)
    lo = rng.normal(size=(n, w)).astype(np.float32)
    hi = lo + np.abs(rng.normal(size=(n, w))).astype(np.float32)
    free = rng.random((n, w)) < 0.1          # unconstrained segments
    lo[free], hi[free] = -np.inf, np.inf
    env = EnvelopeSet(
        paa_lo=jnp.asarray(lo), paa_hi=jnp.asarray(hi),
        sym_lo=np.asarray(isax.symbolize(lo, bp)),
        sym_hi=np.asarray(isax.symbolize(hi, bp)),
        series_id=jnp.arange(n, dtype=jnp.int32),
        anchor=jnp.zeros(n, jnp.int32), n_master=jnp.ones(n, jnp.int32),
        valid=jnp.asarray(rng.random(n) < 0.9))
    q_lo = rng.normal(size=(4, w)).astype(np.float32)
    q_hi = q_lo + np.abs(rng.normal(size=(4, w))).astype(np.float32) \
        * (seed % 2)                          # ED (degenerate) or DTW
    return env, bp, jnp.asarray(q_lo), jnp.asarray(q_hi)


@partial(jax.jit, static_argnames=("seg_len", "nseg", "use_paa"))
def _per_dispatch_bounds(paa_lo, paa_hi, env, breakpoints, seg_len, nseg,
                         use_paa):
    """The plan's bounds as they were computed before the intervals were
    held: the breakpoint lookups inside every dispatch."""
    if use_paa:
        e_lo, e_hi = env.paa_lo, env.paa_hi
    else:
        e_lo, e_hi = bounds.envelope_breakpoint_bounds(env, breakpoints)
    d = bounds.interval_mindist(paa_lo, paa_hi, e_lo, e_hi, seg_len, nseg)
    return jnp.where(env.valid[None, :], d, jnp.inf)


@pytest.mark.parametrize("use_paa", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_plan_bounds_bitwise_equal_per_dispatch_formula(use_paa, seed):
    env, bp, q_lo, q_hi = _random_envelopes(seed)
    if use_paa:
        e_lo, e_hi = env.paa_lo, env.paa_hi
    else:
        e_lo, e_hi = bounds.envelope_breakpoint_bounds(env, bp)
    for nseg in (3, 8):
        want = np.asarray(_per_dispatch_bounds(q_lo, q_hi, env, bp, 16,
                                               nseg, use_paa))
        got = np.asarray(planner.env_lower_bounds_batch(
            q_lo, q_hi, e_lo, e_hi, env.valid, 16, nseg))
        assert np.array_equal(got, want)
        one = np.asarray(planner.env_lower_bounds(
            q_lo[1], q_hi[1], e_lo, e_hi, env.valid, 16, nseg))
        assert np.array_equal(one, want[1])


def _prims(fn, *args):
    return {site.prim for site in walk(jax.make_jaxpr(fn)(*args).jaxpr)}


def test_plan_function_has_no_gather():
    env, bp, q_lo, q_hi = _random_envelopes(0)
    e_lo, e_hi = bounds.envelope_breakpoint_bounds(env, bp)
    plan = partial(planner.env_lower_bounds_batch, seg_len=16, nseg=8)
    assert "gather" not in _prims(plan, q_lo, q_hi, e_lo, e_hi, env.valid)
    # the walk does see the gather the per-dispatch formula had
    old = partial(_per_dispatch_bounds, seg_len=16, nseg=8, use_paa=False)
    assert "gather" in _prims(old, q_lo, q_hi, env, bp)


@pytest.mark.parametrize("with_delta", [False, True])
def test_device_arrays_hold_intervals_in_place_of_symbols(
        with_delta, walk_collection):
    engine = _engine(walk_collection)
    if with_delta:
        engine.append(walk_collection[16:19])
    arrays = engine.device_arrays()
    assert all(isinstance(a, jax.Array) for a in arrays.values())
    assert not {"sym_lo", "sym_hi"} & set(arrays)
    assert not [f for f, a in arrays.items()
                if a.dtype == jnp.int32 and a.ndim == 2]
    env = engine.index.search_envelopes()
    # the bytes the device held before: every field of the candidate
    # set, the (int32) symbols included, and the collection
    coll = engine.index.collection
    before = sum(getattr(coll, f).nbytes for f in (
        "data", "csum", "csum2", "csum_lo", "csum2_lo", "center"))
    before += sum(np.asarray(getattr(env, f)).nbytes for f in (
        "paa_lo", "paa_hi", "sym_lo", "sym_hi", "series_id", "anchor",
        "n_master", "valid"))
    assert sum(a.nbytes for a in arrays.values()) == before
    assert arrays["interval_lo"].dtype == jnp.float32
    assert arrays["interval_lo"].shape == env.sym_lo.shape
