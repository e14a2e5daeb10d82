"""ULISSE similarity search — legacy free-function surface.

.. deprecated::
    `approx_knn` / `exact_knn` / `range_query` are thin wrappers over
    `repro.core.engine.UlisseEngine`, kept so existing callers and tests
    keep working.  New code should build one engine and describe queries
    with `QuerySpec` (see DESIGN.md for the migration table):

        engine = UlisseEngine.from_index(index)
        engine.search(q, QuerySpec(k=5, measure="dtw", r=9))

The algorithms themselves (paper Alg. 4/5, the LB-sorted chunked scan,
the MXU verification kernels) live in the planner/executor split:
repro.core.planner (query prep + lower-bound ordering) and
repro.core.executor (verification kernels, TopK pool, stats).

`brute_force_knn` — the exhaustive oracle used by tests and benchmarks —
is not deprecated and stays here.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtw
from repro.core.engine import QuerySpec, UlisseEngine
# re-exported for backwards compatibility (these used to be defined here)
from repro.core.executor import (SearchResult, SearchStats,  # noqa: F401
                                 TopK as _TopK)
from repro.core.index import UlisseIndex
from repro.core.paa import znormalize
from repro.core.planner import PreparedQuery, prepare_query  # noqa: F401
from repro.core.types import Collection


def _deprecated(old: str, new: str):
    warnings.warn(
        f"repro.core.search.{old} is deprecated; use UlisseEngine.search "
        f"with {new}", DeprecationWarning, stacklevel=3)


def approx_knn(index: UlisseIndex, q, k: int = 1, measure: str = "ed",
               r: int = 0, max_leaves: int = 8) -> SearchResult:
    """Deprecated wrapper: best-first approximate k-NN (paper Alg. 4)."""
    _deprecated("approx_knn", "QuerySpec(mode='approx', ...)")
    return UlisseEngine.from_index(index).search(
        q, QuerySpec(mode="approx", k=k, measure=measure, r=r,
                     max_leaves=max_leaves))


def exact_knn(index: UlisseIndex, q, k: int = 1, measure: str = "ed",
              r: int = 0, chunk_size: int = 512,
              use_paa_bounds: bool = False,
              approx_first: bool = True) -> SearchResult:
    """Deprecated wrapper: exact k-NN (paper Alg. 5)."""
    _deprecated("exact_knn", "QuerySpec(mode='exact', ...)")
    return UlisseEngine.from_index(index).search(
        q, QuerySpec(mode="exact", k=k, measure=measure, r=r,
                     chunk_size=chunk_size, use_paa_bounds=use_paa_bounds,
                     approx_first=approx_first))


def range_query(index: UlisseIndex, q, eps: float, measure: str = "ed",
                r: int = 0, chunk_size: int = 2048) -> SearchResult:
    """Deprecated wrapper: eps-range query (Alg. 5 with bsf := eps)."""
    _deprecated("range_query", "QuerySpec(eps=...)")
    return UlisseEngine.from_index(index).search(
        q, QuerySpec(eps=float(eps), measure=measure, r=r,
                     chunk_size=chunk_size))


# --------------------------------------------------------------------------
# brute-force oracle (ground truth for tests/benchmarks)
# --------------------------------------------------------------------------

def brute_force_d2(data, q, znorm: bool, measure: str = "ed",
                   r: int = 0) -> jax.Array:
    """(S, n - |Q| + 1) float32 squared distances from Q to every
    window of every series of `data`, left on the device that holds
    `data`.  Series are mapped in vmapped blocks of about 2^25 window
    values, so a million-series collection is a few hundred device
    steps, not a million."""
    q = jnp.asarray(q, jnp.float32)
    qlen = int(q.shape[-1])
    n_off = int(data.shape[1]) - qlen + 1
    block = max(1, (1 << 25) // (n_off * qlen))
    return _brute_d2(data, q, znorm, measure, r, block)


@partial(jax.jit, static_argnames=("znorm", "measure", "r", "block"))
def _brute_d2(data, q, znorm: bool, measure: str, r: int, block: int):
    qlen = q.shape[-1]
    qn = znormalize(q) if znorm else q
    offs = jnp.arange(data.shape[1] - qlen + 1, dtype=jnp.int32)

    def per_series(row):
        wins = jax.vmap(
            lambda o: jax.lax.dynamic_slice(row, (o,), (qlen,)))(offs)
        wn = znormalize(wins) if znorm else wins
        if measure == "ed":
            # direct differences: the dot identity cancels near d = 0
            return jnp.sum((wn - qn) ** 2, axis=-1)
        return dtw.dtw_band(qn, wn, r, squared=True)

    return jax.lax.map(per_series, data, batch_size=block)


@partial(jax.jit, static_argnames=("k",))
def _table_topk(d2, k: int):
    """The k smallest entries of one table and their flat indices, on
    its device; equal values keep flat-index order, as in a stable
    sort (lax.top_k returns the lower index first on ties)."""
    s, n_off = d2.shape
    neg, col = jax.lax.top_k(-d2, min(k, n_off))        # per series
    flat = jnp.arange(s, dtype=jnp.int32)[:, None] * n_off + col
    neg, pos = jax.lax.top_k(neg.reshape(-1), min(k, neg.size))
    return -neg, flat.reshape(-1)[pos]


def _result(d2: np.ndarray, flat: np.ndarray, n_off: int) -> SearchResult:
    return SearchResult(
        dists=np.sqrt(np.maximum(d2.astype(np.float64), 0.0)),
        series=(flat // n_off).astype(np.int64),
        offsets=(flat % n_off).astype(np.int64),
        stats=SearchStats(envelopes_total=0))


def knn_from_d2(tables, k: int) -> SearchResult:
    """The k smallest windows over distance tables that stack, in this
    order, into the collection's (S, n_off) table — one table, or one
    per shard, each reduced to its own k best on its own device and
    merged on the host.  Ties in (series, offset) order, as a stable
    argsort of the whole table would give."""
    d2s, flats, base = [], [], 0
    for t in tables:
        d, i = _table_topk(t, k)
        d2s.append(np.asarray(d))
        flats.append(np.asarray(i, np.int64) + base)
        base += t.size
    d2, flat = np.concatenate(d2s), np.concatenate(flats)
    order = np.lexsort((flat, d2))[:k]
    return _result(d2[order], flat[order], tables[0].shape[1])


def range_from_d2(tables, eps: float) -> SearchResult:
    """Every window with d <= eps over distance tables stacked as in
    `knn_from_d2`, ascending by distance (ties in (series, offset)
    order); only the hits leave each device."""
    bound = float(eps) ** 2
    # the largest float32 <= eps^2: float32 d2 <= it iff d2 <= eps^2
    cut = np.float32(bound)
    if float(cut) > bound:
        cut = np.nextafter(cut, np.float32(-np.inf))
    d2s, flats, base = [], [], 0
    for t in tables:
        flat = t.reshape(-1)
        hit = flat <= cut
        i = jnp.nonzero(hit, size=int(jnp.sum(hit)))[0]
        d2s.append(np.asarray(flat[i]))
        flats.append(np.asarray(i, np.int64) + base)
        base += t.size
    d2, flat = np.concatenate(d2s), np.concatenate(flats)
    order = np.lexsort((flat, d2))
    return _result(d2[order], flat[order], tables[0].shape[1])


def brute_force_knn(collection: Collection, q, k: int, znorm: bool,
                    measure: str = "ed", r: int = 0) -> SearchResult:
    """Exhaustive scan over every subsequence of length |Q| (oracle)."""
    return knn_from_d2(
        [brute_force_d2(collection.data, q, znorm, measure, r)], k)


def brute_force_range(collection: Collection, q, eps: float, znorm: bool,
                      measure: str = "ed", r: int = 0) -> SearchResult:
    """Exhaustive eps-range oracle: every subsequence with d <= eps,
    sorted ascending by distance (ties in (series, offset) order)."""
    return range_from_d2(
        [brute_force_d2(collection.data, q, znorm, measure, r)], eps)
