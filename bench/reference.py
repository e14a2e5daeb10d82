"""The plain reference: exact Z-normalized ED and banded-DTW k-NN by
brute force over every window of every series, in `jax.numpy`.

It imports nothing of the program under test and takes nothing it
made.  Its semantics are the program's (`repro.core`):

* Z-normalization: zero mean and unit *population* standard deviation
  (ddof = 0) over the window, the deviation floored at 1e-8, so a flat
  window normalizes to all zeros (`core/paa.znormalize`,
  `core/executor` window stats, `core/engine._ed_rescore`).
* ED: the sum of squared differences of the normalized query and
  window, reported as its square root.
* DTW: the Sakoe-Chiba banded DTW, |i - j| <= r, over squared point
  costs, reported as the square root of the path cost
  (`core/dtw.dtw_band(squared=True)`).  Computed here by anti-diagonal
  wavefront, a formulation independent of the program's row closed
  form.
* k-NN: the k smallest distances over all (series, offset) windows of
  the query's length, ties in (series, offset) order.

Series lie on the lanes (the last axis) so that the per-offset work is
dense, and blocks of series run one after another so that the whole
collection fits on the chip.  `dtype` sets the precision of every
operation: float32 is the reference, bfloat16 is the control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-8


def _znorm(x, axis):
    mu = jnp.mean(x, axis=axis, keepdims=True)
    sd = jnp.std(x, axis=axis, keepdims=True)
    return (x - mu) / jnp.maximum(sd, EPS)


def _window_stats(dt, l: int, n_off: int):
    """(n_off, Sb) mean and floored std of every window of length l of
    the (n, Sb) block."""
    def one(o):
        w = jax.lax.dynamic_slice_in_dim(dt, o, l, axis=0)
        return (jnp.mean(w, axis=0),
                jnp.maximum(jnp.std(w, axis=0), EPS).astype(dt.dtype))
    return jax.lax.map(one, jnp.arange(n_off))


def _ed_block(dt, qn, l: int, n_off: int):
    """(n_off, Sb) squared ED of the normalized query to every window."""
    def one(o):
        w = jax.lax.dynamic_slice_in_dim(dt, o, l, axis=0)
        mu = jnp.mean(w, axis=0, keepdims=True)
        sd = jnp.maximum(jnp.std(w, axis=0, keepdims=True), EPS)
        return jnp.sum(((w - mu) / sd - qn[:, None]) ** 2, axis=0)
    return jax.lax.map(one, jnp.arange(n_off))


def _dtw_block(dt, qn, l: int, n_off: int, r: int):
    """(n_off, Sb) squared banded DTW of the normalized query to every
    window, by anti-diagonal wavefront.

    State: the cost matrix's last two anti-diagonals t-1 and t-2,
    indexed by d = i - j in [-r, r], shape (2r+1, n_off, Sb).  Cell
    (i, j) on diagonal t = i + j takes its cost plus the least of
    (i-1, j) [diagonal t-1, d-1], (i, j-1) [t-1, d+1] and
    (i-1, j-1) [t-2, d].
    """
    dtype = dt.dtype
    mu, sd = _window_stats(dt, l, n_off)
    inf = jnp.array(jnp.inf, dtype)
    dvec = jnp.arange(-r, r + 1)
    band = 2 * r + 1
    shape = (band, n_off, dt.shape[1])
    pad = jnp.full((1,) + shape[1:], inf)

    def step(t, carry):
        prev2, prev1 = carry
        i = (t + dvec) // 2
        j = (t - dvec) // 2
        valid = (((t + dvec) % 2) == 0) & (i >= 0) & (i < l) \
            & (j >= 0) & (j < l)
        qv = qn[jnp.clip(i, 0, l - 1)]
        jlo = jnp.clip((t - r) // 2, 0, l - 1 - r)
        x = jax.lax.dynamic_slice_in_dim(dt, jlo, n_off + r, axis=0)
        xs = jnp.stack([x[m:m + n_off] for m in range(r + 1)])
        wv = jnp.take(xs, jnp.clip(j - jlo, 0, r), axis=0)
        wn = (wv - mu[None]) / sd[None]
        cost = (qv[:, None, None] - wn) ** 2
        up = jnp.concatenate([pad, prev1[:-1]], axis=0)
        left = jnp.concatenate([prev1[1:], pad], axis=0)
        best = jnp.minimum(jnp.minimum(up, left), prev2)
        best = jnp.where(t == 0, jnp.zeros_like(best), best)
        new = jnp.where(valid[:, None, None], cost + best, inf)
        return prev1, new

    init = (jnp.full(shape, inf), jnp.full(shape, inf))
    _, last = jax.lax.fori_loop(0, 2 * l - 1, step, init)
    return last[r]


def _block_size(measure: str, s: int, n: int, l: int, r: int) -> int:
    """Series per block: about 2^25 values of the per-step state."""
    per = (2 * r + 1) * (n - l + 1) if measure == "dtw" else l
    b = max(128, (1 << 25) // max(per, 1))
    b = 1 << (b.bit_length() - 1)
    while s % b:
        b //= 2
    return max(b, 1)


@partial(jax.jit, static_argnames=("l", "measure", "r", "block", "dtype"))
def _table(data, q, *, l: int, measure: str, r: int, block: int, dtype):
    """(S, n_off) squared distances, series-major."""
    s, n = data.shape
    n_off = n - l + 1
    qn = _znorm(q.astype(dtype), 0)
    dt = data.astype(dtype).T.reshape(n, s // block, block)
    dt = jnp.moveaxis(dt, 1, 0)                 # (nb, n, block)

    def one(blk):
        if measure == "ed":
            return _ed_block(blk, qn, l, n_off)
        return _dtw_block(blk, qn, l, n_off, r)

    tab = jax.lax.map(one, dt)                  # (nb, n_off, block)
    return jnp.moveaxis(tab, 1, 2).reshape(s, n_off)


@partial(jax.jit, static_argnames=("k",))
def _topk(tab, k: int):
    neg, flat = jax.lax.top_k(-tab.reshape(-1), k)
    return -neg, flat


def knn(data, q, k: int, measure: str, r: int = 0, dtype=jnp.float32):
    """Exact k-NN of one query over every window of `data` (S, n).

    Returns host arrays (dists float64 ascending, series, offsets)."""
    q = jnp.asarray(q)
    l = int(q.shape[0])
    s, n = int(data.shape[0]), int(data.shape[1])
    block = _block_size(measure, s, n, l, r)
    tab = _table(data, q, l=l, measure=measure, r=r, block=block,
                 dtype=dtype)
    d2, flat = _topk(tab, k)
    d2 = np.asarray(d2.astype(jnp.float32), np.float64)
    flat = np.asarray(flat, np.int64)
    n_off = n - l + 1
    return np.sqrt(np.maximum(d2, 0.0)), flat // n_off, flat % n_off


@partial(jax.jit, static_argnames=("l",))
def _cut(data, sids, offs, l: int):
    rows = jnp.take(data, sids, axis=0)
    return jax.vmap(lambda row, o: jax.lax.dynamic_slice_in_dim(
        row, o, l))(rows, offs)


def window_dists(data, q, series, offsets, measure: str, r: int = 0,
                 dtype=jnp.float32) -> np.ndarray:
    """Distances (float64, host) of the query to the given windows."""
    q = jnp.asarray(q)
    l = int(q.shape[0])
    m = len(series)
    if m == 0:
        return np.zeros((0,), np.float64)
    wins = _cut(data, jnp.asarray(np.asarray(series, np.int32)),
                jnp.asarray(np.asarray(offsets, np.int32)), l)
    tab = _table(wins, q, l=l, measure=measure, r=r,
                 block=_block_size(measure, m, l, l, r), dtype=dtype)
    d2 = np.asarray(tab[:, 0].astype(jnp.float32), np.float64)
    return np.sqrt(np.maximum(d2, 0.0))
