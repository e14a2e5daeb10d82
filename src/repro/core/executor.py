"""Verification kernels for ULISSE search (the *executor* half).

Everything that touches raw series data lives here: candidate-window
gathers, batched true-distance kernels (ED on the MXU via the dot-product
identity, the LB_Keogh -> banded-DP DTW cascade), the host-side k-best
pool, and the result/stats containers.  The planner half (planner.py)
decides *which* envelopes to verify; this module computes the distances.

Like the planner, two shape regimes coexist:

  * static qlen (`gather_windows`, `ed_batch`, ...) — the host-driven
    local backend, jitted once per query length;
  * bucket-padded traced qlen (`gather_bucket_windows`, `masked_ed`) —
    pure traceable functions called inside the batched distributed
    shard_map programs, one executable per length bucket.
"""
from __future__ import annotations

import dataclasses
import functools
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dtw
from repro.core.paa import masked_znormalize, znormalize
from repro.kernels.common import default_interpret
from repro.kernels.fused_verify import (fused_gather_ed,
                                        fused_gather_lb_keogh)


# --------------------------------------------------------------------------
# results + stats
# --------------------------------------------------------------------------

# The per-query device stats vector carried through the scan loops:
# column order is load-bearing (engine stats assembly, distributed
# per-shard stacks, and the obs exporter all index into it).  Every
# consumer imports THESE names — repro.analysis rule R5 flags any
# module restating the width or the order as its own literal.
STATS_COLUMNS = ("chunks_visited", "envelopes_checked",
                 "true_dist_computations", "dtw_lb_keogh", "dtw_full",
                 "envelopes_pruned")
STATS_WIDTH = 6
assert len(STATS_COLUMNS) == STATS_WIDTH


@dataclasses.dataclass
class SearchStats:
    """The ONE per-query stats schema every backend populates
    (host, device, distributed-per-shard) — DESIGN.md §12.

    Counter semantics are backend-independent: `envelopes_pruned`
    counts envelopes cut by the bsf/eps lower-bound test *inside
    visited chunks* (plan rows never reached because the scan stopped
    early are neither checked nor pruned — the gap is
    `chunks_planned - chunks_visited`); `chunks_planned` is the
    dispatch plan's chunk count (device: padded plan rows / chunk
    size; host: candidate batches the reference loop would run
    unpruned; sharded: summed over shards).
    """
    envelopes_total: int = 0
    envelopes_checked: int = 0       # envelopes whose raw data was read
    envelopes_pruned: int = 0        # LB/bsf cuts inside visited chunks
    lb_computations: int = 0
    true_dist_computations: int = 0  # ED or DTW on raw windows
    dtw_lb_keogh: int = 0            # second-tier LB computations
    dtw_full: int = 0                # full banded DPs executed
    leaves_visited: int = 0
    chunks_visited: int = 0
    chunks_planned: int = 0          # chunks in the dispatch plan
    exact_from_approx: bool = False
    escalations: int = 0             # exactness-certificate retries
    range_overflows: int = 0         # device hit-buffer overflows (range)
    shard_chunks: Optional[list] = None  # per-shard chunk counts (sharded
    #                                      scan only; chunks_visited sums it)

    @property
    def pruning_power(self) -> float:
        if self.envelopes_total == 0:
            return 0.0
        return 1.0 - self.envelopes_checked / self.envelopes_total

    @property
    def abandoning_power(self) -> float:
        """Fraction of candidate true-distance computations avoided."""
        if self.dtw_lb_keogh > 0:
            return 1.0 - self.dtw_full / max(self.dtw_lb_keogh, 1)
        return 0.0

    def as_dict(self) -> dict:
        """JSON-ready snapshot including the derived ratios — what the
        obs exporters and examples print."""
        d = dataclasses.asdict(self)
        d["pruning_power"] = self.pruning_power
        d["abandoning_power"] = self.abandoning_power
        return d


@dataclasses.dataclass
class SearchResult:
    dists: np.ndarray      # (k,) sorted true distances
    series: np.ndarray     # (k,) series ids
    offsets: np.ndarray    # (k,) window offsets
    stats: SearchStats


class TopK:
    """Host-side k-best pool over (dist, sid, off) triples."""

    def __init__(self, k: int):
        self.k = k
        self.d = np.full((0,), np.inf, np.float64)
        self.s = np.zeros((0,), np.int64)
        self.o = np.zeros((0,), np.int64)

    def push(self, d, s, o):
        d = np.concatenate([self.d, np.asarray(d, np.float64)])
        s = np.concatenate([self.s, np.asarray(s, np.int64)])
        o = np.concatenate([self.o, np.asarray(o, np.int64)])
        # dedup (sid, off): the approx phase and the exact scan may verify
        # the same envelope; a subsequence must appear in the pool once.
        # lexsort on the raw columns — a packed s * 2^32 + o key silently
        # collides/overflows once sid >= 2^31 or off >= 2^32
        order = np.lexsort((d, o, s))
        d, s, o = d[order], s[order], o[order]
        first = np.ones(len(d), bool)
        first[1:] = (s[1:] != s[:-1]) | (o[1:] != o[:-1])
        d, s, o = d[first], s[first], o[first]
        order = np.argsort(d, kind="stable")[: self.k]
        self.d, self.s, self.o = d[order], s[order], o[order]

    @property
    def kth(self) -> float:
        return float(self.d[-1]) if len(self.d) == self.k else np.inf

    def result(self, stats: SearchStats) -> SearchResult:
        return SearchResult(dists=np.sqrt(np.maximum(self.d, 0.0)),
                            series=self.s, offsets=self.o, stats=stats)


# --------------------------------------------------------------------------
# jitted device steps (static qlen)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("qlen", "g"))
def gather_windows(data: jnp.ndarray, sids, anchors, n_master,
                   qlen: int, g: int):
    """Raw candidate windows for a batch of envelopes.

    Each envelope contributes g = gamma+1 candidate offsets
    anchor .. anchor + g - 1 (masked by n_master and by window fit).
    Returns windows (B*g, qlen) and a validity mask (B*g,).
    """
    n = data.shape[1]
    offs = anchors[:, None] + jnp.arange(g, dtype=jnp.int32)[None, :]  # (B,g)
    ok = (jnp.arange(g)[None, :] < n_master[:, None]) & (offs + qlen <= n)
    offs_c = jnp.clip(offs, 0, n - qlen)

    def slice_one(sid, off):
        return jax.lax.dynamic_slice(data, (sid, off), (1, qlen))[0]

    windows = jax.vmap(jax.vmap(slice_one, in_axes=(None, 0)),
                       in_axes=(0, 0))(sids, offs_c)
    B = offs.shape[0]
    return (windows.reshape(B * g, qlen), ok.reshape(B * g),
            offs.reshape(B * g))


@partial(jax.jit, static_argnames=("znorm",))
def ed_batch(windows: jnp.ndarray, q: jnp.ndarray, znorm: bool):
    """Batched ED (squared) via the dot-product identity (MXU-friendly).

    Z-normalized: q is already normalized, so Qhat.What = (W @ q) / sigma_w
    and ED^2 = 2l - 2 (W @ q) / sigma_w.
    """
    l = windows.shape[-1]
    # HIGHEST: f32 passes on the TPU's MXU, not its default single
    # bf16 pass (whose error would swamp near-tie distances)
    dots = jnp.dot(windows, q, precision=jax.lax.Precision.HIGHEST)
    if znorm:
        mu = jnp.mean(windows, axis=-1)
        var = jnp.mean(windows * windows, axis=-1) - mu * mu
        sd = jnp.maximum(jnp.sqrt(jnp.maximum(var, 0.0)), 1e-8)
        d2 = 2.0 * l - 2.0 * dots / sd
    else:
        d2 = (jnp.sum(windows * windows, axis=-1) - 2.0 * dots
              + jnp.sum(q * q))
    return jnp.maximum(d2, 0.0)


@partial(jax.jit, static_argnames=("znorm",))
def lb_keogh_batch(windows, dtw_lo, dtw_hi, znorm: bool):
    if znorm:
        windows = znormalize(windows)
    return dtw.lb_keogh(dtw_lo, dtw_hi, windows, squared=True), windows


@partial(jax.jit, static_argnames=("r", "znorm"))
def dtw_batch(windows, q, r: int, znorm: bool):
    if znorm:
        windows = znormalize(windows)
    return dtw.dtw_band(q, windows, r, squared=True)


# --------------------------------------------------------------------------
# bucket-padded primitives (traced qlen; used inside shard_map programs)
# --------------------------------------------------------------------------

def gather_bucket_windows(data: jnp.ndarray, sids, anchors, n_master,
                          qlen: jnp.ndarray, bucket: int, g: int):
    """gather_windows with a *traced* true length over a static bucket.

    Slices `bucket`-length windows (clamped to fit the series, then rolled
    so position 0 is the true window start); entries past qlen are
    garbage and must be masked by the caller.  Returns
    (windows (B*g, bucket), ok (B*g,), offs (B*g,)).
    """
    n = data.shape[1]
    offs = anchors[:, None] + jnp.arange(g, dtype=jnp.int32)[None, :]
    ok = (jnp.arange(g)[None, :] < n_master[:, None]) & (offs + qlen <= n)
    offs_c = jnp.clip(offs, 0, n - bucket)

    def slice_one(sid, off, off_c):
        w = jax.lax.dynamic_slice(data, (sid, off_c), (1, bucket))[0]
        w = jnp.roll(w, off_c - off)   # left-shift by the clamp delta
        # the roll wraps the slab's first off-off_c values into positions
        # >= n - off; zero them so pre-window data can never leak through
        # a caller whose tail masking assumes in-series values there
        return jnp.where(jnp.arange(bucket) < n - off, w, 0.0)

    windows = jax.vmap(jax.vmap(slice_one, in_axes=(None, 0, 0)),
                       in_axes=(0, 0, 0))(sids, jnp.clip(offs, 0, n),
                                          offs_c)
    B = offs.shape[0]
    return (windows.reshape(B * g, bucket), ok.reshape(B * g),
            offs.reshape(B * g))


def masked_ed(windows: jnp.ndarray, qn: jnp.ndarray, mask: jnp.ndarray,
              qlen: jnp.ndarray, znorm: bool):
    """Squared ED between bucket-padded windows and a prepared query.

    qn must already be masked-normalized with a zero tail (see
    planner.masked_prepare); windows are normalized here the same way, so
    the direct sum of squared differences over the bucket equals the ED
    over the true qlen-prefix.
    """
    if znorm:
        wn = masked_znormalize(windows, mask[None, :], qlen)
    else:
        wn = jnp.where(mask[None, :], windows, 0.0)
    return jnp.sum((wn - qn[None, :]) ** 2, axis=-1)


# --------------------------------------------------------------------------
# verification of a batch of envelopes (host-driven local backend)
# --------------------------------------------------------------------------

def verify_envelopes(index, pq, env_idx: np.ndarray, pool: TopK,
                     stats: SearchStats, eps2: Optional[float] = None,
                     collector: Optional[list] = None):
    """Compute true distances for all candidates of the given envelopes.

    Updates the pool (k-NN) or appends (sid, off, d2) rows below eps2 to
    `collector` (range query).  Distances are squared throughout.

    `env_idx` indexes the combined candidate set (main ++ delta, see
    UlisseIndex.search_envelopes) — the collection already holds the
    raw rows of appended series, so the gather is uniform.
    """
    p = index.params
    env = index.search_envelopes()
    g = p.gamma + 1
    idx = jnp.asarray(env_idx, jnp.int32)
    sids = jnp.take(env.series_id, idx)
    anchors = jnp.take(env.anchor, idx)
    n_master = jnp.take(env.n_master, idx)

    windows, ok, offs = gather_windows(index.collection.data, sids, anchors,
                                       n_master, pq.qlen, g)
    stats.envelopes_checked += len(env_idx)
    verify_windows(windows, np.repeat(np.asarray(sids), g),
                   np.asarray(offs), np.asarray(ok), pq, p.znorm, pool,
                   stats, eps2=eps2, collector=collector)


def verify_windows(windows, all_sids: np.ndarray, offs_np: np.ndarray,
                   ok_np: np.ndarray, pq, znorm: bool, pool: TopK,
                   stats: SearchStats, *, eps2: Optional[float] = None,
                   collector: Optional[list] = None):
    """Distance tiers + pool/collector update for gathered candidate
    windows (B*g, qlen).

    The verification half of `verify_envelopes`, split out so every
    host-side caller shares ONE copy of the cut and padding rules —
    the index-driven reference path and the distributed range
    continuation (`engine._host_range_tail`, which gathers its windows
    from a host array instead of an index): the inclusive range-query
    cuts and the pow2-padded DTW survivor batch must never diverge
    between them.
    """
    if pq.measure == "ed":
        d2 = np.asarray(ed_batch(windows, pq.q, znorm), np.float64)
        d2[~ok_np] = np.inf
        stats.true_dist_computations += int(ok_np.sum())
    else:
        lb2, wn = lb_keogh_batch(windows, pq.dtw_lo, pq.dtw_hi, znorm)
        lb2 = np.asarray(lb2, np.float64)
        lb2[~ok_np] = np.inf
        stats.dtw_lb_keogh += int(ok_np.sum())
        # k-NN prunes strictly (lb == kth cannot improve the pool), but
        # range queries collect d2 <= eps2, and lb <= d — a strict cut
        # would drop true boundary hits with lb == d == eps
        if eps2 is None:
            survivors = np.nonzero(lb2 < pool.kth)[0]
        else:
            survivors = np.nonzero(lb2 <= eps2)[0]
        d2 = np.full(lb2.shape, np.inf)
        if len(survivors) > 0:
            # pad survivors to a pow2 bucket to bound recompilation
            m = pow2ceil(len(survivors))
            pad = np.concatenate([survivors,
                                  np.full(m - len(survivors), survivors[0])])
            dd = np.asarray(dtw_batch(wn[jnp.asarray(pad)], pq.q, pq.r,
                                      False), np.float64)
            d2[survivors] = dd[: len(survivors)]
            stats.dtw_full += len(survivors)
        stats.true_dist_computations += len(survivors)

    if collector is not None:
        hit = np.nonzero(d2 <= eps2)[0]
        if len(hit):
            collector.append(np.stack([all_sids[hit], offs_np[hit],
                                       d2[hit]], axis=1))
    else:
        pool.push(d2, all_sids, offs_np)


# --------------------------------------------------------------------------
# device-resident exact scan (paper Alg. 5 as ONE device program)
# --------------------------------------------------------------------------
#
# The host-driven loop above syncs device->host once per chunk and re-sorts
# a numpy pool on every push.  The device scan instead carries a (k,)
# squared-distance pool + (sid, off) codes through a lax.while_loop over
# pow2-padded LB-sorted chunks: each step gathers + verifies one chunk via
# the fused Pallas kernels (kernels/fused_verify.py), prunes against the
# running kth bound on device, and merges with one lax.top_k.  The only
# host sync is the final pool readback — one per query (or per batch, on
# the vmapped multi-query path).

def pow2ceil(x: int) -> int:
    b = 1
    while b < x:
        b <<= 1
    return b


def shard_pack_geometry(n_rows: int, delta_rows: int, chunk_size: int):
    """Chunk geometry of a shard's packed kNN plan with a delta-first
    region (DESIGN.md §15).

    The sharded scan packs each shard's `delta_rows` unsorted delta
    envelopes FIRST — padded up to whole chunks — followed by the
    LB-sorted main rows, then pow2-pads the total.  Returns
    (n_pad, chunk, nd_pad): the packed plan width, the chunk size the
    scan will use, and the padded delta region width (a multiple of
    `chunk`; `nd_pad // chunk` is the number of always-visited delta
    chunks the approximate budget must be extended by).  With
    delta_rows == 0 this reduces to the classic geometry
    (n_pad = pow2ceil(n_rows), nd_pad = 0).

    One implementation shared by the shard_map program makers
    (distributed/ulisse.py) and the engine's stats/plan accounting —
    restating it would let the two drift.
    """
    chunk = min(pow2ceil(chunk_size), pow2ceil(max(n_rows, 1)))
    nd_pad = -(-delta_rows // chunk) * chunk
    n_pad = pow2ceil((n_rows - delta_rows) + nd_pad)
    return n_pad, chunk, nd_pad


def _chunk_slice(sids, anchors, n_master, lbs2, i, chunk: int):
    """Slice chunk i out of the packed (B, n_pad) plan arrays."""
    return (jax.lax.dynamic_slice_in_dim(sids, i * chunk, chunk, 1),
            jax.lax.dynamic_slice_in_dim(anchors, i * chunk, chunk, 1),
            jax.lax.dynamic_slice_in_dim(n_master, i * chunk, chunk, 1),
            jax.lax.dynamic_slice_in_dim(lbs2, i * chunk, chunk, 1))


def _chunk_candidates(csid, canc, cnm, keep, qlen: int, n: int, g: int):
    """Expand a chunk's envelopes into per-offset candidates.

    Shared by the exact and range cores so the window-fit test stays
    identical on both paths.  Returns (ok, cand_sid, cand_off) each
    (B, chunk*g): ok masks offsets that are real masters, fit the
    series, and belong to a kept (unpruned) envelope.
    """
    b_sz, chunk = csid.shape
    joff = jnp.arange(g, dtype=jnp.int32)
    offs = canc[:, :, None] + joff[None, None, :]       # (B, chunk, g)
    ok = ((joff[None, None, :] < cnm[:, :, None]) & (offs + qlen <= n)
          & keep[:, :, None]).reshape(b_sz, chunk * g)
    return ok, jnp.repeat(csid, g, axis=1), offs.reshape(b_sz, chunk * g)


def _survivors_first(surv: jnp.ndarray) -> jnp.ndarray:
    """Stable survivors-first position pack of a (B, M) mask.

    The gather twin of `jnp.argsort(~surv)`: position j of the result
    is the j-th True column (binary search over the mask cumsum);
    positions >= nsurv carry clamped duplicates, which every consumer
    masks by `pos < nsurv`.  Two reasons over argsort: (a) a sort is
    ~the cost of a whole verification chunk on CPU while the cumsum
    pack is a few fused elementwise passes, and (b) XLA's SPMD
    partitioner rewrites sorts inside a while body into cross-device
    all-reduce canonicalization even in a manual shard_map region —
    which deadlocks the sharded scan, whose shards run data-dependent
    trip counts between bsf syncs.
    """
    sc = jnp.cumsum(surv, axis=1)
    ranks = jnp.arange(surv.shape[1], dtype=jnp.int32) + 1
    sidx = jax.vmap(jnp.searchsorted, in_axes=(0, None))(sc, ranks)
    return jnp.minimum(sidx, surv.shape[1] - 1).astype(jnp.int32)


def _survivor_bucket(data, qs, cand_sid, cand_off, sidx, mu, sd, j,
                     *, sb: int, r: int, znorm: bool):
    """Gather + normalize + DP one masked survivor bucket (DTW tier).

    Shared by the exact and range cores: the window clamp and the reuse
    of the LB kernel's (mu, sd) are what keep LB_Keogh <= DTW exact
    on-device (pruning soundness) — one implementation, two callers.
    Returns (pos, bs, bo, db): bucket positions, candidate codes, and
    squared banded-DTW distances (B, sb).
    """
    n = data.shape[1]
    b_sz, chunk_g = cand_sid.shape
    qlen = qs.shape[1]
    pos = j * sb + jnp.arange(sb)
    bi = jnp.take_along_axis(
        sidx, jnp.minimum(pos, chunk_g - 1)[None, :].repeat(b_sz, 0),
        axis=1)                                          # (B, sb)
    bs = jnp.take_along_axis(cand_sid, bi, axis=1)
    bo = jnp.take_along_axis(cand_off, bi, axis=1)
    # one (1, qlen) row slice per candidate: a flat take would make
    # XLA relayout the whole (8, 128)-tiled collection first
    wb = jax.vmap(jax.vmap(
        lambda s, o: jax.lax.dynamic_slice(data, (s, o), (1, qlen))[0]))(
            bs, jnp.clip(bo, 0, n - qlen))
    if znorm:
        # normalize EXACTLY as the LB tier did (kernel mu/sd) so
        # LB_Keogh <= DTW holds bitwise on survivors
        wb = ((wb - jnp.take_along_axis(mu, bi, 1)[..., None])
              / jnp.take_along_axis(sd, bi, 1)[..., None])
    db = jax.vmap(lambda q1, c: dtw.dtw_band(q1, c, r, squared=True))(
        qs, wb)
    return pos, bi, bs, bo, db


def _pool_merge(pool, cd2, csid, coff, k: int):
    """Merge (B, M) candidates into a (B, k) sorted pool.

    Keeps rows sorted by d2; incumbents win ties (they come first in
    the concatenation).  Shared by the local scan core and the sharded
    distributed scan (distributed/ulisse.py)."""
    pd2, psid, poff = pool
    alld = jnp.concatenate([pd2, cd2], axis=1)
    alls = jnp.concatenate([psid, csid], axis=1)
    allo = jnp.concatenate([poff, coff], axis=1)
    neg, sel = jax.lax.top_k(-alld, k)
    return (-neg, jnp.take_along_axis(alls, sel, axis=1),
            jnp.take_along_axis(allo, sel, axis=1))


def _chunk_heads(lbs2, chunk: int):
    """(B, n_chunks) squared lower bounds heading each chunk of the
    packed plan — the LB-sorted order makes each one its chunk's (and
    every later chunk's) best case, so it alone decides the scan's
    stop/skip tests.  Taken once, outside the scan loop: slicing a
    (B, 1) column out of the (B, n_pad) plan every trip makes XLA lay
    the whole plan out column-major, padding B to 128 lanes."""
    return lbs2[:, ::chunk]


def _first_lb2(heads, i):
    """Chunk i's head from `_chunk_heads`; callers mask i >= n_chunks."""
    return jax.lax.dynamic_slice_in_dim(
        heads, jnp.minimum(i, heads.shape[1] - 1), 1, axis=1)[:, 0]


def _scan_chunk_step(data, csum, csum2, cslo, cs2lo, center, sids,
                     anchors, n_master, lbs2, qs, dtw_lo, dtw_hi, i,
                     pool, kth, active, *, k: int, g: int, chunk: int,
                     znorm: bool, measure: str, r: int, sb: int,
                     interpret: bool, gsids=None):
    """Verify chunk `i` of the packed plan into the (B, k) pool.

    THE shared k-NN chunk step: the local device scan
    (`_device_scan_core`), the sharded distributed scan
    (`distributed/ulisse._sharded_knn_scan`) and the paged chunk
    program (`_paged_scan_chunk_core`) all run their loops over this
    function — the only differences between the three are the `kth`
    cut the caller prunes with (the pool's own kth locally; the min of
    the local kth and the mesh-wide broadcast bsf on a sharded scan)
    and, for the paged caller, `gsids`: (B, n_pad) GLOBAL series ids
    reported in the pool when `sids` are slab-local gather rows (None
    = sids are already global, the whole-resident case).

    Returns (pool, dstats) where dstats (B, STATS_WIDTH) holds the
    per-query increments of [chunks, envelopes_checked, true_dists,
    lb_keogh, dtw_full, envelopes_pruned].
    """
    n = data.shape[1]
    b_sz, qlen = qs.shape
    zeros = jnp.zeros((b_sz,), jnp.int32)
    csid, canc, cnm, clb2 = _chunk_slice(sids, anchors, n_master,
                                         lbs2, i, chunk)
    keep = (clb2 < kth[:, None]) & active[:, None]  # bsf pruning
    ok, cand_sid, cand_off = _chunk_candidates(csid, canc, cnm,
                                               keep, qlen, n, g)
    if gsids is None:
        cand_code = cand_sid
    else:
        cgsid = jax.lax.dynamic_slice_in_dim(gsids, i * chunk, chunk, 1)
        cand_code = jnp.repeat(cgsid, g, axis=1)
    checked = jnp.sum(keep, axis=1, dtype=jnp.int32)
    # envelopes cut by the bsf LB test in this visited chunk (padding
    # rows carry lbs2 = +inf and are excluded by the isfinite test)
    pruned = jnp.sum(jnp.isfinite(clb2) & active[:, None] & ~keep,
                     axis=1, dtype=jnp.int32)
    tdist = nlbk = ndtw = zeros
    if measure == "ed":
        d2 = fused_gather_ed(data, csum, csum2, cslo, cs2lo, center,
                             csid.reshape(-1), canc.reshape(-1),
                             qs, g=g, rows=chunk, znorm=znorm,
                             interpret=interpret)
        d2 = jnp.where(ok, d2.reshape(b_sz, chunk * g), jnp.inf)
        pool = _pool_merge(pool, d2, cand_code, cand_off, k)
        tdist = jnp.sum(ok, axis=1, dtype=jnp.int32)
    else:
        lb2w, mu, sd = fused_gather_lb_keogh(
            data, csum, csum2, cslo, cs2lo, center,
            csid.reshape(-1), canc.reshape(-1), dtw_lo, dtw_hi,
            g=g, rows=chunk, znorm=znorm, interpret=interpret)
        lb2w = jnp.where(ok, lb2w.reshape(b_sz, chunk * g), jnp.inf)
        mu = mu.reshape(b_sz, chunk * g)
        sd = sd.reshape(b_sz, chunk * g)
        nlbk = jnp.sum(ok, axis=1, dtype=jnp.int32)
        # masked survivor buckets: pack LB survivors to the front,
        # run the banded DP bucket by bucket, stop when every
        # query's packed prefix is exhausted — static shapes,
        # data-dependent work
        surv = lb2w < kth[:, None]
        nsurv = jnp.sum(surv, axis=1, dtype=jnp.int32)
        sidx = _survivors_first(surv)

        def inner_body(st):
            j, ipool, indtw = st
            pos, bi, bs, bo, db = _survivor_bucket(
                data, qs, cand_sid, cand_off, sidx, mu, sd, j,
                sb=sb, r=r, znorm=znorm)
            if gsids is not None:
                bs = jnp.take_along_axis(cand_code, bi, axis=1)
            m = pos[None, :] < nsurv[:, None]
            ipool = _pool_merge(ipool, jnp.where(m, db, jnp.inf), bs,
                                bo, k)
            return (j + 1, ipool,
                    indtw + jnp.sum(m, axis=1, dtype=jnp.int32))

        _, pool, ndtw = jax.lax.while_loop(
            lambda st: jnp.any(st[0] * sb < nsurv), inner_body,
            (jnp.int32(0), pool, ndtw))
        tdist = nsurv
    return pool, jnp.stack([active.astype(jnp.int32), checked, tdist,
                            nlbk, ndtw, pruned], axis=1)


def _device_scan_core(data, csum, csum2, cslo, cs2lo, center, sids,
                      anchors, n_master, lbs2, qs, dtw_lo, dtw_hi,
                      seed_d2, seed_sid, seed_off, *, k: int, g: int,
                      chunk: int, znorm: bool, measure: str, r: int,
                      sb: int, interpret: bool):
    """The natively-batched LB-sorted bsf-pruned scan.

    All per-query arrays carry a leading batch axis B — the loop is NOT
    vmapped: every chunk step verifies the i-th chunk of all still-
    active queries through one fused-kernel launch (grid = B), so the
    batch vectorizes inside the program instead of replaying it per
    lane.  Queries whose scan has converged keep looping with their
    candidates masked to +inf (merge no-ops) until the whole batch is
    done — per-query early exit costs masked work, not host syncs.

    sids/anchors/n_master/lbs2 (B, n_pad) are each query's candidate
    envelopes in ascending lower-bound order, padded to a multiple of
    `chunk` (padding rows carry lbs2 = +inf).  seed_* (B, k) is the
    pool from the approximate pass (ascending d2, +inf filler) — seeded
    envelopes must already be excluded from the scan order, so the pool
    never sees a (sid, off) twice and needs no dedup.
    """
    b_sz = qs.shape[0]
    n_pad = sids.shape[1]
    n_chunks = n_pad // chunk

    heads = _chunk_heads(lbs2, chunk)

    def active_at(i, pool):
        first = _first_lb2(heads, i)
        return ((i < n_chunks) & jnp.isfinite(first)
                & (first < pool[0][:, k - 1]))

    def body(state):
        i, pool, stats = state
        active = active_at(i, pool)
        kth = pool[0][:, k - 1]
        pool, ds = _scan_chunk_step(
            data, csum, csum2, cslo, cs2lo, center, sids, anchors,
            n_master, lbs2, qs, dtw_lo, dtw_hi, i, pool, kth, active,
            k=k, g=g, chunk=chunk, znorm=znorm, measure=measure, r=r,
            sb=sb, interpret=interpret)
        return i + 1, pool, stats + ds

    def cond(state):
        return jnp.any(active_at(state[0], state[1]))

    state = (jnp.int32(0), (seed_d2, seed_sid, seed_off),
             jnp.zeros((b_sz, STATS_WIDTH), jnp.int32))
    _, pool, stats = jax.lax.while_loop(cond, body, state)
    return pool[0], pool[1], pool[2], stats


@functools.lru_cache(maxsize=None)
def _device_scan_program(k: int, g: int, chunk: int, znorm: bool,
                         measure: str, r: int, sb: int, interpret: bool):
    """Compiled batched scan for one static config (cached)."""
    core = functools.partial(_device_scan_core, k=k, g=g, chunk=chunk,
                             znorm=znorm, measure=measure, r=r, sb=sb,
                             interpret=interpret)
    return jax.jit(core)


def device_exact_scan(collection, sids, anchors, n_master, lbs2, qs,
                      dtw_lo, dtw_hi, seed_d2, seed_sid, seed_off, *,
                      k: int, g: int, measure: str, r: int, znorm: bool,
                      chunk_size: int, interpret: Optional[bool] = None):
    """Batched device-resident exact scan (no host sync — see engine).

    `collection` supplies the raw series plus the precomputed centered
    prefix sums the fused kernels derive window stats from.  All
    per-query arrays carry a leading batch axis B (B = 1 for a single
    query): sids/anchors/n_master/lbs2 are (B, n_pad) LB-sorted padded
    candidate rows (`planner.device_scan_pack` / `device_leaf_pack` for
    the approx stage), qs/dtw_lo/dtw_hi (B, qlen) prepared
    queries (for ED pass qs in the dtw slots — they are ignored),
    seed_* the (B, k) pools from the approximate pass.

    Returns DEVICE arrays (d2 (B, k) f32 ascending, sid/off (B, k)
    int32, stats (B, STATS_WIDTH) int32 = [chunks, envelopes_checked,
    true_dists, lb_keogh, dtw_full, envelopes_pruned]); the caller
    performs the one host readback (`jax.device_get`) for the whole
    batch.
    """
    if interpret is None:
        interpret = default_interpret()
    n_pad = sids.shape[1]
    chunk = min(pow2ceil(chunk_size), n_pad)
    sb = min(128, chunk * g)
    fn = _device_scan_program(k, g, chunk, znorm, measure, r, sb,
                              interpret)
    return fn(
        collection.data, collection.csum, collection.csum2,
        collection.csum_lo, collection.csum2_lo, collection.center,
        jnp.asarray(sids, jnp.int32), jnp.asarray(anchors, jnp.int32),
        jnp.asarray(n_master, jnp.int32), jnp.asarray(lbs2, jnp.float32),
        jnp.asarray(qs, jnp.float32), jnp.asarray(dtw_lo, jnp.float32),
        jnp.asarray(dtw_hi, jnp.float32), jnp.asarray(seed_d2, jnp.float32),
        jnp.asarray(seed_sid, jnp.int32), jnp.asarray(seed_off, jnp.int32))


# --------------------------------------------------------------------------
# device-resident eps-range scan (paper Alg. 5 with bsf := eps, ONE program)
# --------------------------------------------------------------------------
#
# Unlike the k-NN pool, a range query's result size is data-dependent: the
# scan carries a fixed-capacity (B, cap) hit buffer of (d2, sid, off) rows
# through the while_loop and appends every verified candidate with
# d2 <= eps2.  The pruning cut is INCLUSIVE (lb2 <= eps2): lb <= d, so a
# boundary hit with lb == d == eps survives every tier (the PR 3 DTW
# regression, now structural).  Overflow protocol: if a chunk's hits would
# exceed the remaining capacity, NONE of that chunk's hits are written,
# the chunk index is recorded, and the query goes inactive — the buffer
# then holds exactly the hits of chunks [0, ovf), and the host finishes
# chunks [ovf, n_chunks) through the reference path (DESIGN.md §9).

def _device_range_core(data, csum, csum2, cslo, cs2lo, center, sids,
                       anchors, n_master, lbs2, qs, dtw_lo, dtw_hi,
                       eps2, *, cap: int, g: int, chunk: int,
                       znorm: bool, measure: str, r: int, sb: int,
                       interpret: bool):
    """The natively-batched LB-sorted eps-range scan.

    Layout as in _device_scan_core: per-query candidate rows (B, n_pad)
    in ascending lower-bound order, chunk-padded with lbs2 = +inf;
    eps2 (B,) squared radii.  Returns (buf_d2 (B, cap), buf_sid,
    buf_off, cnt (B,), ovf (B,) — the first unwritten chunk index, or
    n_chunks when the buffer never overflowed — and the stats stack).
    """
    n = data.shape[1]
    b_sz, qlen = qs.shape
    n_pad = sids.shape[1]
    n_chunks = n_pad // chunk
    no_ovf = jnp.int32(n_chunks)
    rows_idx = jnp.arange(b_sz)[:, None]

    heads = _chunk_heads(lbs2, chunk)

    def active_at(i, ovf):
        first = _first_lb2(heads, i)
        return ((i < n_chunks) & jnp.isfinite(first)
                & (first <= eps2) & (ovf == no_ovf))

    def body(state):
        (i, bd2, bsid, boff, cnt, ovf, nchunks, checked, tdist, nlbk,
         ndtw, npruned) = state
        active = active_at(i, ovf)
        nchunks = nchunks + active.astype(jnp.int32)
        csid, canc, cnm, clb2 = _chunk_slice(sids, anchors, n_master,
                                             lbs2, i, chunk)
        keep = (clb2 <= eps2[:, None]) & active[:, None]   # INCLUSIVE
        ok, cand_sid, cand_off = _chunk_candidates(csid, canc, cnm,
                                                   keep, qlen, n, g)
        checked = checked + jnp.sum(keep, axis=1, dtype=jnp.int32)
        npruned = npruned + jnp.sum(
            jnp.isfinite(clb2) & active[:, None] & ~keep,
            axis=1, dtype=jnp.int32)
        if measure == "ed":
            d2 = fused_gather_ed(data, csum, csum2, cslo, cs2lo, center,
                                 csid.reshape(-1), canc.reshape(-1),
                                 qs, g=g, rows=chunk, znorm=znorm,
                                 interpret=interpret)
            d2 = jnp.where(ok, d2.reshape(b_sz, chunk * g), jnp.inf)
            tdist = tdist + jnp.sum(ok, axis=1, dtype=jnp.int32)
        else:
            lb2w, mu, sd = fused_gather_lb_keogh(
                data, csum, csum2, cslo, cs2lo, center,
                csid.reshape(-1), canc.reshape(-1), dtw_lo, dtw_hi,
                g=g, rows=chunk, znorm=znorm, interpret=interpret)
            lb2w = jnp.where(ok, lb2w.reshape(b_sz, chunk * g), jnp.inf)
            mu = mu.reshape(b_sz, chunk * g)
            sd = sd.reshape(b_sz, chunk * g)
            nlbk = nlbk + jnp.sum(ok, axis=1, dtype=jnp.int32)
            surv = lb2w <= eps2[:, None]                   # INCLUSIVE
            nsurv = jnp.sum(surv, axis=1, dtype=jnp.int32)
            sidx = _survivors_first(surv)

            def inner_body(st):
                j, d2acc, indtw = st
                pos, bi, _, _, db = _survivor_bucket(
                    data, qs, cand_sid, cand_off, sidx, mu, sd, j,
                    sb=sb, r=r, znorm=znorm)
                m = pos[None, :] < nsurv[:, None]
                # scatter-min: clamped duplicate positions past nsurv
                # carry +inf, so they can never clobber a real distance
                d2acc = d2acc.at[rows_idx, bi].min(
                    jnp.where(m, db, jnp.inf), mode="drop")
                return (j + 1, d2acc,
                        indtw + jnp.sum(m, axis=1, dtype=jnp.int32))

            d2 = jnp.full((b_sz, chunk * g), jnp.inf, jnp.float32)
            _, d2, ndtw = jax.lax.while_loop(
                lambda st: jnp.any(st[0] * sb < nsurv), inner_body,
                (jnp.int32(0), d2, ndtw))
            tdist = tdist + nsurv
        hit = ok & (d2 <= eps2[:, None])
        nh = jnp.sum(hit, axis=1, dtype=jnp.int32)
        ovf_now = active & (cnt + nh > cap)
        # gather-based append (XLA CPU lowers scatter to a serial loop —
        # ~7x the whole chunk's kernel time): buffer slot j receives the
        # (j - cnt + 1)-th hit, located by binary search over the hit
        # cumsum — searchsorted(hc, r) is the first index where hc
        # reaches r, which is exactly the r-th hit's position
        hc = jnp.cumsum(hit, axis=1)
        ranks = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                 - cnt[:, None] + 1)
        src = jax.vmap(jnp.searchsorted)(hc, ranks)
        src = jnp.minimum(src, hit.shape[1] - 1)
        write = ((ranks >= 1) & (ranks <= nh[:, None])
                 & ~ovf_now[:, None] & active[:, None])
        bd2 = jnp.where(
            write,
            jnp.take_along_axis(d2, src, 1).astype(jnp.float32), bd2)
        bsid = jnp.where(write, jnp.take_along_axis(cand_sid, src, 1),
                         bsid)
        boff = jnp.where(write, jnp.take_along_axis(cand_off, src, 1),
                         boff)
        cnt = jnp.where(ovf_now, cnt, cnt + nh)
        ovf = jnp.where(ovf_now & (ovf == no_ovf), i, ovf)
        return (i + 1, bd2, bsid, boff, cnt, ovf, nchunks, checked,
                tdist, nlbk, ndtw, npruned)

    def cond(state):
        return jnp.any(active_at(state[0], state[5]))

    zeros = jnp.zeros((b_sz,), jnp.int32)
    state = (jnp.int32(0),
             jnp.full((b_sz, cap), jnp.inf, jnp.float32),
             jnp.full((b_sz, cap), -1, jnp.int32),
             jnp.full((b_sz, cap), -1, jnp.int32),
             zeros, jnp.full((b_sz,), no_ovf, jnp.int32),
             zeros, zeros, zeros, zeros, zeros, zeros)
    (_, bd2, bsid, boff, cnt, ovf, nchunks, checked, tdist, nlbk,
     ndtw, npruned) = jax.lax.while_loop(cond, body, state)
    return bd2, bsid, boff, cnt, ovf, jnp.stack(
        [nchunks, checked, tdist, nlbk, ndtw, npruned], axis=1)


@functools.lru_cache(maxsize=None)
def _device_range_program(cap: int, g: int, chunk: int, znorm: bool,
                          measure: str, r: int, sb: int,
                          interpret: bool):
    core = functools.partial(_device_range_core, cap=cap, g=g,
                             chunk=chunk, znorm=znorm, measure=measure,
                             r=r, sb=sb, interpret=interpret)
    return jax.jit(core)


def device_range_scan(collection, sids, anchors, n_master, lbs2, qs,
                      dtw_lo, dtw_hi, eps2, *, capacity: int, g: int,
                      measure: str, r: int, znorm: bool,
                      chunk_size: int, interpret: Optional[bool] = None):
    """Batched device eps-range scan (no host sync — see engine).

    Returns (buf_d2 (B, cap) f32, buf_sid/buf_off (B, cap) int32,
    cnt (B,), ovf_chunk (B,), stats (B, STATS_WIDTH), chunk) — device
    arrays plus
    the static chunk size the scan actually used: `ovf_chunk` counts in
    units of `chunk` rows of the packed plan, and the host continuation
    of an overflowed query must resume at row `ovf_chunk * chunk` —
    returning it keeps the engine from re-deriving (and drifting from)
    the internal chunking.  ovf_chunk == n_pad // chunk means the
    buffer held everything.
    """
    if interpret is None:
        interpret = default_interpret()
    n_pad = sids.shape[1]
    chunk = min(pow2ceil(chunk_size), n_pad)
    sb = min(128, chunk * g)
    fn = _device_range_program(pow2ceil(capacity), g, chunk, znorm,
                               measure, r, sb, interpret)
    return fn(
        collection.data, collection.csum, collection.csum2,
        collection.csum_lo, collection.csum2_lo, collection.center,
        jnp.asarray(sids, jnp.int32), jnp.asarray(anchors, jnp.int32),
        jnp.asarray(n_master, jnp.int32), jnp.asarray(lbs2, jnp.float32),
        jnp.asarray(qs, jnp.float32), jnp.asarray(dtw_lo, jnp.float32),
        jnp.asarray(dtw_hi, jnp.float32),
        jnp.asarray(eps2, jnp.float32)) + (chunk,)


# --------------------------------------------------------------------------
# paged out-of-core scan (host-driven chunk loop over a PayloadStore)
# --------------------------------------------------------------------------
#
# The drivers below run the SAME chunk step as the monolithic while_loop
# programs, but host-driven: each LB-sorted plan chunk is verified by a
# one-chunk jitted program against a "slab" — the sorted-unique series
# rows that chunk actually touches, gathered from the store's LRU page
# cache and device_put fresh per chunk.  The plan's candidate sids are
# remapped slab-local for the gather kernels; the GLOBAL ids travel
# alongside (`gsids` in _scan_chunk_step) so pools/hit buffers report
# real series ids.  Answers are bit-equal to the whole-resident scan:
# the chunk step is shared code, per-page prefix sums are row-wise
# identical to the whole-collection ones (types.host_prefix_stats is
# the single implementation), and the host loop only ever runs EXTRA
# chunks past the monolithic cond's stop point — which are masked
# no-ops with zero stats (active=False => keep=False => every merge
# and every write is a no-op).
#
# Double-buffered prefetch: a one-worker ThreadPoolExecutor assembles
# and device_puts slab t+1 (page faults + prefix sums + gathers, all
# GIL-releasing numpy) while chunk t's asynchronously-dispatched
# program computes.  `prefetch=False` degrades to synchronous
# load-then-scan (the benchmark baseline).  Early stop is host-checked
# every `sync_every` chunks from the plan's chunk-head bounds plus one
# planned kth/ovf readback — these readbacks are budgeted in
# analysis_baseline.json (rule R2).

PAGED_SYNC_EVERY = 8


def _gather_slab(store, uniq: np.ndarray, row_pad: int):
    """Gather the six kernel planes for the sorted-unique global series
    ids `uniq` out of the store's page cache, zero-padded to `row_pad`
    rows (pow2 — bounds the one-chunk program's retrace count)."""
    n = store.series_len
    shape1 = (row_pad, n + 1)
    data = np.zeros((row_pad, n), np.float32)
    csum = np.zeros(shape1, np.float32)
    csum2 = np.zeros(shape1, np.float32)
    cslo = np.zeros(shape1, np.float32)
    cs2lo = np.zeros(shape1, np.float32)
    center = np.zeros((row_pad,), np.float32)
    pages = uniq // store.page_rows
    for p in np.unique(pages):
        blk = store.load_page(int(p))
        pos = np.flatnonzero(pages == p)
        idx = uniq[pos] - blk.start
        data[pos] = blk.data[idx]
        csum[pos] = blk.csum[idx]
        csum2[pos] = blk.csum2[idx]
        cslo[pos] = blk.csum_lo[idx]
        cs2lo[pos] = blk.csum2_lo[idx]
        center[pos] = blk.center[idx]
    return data, csum, csum2, cslo, cs2lo, center


def _make_chunk_slab(store, sids, anchors, n_master, lbs2, i, chunk: int):
    """Assemble + device_put chunk i's slab and its slab-local plan.

    Runs on the prefetch worker thread: every step here is either
    GIL-releasing numpy or a host->device transfer, so it overlaps the
    previous chunk's in-flight program."""
    from repro.core.planner import chunk_pages
    sl = slice(i * chunk, (i + 1) * chunk)
    uniq, local, _ = chunk_pages(sids, i, chunk, store.page_rows)
    row_pad = pow2ceil(max(int(uniq.shape[0]), 1))
    planes = _gather_slab(store, uniq, row_pad)
    return jax.device_put(planes + (
        local,
        np.ascontiguousarray(anchors[:, sl], np.int32),
        np.ascontiguousarray(n_master[:, sl], np.int32),
        np.ascontiguousarray(lbs2[:, sl], np.float32),
        np.ascontiguousarray(sids[:, sl], np.int32)))


def _paged_scan_chunk_core(data, csum, csum2, cslo, cs2lo, center,
                           csid, canc, cnm, clb2, cgsid, qs, dtw_lo,
                           dtw_hi, pd2, psid, poff, *, k: int, g: int,
                           chunk: int, znorm: bool, measure: str,
                           r: int, sb: int, interpret: bool):
    """One k-NN chunk of the paged scan: exactly one monolithic
    while_loop body iteration, with the plan pre-sliced to (B, chunk)
    and candidate sids slab-local (cgsid carries the global ids)."""
    kth = pd2[:, k - 1]
    active = jnp.isfinite(clb2[:, 0]) & (clb2[:, 0] < kth)
    pool, ds = _scan_chunk_step(
        data, csum, csum2, cslo, cs2lo, center, csid, canc, cnm, clb2,
        qs, dtw_lo, dtw_hi, jnp.int32(0), (pd2, psid, poff), kth,
        active, k=k, g=g, chunk=chunk, znorm=znorm, measure=measure,
        r=r, sb=sb, interpret=interpret, gsids=cgsid)
    return pool[0], pool[1], pool[2], ds


@functools.lru_cache(maxsize=None)
def _paged_scan_chunk_program(k: int, g: int, chunk: int, znorm: bool,
                              measure: str, r: int, sb: int,
                              interpret: bool):
    core = functools.partial(_paged_scan_chunk_core, k=k, g=g,
                             chunk=chunk, znorm=znorm, measure=measure,
                             r=r, sb=sb, interpret=interpret)
    return jax.jit(core)


def paged_exact_scan(store, sids, anchors, n_master, lbs2, qs, dtw_lo,
                     dtw_hi, seed_d2, seed_sid, seed_off, *, k: int,
                     g: int, measure: str, r: int, znorm: bool,
                     chunk_size: int, prefetch: bool = True,
                     sync_every: int = PAGED_SYNC_EVERY,
                     interpret: Optional[bool] = None):
    """Out-of-core twin of `device_exact_scan` over a PayloadStore.

    Plan arrays are HOST numpy here (the engine reads the device pack
    back once — a planned transfer); returns the same device 4-tuple
    as `device_exact_scan` so the engine's single batch readback is
    unchanged.
    """
    if interpret is None:
        interpret = default_interpret()
    sids = np.asarray(sids)
    anchors = np.asarray(anchors)
    n_master = np.asarray(n_master)
    lbs2 = np.asarray(lbs2)
    n_pad = sids.shape[1]
    chunk = min(pow2ceil(chunk_size), n_pad)
    sb = min(128, chunk * g)
    n_chunks = n_pad // chunk
    first_np = lbs2[:, ::chunk]                  # (B, n_chunks) chunk heads
    qs_d = jnp.asarray(qs, jnp.float32)
    lo_d = jnp.asarray(dtw_lo, jnp.float32)
    hi_d = jnp.asarray(dtw_hi, jnp.float32)
    pool = (jnp.asarray(seed_d2, jnp.float32),
            jnp.asarray(seed_sid, jnp.int32),
            jnp.asarray(seed_off, jnp.int32))
    b_sz = qs_d.shape[0]
    stats = jnp.zeros((b_sz, STATS_WIDTH), jnp.int32)
    program = _paged_scan_chunk_program(k, g, chunk, znorm, measure, r,
                                        sb, interpret)
    from repro.obs import span                   # obs imports executor

    def run_chunk(slab, pool, stats):
        (data, csum, csum2, cslo, cs2lo, center, local, canc, cnm,
         clb2, cgsid) = slab
        pd2, psid, poff, ds = program(
            data, csum, csum2, cslo, cs2lo, center, local, canc, cnm,
            clb2, cgsid, qs_d, lo_d, hi_d, *pool)
        return (pd2, psid, poff), stats + ds

    def converged(i):
        # the monolithic cond at chunk i: LB-sorted heads are
        # nondecreasing and kth only shrinks, so a False here is final
        kth = np.asarray(jax.device_get(pool[0][:, k - 1]))
        nf = first_np[:, i]
        return not np.any(np.isfinite(nf) & (nf < kth))

    if prefetch and n_chunks > 1:
        with ThreadPoolExecutor(max_workers=1) as ex:
            fut = ex.submit(_make_chunk_slab, store, sids, anchors,
                            n_master, lbs2, 0, chunk)
            for i in range(n_chunks):
                with span("page.prefetch", chunk=i):
                    slab = fut.result()
                if i + 1 < n_chunks:
                    fut = ex.submit(_make_chunk_slab, store, sids,
                                    anchors, n_master, lbs2, i + 1,
                                    chunk)
                pool, stats = run_chunk(slab, pool, stats)
                if i + 1 < n_chunks and (i + 1) % sync_every == 0 \
                        and converged(i + 1):
                    fut.cancel()
                    break
    else:
        for i in range(n_chunks):
            with span("page.prefetch", chunk=i):
                slab = _make_chunk_slab(store, sids, anchors, n_master,
                                        lbs2, i, chunk)
            pool, stats = run_chunk(slab, pool, stats)
            jax.block_until_ready(pool[0])       # no overlap: baseline
            if i + 1 < n_chunks and (i + 1) % sync_every == 0 \
                    and converged(i + 1):
                break
    return pool[0], pool[1], pool[2], stats


def _paged_range_chunk_core(data, csum, csum2, cslo, cs2lo, center,
                            csid, canc, cnm, clb2, cgsid, qs, dtw_lo,
                            dtw_hi, eps2, bd2, bsid, boff, cnt, ovf,
                            i_code, no_ovf, *, cap: int, g: int,
                            chunk: int, znorm: bool, measure: str,
                            r: int, sb: int, interpret: bool):
    """One eps-range chunk of the paged scan: one monolithic
    `_device_range_core` body iteration over a pre-sliced (B, chunk)
    plan with slab-local sids.  `i_code`/`no_ovf` are the global chunk
    index and the no-overflow sentinel (traced scalars — the overflow
    protocol records GLOBAL chunk indices so the host continuation
    resumes at the right plan row)."""
    n = data.shape[1]
    b_sz, qlen = qs.shape
    zeros = jnp.zeros((b_sz,), jnp.int32)
    rows_idx = jnp.arange(b_sz)[:, None]
    first = clb2[:, 0]
    active = jnp.isfinite(first) & (first <= eps2) & (ovf == no_ovf)
    nchunks = active.astype(jnp.int32)
    keep = (clb2 <= eps2[:, None]) & active[:, None]       # INCLUSIVE
    ok, cand_sid, cand_off = _chunk_candidates(csid, canc, cnm, keep,
                                               qlen, n, g)
    cand_code = jnp.repeat(cgsid, g, axis=1)
    checked = jnp.sum(keep, axis=1, dtype=jnp.int32)
    npruned = jnp.sum(jnp.isfinite(clb2) & active[:, None] & ~keep,
                      axis=1, dtype=jnp.int32)
    tdist = nlbk = ndtw = zeros
    if measure == "ed":
        d2 = fused_gather_ed(data, csum, csum2, cslo, cs2lo, center,
                             csid.reshape(-1), canc.reshape(-1),
                             qs, g=g, rows=chunk, znorm=znorm,
                             interpret=interpret)
        d2 = jnp.where(ok, d2.reshape(b_sz, chunk * g), jnp.inf)
        tdist = jnp.sum(ok, axis=1, dtype=jnp.int32)
    else:
        lb2w, mu, sd = fused_gather_lb_keogh(
            data, csum, csum2, cslo, cs2lo, center,
            csid.reshape(-1), canc.reshape(-1), dtw_lo, dtw_hi,
            g=g, rows=chunk, znorm=znorm, interpret=interpret)
        lb2w = jnp.where(ok, lb2w.reshape(b_sz, chunk * g), jnp.inf)
        mu = mu.reshape(b_sz, chunk * g)
        sd = sd.reshape(b_sz, chunk * g)
        nlbk = jnp.sum(ok, axis=1, dtype=jnp.int32)
        surv = lb2w <= eps2[:, None]                       # INCLUSIVE
        nsurv = jnp.sum(surv, axis=1, dtype=jnp.int32)
        sidx = _survivors_first(surv)

        def inner_body(st):
            j, d2acc, indtw = st
            pos, bi, _, _, db = _survivor_bucket(
                data, qs, cand_sid, cand_off, sidx, mu, sd, j,
                sb=sb, r=r, znorm=znorm)
            m = pos[None, :] < nsurv[:, None]
            d2acc = d2acc.at[rows_idx, bi].min(
                jnp.where(m, db, jnp.inf), mode="drop")
            return (j + 1, d2acc,
                    indtw + jnp.sum(m, axis=1, dtype=jnp.int32))

        d2 = jnp.full((b_sz, chunk * g), jnp.inf, jnp.float32)
        _, d2, ndtw = jax.lax.while_loop(
            lambda st: jnp.any(st[0] * sb < nsurv), inner_body,
            (jnp.int32(0), d2, ndtw))
        tdist = nsurv
    hit = ok & (d2 <= eps2[:, None])
    nh = jnp.sum(hit, axis=1, dtype=jnp.int32)
    ovf_now = active & (cnt + nh > cap)
    hc = jnp.cumsum(hit, axis=1)
    ranks = (jnp.arange(cap, dtype=jnp.int32)[None, :]
             - cnt[:, None] + 1)
    src = jax.vmap(jnp.searchsorted)(hc, ranks)
    src = jnp.minimum(src, hit.shape[1] - 1)
    write = ((ranks >= 1) & (ranks <= nh[:, None])
             & ~ovf_now[:, None] & active[:, None])
    bd2 = jnp.where(
        write, jnp.take_along_axis(d2, src, 1).astype(jnp.float32), bd2)
    bsid = jnp.where(write, jnp.take_along_axis(cand_code, src, 1), bsid)
    boff = jnp.where(write, jnp.take_along_axis(cand_off, src, 1), boff)
    cnt = jnp.where(ovf_now, cnt, cnt + nh)
    ovf = jnp.where(ovf_now & (ovf == no_ovf), i_code, ovf)
    return bd2, bsid, boff, cnt, ovf, jnp.stack(
        [nchunks, checked, tdist, nlbk, ndtw, npruned], axis=1)


@functools.lru_cache(maxsize=None)
def _paged_range_chunk_program(cap: int, g: int, chunk: int,
                               znorm: bool, measure: str, r: int,
                               sb: int, interpret: bool):
    core = functools.partial(_paged_range_chunk_core, cap=cap, g=g,
                             chunk=chunk, znorm=znorm, measure=measure,
                             r=r, sb=sb, interpret=interpret)
    return jax.jit(core)


def paged_range_scan(store, sids, anchors, n_master, lbs2, qs, dtw_lo,
                     dtw_hi, eps2, *, capacity: int, g: int,
                     measure: str, r: int, znorm: bool, chunk_size: int,
                     prefetch: bool = True,
                     sync_every: int = PAGED_SYNC_EVERY,
                     interpret: Optional[bool] = None):
    """Out-of-core twin of `device_range_scan` over a PayloadStore.

    Same return contract (device buffers + cnt/ovf/stats + the static
    chunk size); `ovf` records GLOBAL plan chunk indices, so the
    engine's host continuation of an overflowed query is unchanged.
    """
    if interpret is None:
        interpret = default_interpret()
    sids = np.asarray(sids)
    anchors = np.asarray(anchors)
    n_master = np.asarray(n_master)
    lbs2 = np.asarray(lbs2)
    eps2_np = np.asarray(eps2, np.float32)
    n_pad = sids.shape[1]
    chunk = min(pow2ceil(chunk_size), n_pad)
    sb = min(128, chunk * g)
    cap = pow2ceil(capacity)
    n_chunks = n_pad // chunk
    first_np = lbs2[:, ::chunk]
    b_sz = eps2_np.shape[0]
    qs_d = jnp.asarray(qs, jnp.float32)
    lo_d = jnp.asarray(dtw_lo, jnp.float32)
    hi_d = jnp.asarray(dtw_hi, jnp.float32)
    eps2_d = jnp.asarray(eps2_np)
    zeros = jnp.zeros((b_sz,), jnp.int32)
    bd2 = jnp.full((b_sz, cap), jnp.inf, jnp.float32)
    bsid = jnp.full((b_sz, cap), -1, jnp.int32)
    boff = jnp.full((b_sz, cap), -1, jnp.int32)
    cnt = zeros
    ovf = jnp.full((b_sz,), n_chunks, jnp.int32)
    stats = jnp.zeros((b_sz, STATS_WIDTH), jnp.int32)
    no_ovf = np.int32(n_chunks)
    program = _paged_range_chunk_program(cap, g, chunk, znorm, measure,
                                         r, sb, interpret)
    from repro.obs import span                   # obs imports executor

    def run_chunk(slab, i, st):
        bd2, bsid, boff, cnt, ovf, stats = st
        (data, csum, csum2, cslo, cs2lo, center, local, canc, cnm,
         clb2, cgsid) = slab
        bd2, bsid, boff, cnt, ovf, ds = program(
            data, csum, csum2, cslo, cs2lo, center, local, canc, cnm,
            clb2, cgsid, qs_d, lo_d, hi_d, eps2_d, bd2, bsid, boff,
            cnt, ovf, np.int32(i), no_ovf)
        return bd2, bsid, boff, cnt, ovf, stats + ds

    def converged(i, st):
        # lb/eps half of the monolithic cond is host-known from the
        # packed chunk heads; the overflow half needs the one readback
        nf = first_np[:, i]
        live = np.isfinite(nf) & (nf <= eps2_np)
        if not np.any(live):
            return True
        ovf_np = np.asarray(jax.device_get(st[4]))
        return not np.any(live & (ovf_np == n_chunks))

    st = (bd2, bsid, boff, cnt, ovf, stats)
    if prefetch and n_chunks > 1:
        with ThreadPoolExecutor(max_workers=1) as ex:
            fut = ex.submit(_make_chunk_slab, store, sids, anchors,
                            n_master, lbs2, 0, chunk)
            for i in range(n_chunks):
                with span("page.prefetch", chunk=i):
                    slab = fut.result()
                if i + 1 < n_chunks:
                    fut = ex.submit(_make_chunk_slab, store, sids,
                                    anchors, n_master, lbs2, i + 1,
                                    chunk)
                st = run_chunk(slab, i, st)
                if i + 1 < n_chunks and (i + 1) % sync_every == 0 \
                        and converged(i + 1, st):
                    fut.cancel()
                    break
    else:
        for i in range(n_chunks):
            with span("page.prefetch", chunk=i):
                slab = _make_chunk_slab(store, sids, anchors, n_master,
                                        lbs2, i, chunk)
            st = run_chunk(slab, i, st)
            jax.block_until_ready(st[0])         # no overlap: baseline
            if i + 1 < n_chunks and (i + 1) % sync_every == 0 \
                    and converged(i + 1, st):
                break
    return st + (chunk,)
