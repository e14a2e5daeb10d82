"""Query planning for ULISSE search (the *planner* half of the engine).

A plan is everything derivable from (query, index params) before any raw
data is touched: the (possibly Z-normalized) query, its PAA interval
(degenerate for ED, [PAA(L_dtw), PAA(U_dtw)] for DTW — paper Alg. 4
lines 1-2), and lower-bound orderings over blocks / envelopes.  Both the
host-driven local backend and the shard_map distributed backend consume
these primitives; the *executor* half (executor.py) owns everything that
reads raw series data.

Two flavors coexist:

  * static-shape planning (`prepare_query`, `env_lower_bounds`,
    `block_lower_bounds`) — host-driven search, one trace per qlen;
  * masked planning (`masked_prepare`) — traced qlen over a padded
    length bucket, used by the batched distributed programs so one
    compiled executable serves every query length in the bucket.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bounds, dtw
from repro.core.paa import masked_znormalize, paa, znormalize
from repro.core.types import EnvelopeParams


# --------------------------------------------------------------------------
# static-shape query preparation (host-driven local backend)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PreparedQuery:
    """Everything derived from Q once per query (paper Alg. 4 lines 1-2)."""

    q: jnp.ndarray            # (possibly Z-normalized) query values (l,)
    qlen: int
    nseg: int                 # floor(|Q| / s)
    paa_lo: jnp.ndarray       # (w,) query interval in PAA space
    paa_hi: jnp.ndarray
    dtw_lo: Optional[jnp.ndarray] = None   # (l,) dtwENV for LB_Keogh
    dtw_hi: Optional[jnp.ndarray] = None
    measure: str = "ed"
    r: int = 0


def prepare_query(q, p: EnvelopeParams, measure: str = "ed",
                  r: int = 0) -> PreparedQuery:
    q = jnp.asarray(q, jnp.float32)
    qlen = int(q.shape[-1])
    nseg = p.query_segments(qlen)
    qn = znormalize(q) if p.znorm else q
    if measure == "ed":
        qp = paa(qn, p.seg_len)
        return PreparedQuery(q=qn, qlen=qlen, nseg=nseg, paa_lo=qp, paa_hi=qp,
                             measure="ed")
    elif measure == "dtw":
        if r <= 0:
            raise ValueError("DTW search needs a warping window r > 0")
        dlo, dhi = dtw.dtw_envelope(qn, r)
        return PreparedQuery(
            q=qn, qlen=qlen, nseg=nseg,
            paa_lo=paa(dlo, p.seg_len), paa_hi=paa(dhi, p.seg_len),
            dtw_lo=dlo, dtw_hi=dhi, measure="dtw", r=r)
    raise ValueError(f"unknown measure {measure!r}")


@partial(jax.jit, static_argnames=("seg_len", "znorm", "measure", "r"))
def prepare_query_batch(q: jnp.ndarray, seg_len: int, znorm: bool,
                        measure: str, r: int):
    """prepare_query for a (B, qlen) same-length batch, ONE jitted call.

    The one-sync device pipeline preps whole length groups at once —
    per-query eager znormalize/paa dispatch used to cost more than the
    verification itself.  Returns (qn, dtw_lo, dtw_hi, paa_lo, paa_hi),
    each (B, ...); for ED the dtw slots alias qn (ignored downstream).
    """
    qn = znormalize(q) if znorm else q
    if measure == "ed":
        qp = paa(qn, seg_len)
        return qn, qn, qn, qp, qp
    dlo, dhi = dtw.dtw_envelope(qn, r)
    return qn, dlo, dhi, paa(dlo, seg_len), paa(dhi, seg_len)


# --------------------------------------------------------------------------
# per-request admission planning (host, cheap — the serving tier's half)
# --------------------------------------------------------------------------

def length_bucket(qlen: int, cap: int) -> int:
    """The pow2 length bucket (capped at `cap`, normally lmax).

    This is the compiled-program routing key shared by the engine's
    distributed batch path and the serving tier's request queues: two
    queries land in the same bucket iff they can share one padded
    device program, so coalescing by bucket is coalescing by program.
    """
    return min(1 << max(qlen - 1, 0).bit_length(), cap)


def admit_query(q, p: EnvelopeParams) -> Tuple[np.ndarray, int]:
    """Admission-time planning for one request: validate + route.

    Everything that can be decided per request WITHOUT touching the
    index or a device happens here, on the submitting thread — dtype
    coercion, shape/finiteness checks, the length-range check, and the
    pow2 bucket assignment.  Malformed requests are rejected at the
    door with ValueError instead of poisoning a whole dispatched batch;
    execution (device, batched, per bucket) never sees them.

    Returns (query as float32 ndarray, bucket).
    """
    arr = np.asarray(q, np.float32)
    if arr.ndim != 1:
        raise ValueError(
            f"a request is one 1-D query (got shape {arr.shape}); "
            "submit batch members individually — the serving tier does "
            "the batching")
    if arr.size == 0 or not np.all(np.isfinite(arr)):
        raise ValueError("query values must be finite and non-empty")
    if not (p.lmin <= arr.size <= p.lmax):
        raise ValueError(
            f"query length {arr.size} outside the index's "
            f"[{p.lmin}, {p.lmax}]")
    return arr, length_bucket(arr.size, p.lmax)


# --------------------------------------------------------------------------
# jitted lower-bound kernels
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("seg_len", "nseg"))
def env_lower_bounds(paa_lo, paa_hi, e_lo, e_hi, valid,
                     seg_len: int, nseg: int):
    """Lower bounds to every envelope (Eq. 5 / Eq. 8 unified).

    `e_lo`/`e_hi` (N, w) are the envelopes' intervals as
    `UlisseIndex.lb_intervals` holds them; `valid` (N,) masks padding.
    """
    d = bounds.interval_mindist(paa_lo, paa_hi, e_lo, e_hi, seg_len, nseg)
    return jnp.where(valid, d, jnp.inf)


@partial(jax.jit, static_argnames=("seg_len", "nseg"))
def env_lower_bounds_batch(paa_lo, paa_hi, e_lo, e_hi, valid,
                           seg_len: int, nseg: int):
    """Lower bounds of a stacked (B, w) query batch to every envelope.

    The envelope-side intervals are read as held (the index computes
    its breakpoint intervals once per build, append, compact or open)
    and shared across the batch — the "shared plan" of the batched
    local backend.  Returns (B, N).
    """
    d = bounds.interval_mindist(paa_lo, paa_hi, e_lo, e_hi, seg_len, nseg)
    return jnp.where(valid[None, :], d, jnp.inf)


@partial(jax.jit, static_argnames=("seg_len", "nseg"))
def block_lower_bounds(paa_lo, paa_hi, blk_lo, blk_hi, blk_valid,
                       seg_len: int, nseg: int):
    """Lower bounds to block-level envelope unions (always PAA-valued —
    block unions are built from raw L/U PAA bounds, there is no quantized
    alternative at this level)."""
    d = bounds.interval_mindist(paa_lo, paa_hi, blk_lo, blk_hi, seg_len, nseg)
    return jnp.where(blk_valid, d, jnp.inf)


@partial(jax.jit, static_argnames=("seg_len", "nseg"))
def block_lower_bounds_batch(paa_lo, paa_hi, blk_lo, blk_hi, blk_valid,
                             seg_len: int, nseg: int):
    """block_lower_bounds of a stacked (B, w) query batch: (B, Nb)."""
    d = bounds.interval_mindist(paa_lo, paa_hi, blk_lo, blk_hi, seg_len, nseg)
    return jnp.where(blk_valid[None, :], d, jnp.inf)


# --------------------------------------------------------------------------
# host-side orderings
# --------------------------------------------------------------------------

def plan_leaf_order(index, pq: PreparedQuery) -> Tuple[np.ndarray, np.ndarray]:
    """Best-first order over the finest block level: (order, block_lbs)."""
    fine = index.levels[-1]
    blk_lb = np.asarray(block_lower_bounds(
        pq.paa_lo, pq.paa_hi, fine.paa_lo, fine.paa_hi, fine.valid,
        index.params.seg_len, pq.nseg), np.float64)
    return np.argsort(blk_lb), blk_lb


def plan_scan_order(index, pq: PreparedQuery,
                    use_paa_bounds: bool = False
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """LB-sorted envelope order for the exact scan: (order, sorted_lbs).

    Orders the FULL candidate set — the main sorted envelopes plus the
    unsorted ingestion delta (`index.search_envelopes()`), so appended
    series are scanned with the same bsf pruning as bulk-loaded ones.
    """
    lbs = np.asarray(env_lower_bounds(
        pq.paa_lo, pq.paa_hi, *index.lb_intervals(use_paa_bounds),
        index.search_envelopes().valid, index.params.seg_len, pq.nseg),
        np.float64)
    order = np.argsort(lbs)
    return order, lbs[order]


# --------------------------------------------------------------------------
# device-side packing (the one-sync local pipeline)
# --------------------------------------------------------------------------
#
# A host-side pack (argsort over np.asarray'd lower bounds, as PR 3's
# pack_scan_plan did) forces a device->host readback of every bound
# before the scan program can launch.  The one-sync pipeline
# (engine._local_exact_device / _local_range_device) instead packs on
# DEVICE: these functions are jitted, consume the traced lower bounds,
# and their outputs flow straight into the scan programs — the only
# host sync left is the final result readback.

@partial(jax.jit, static_argnames=("n_main", "block_size", "chunk", "n_leaves"))
def device_leaf_pack(env_sid, env_anchor, env_nm, env_valid, blk_lb,
                     n_main: int, block_size: int, chunk: int,
                     n_leaves: int):
    """Pack the approximate pass's candidates (paper Alg. 4, batched).

    Builds the chunk-aligned candidate rows the device scan core
    consumes for the *approximate* stage: first the ingestion delta
    (rows [n_main, N) of the combined set) padded to a multiple of
    `chunk` with lbs2 = 0 for real rows (the delta has no block cover —
    it is always swept, which primes the bsf exactly like the host
    path), then the `n_leaves` best leaves in ascending block-LB order,
    each leaf padded to `chunk` rows (chunk = pow2ceil(block_size)),
    every row carrying its BLOCK's squared lower bound — so the scan
    core's per-chunk stop IS Alg. 4's "next leaf cannot improve" stop.

    Returns (sids, anchors, n_master, lbs2, comb_idx, blk_lb_sorted):
    all (B, n_pad) except blk_lb_sorted, the ascending (B, min(n_leaves
    + 1, Nb)) best block bounds; comb_idx maps each
    packed row back to its combined-set envelope index (N for padding —
    scatter-dropped by device_scan_pack's exclusion).
    """
    b_sz, nblk = blk_lb.shape
    n_comb = env_sid.shape[0]
    n_delta = n_comb - n_main
    nd_pad = -(-n_delta // chunk) * chunk

    # only the n_leaves best blocks (and the next one, for the exactness
    # certificate) are ever read: a top-k, not a full per-query sort —
    # ties resolve to the lower block index, as a stable argsort would
    neg, order = jax.lax.top_k(-blk_lb, min(n_leaves + 1, nblk))
    blk_sorted = -neg                                   # (B, <= n_leaves+1)
    leaf_lb2 = (blk_sorted[:, :n_leaves] ** 2).astype(jnp.float32)

    member = jnp.arange(chunk, dtype=jnp.int32)
    lidx = (order[:, :n_leaves, None].astype(jnp.int32) * block_size
            + member[None, None, :])                # (B, n_leaves, chunk)
    lidx = jnp.where(member[None, None, :] < block_size, lidx, n_comb)
    didx = jnp.where(jnp.arange(nd_pad) < n_delta,
                     n_main + jnp.arange(nd_pad, dtype=jnp.int32), n_comb)
    comb_idx = jnp.concatenate(
        [jnp.broadcast_to(didx[None, :], (b_sz, nd_pad)),
         lidx.reshape(b_sz, n_leaves * chunk)], axis=1)     # (B, n_pad)

    real = comb_idx < n_comb
    safe = jnp.minimum(comb_idx, n_comb - 1)
    sids = jnp.where(real, jnp.take(env_sid, safe), 0).astype(jnp.int32)
    anchors = jnp.where(real, jnp.take(env_anchor, safe), 0) \
        .astype(jnp.int32)
    nm = jnp.where(real & jnp.take(env_valid, safe),
                   jnp.take(env_nm, safe), 0).astype(jnp.int32)
    row_lb2 = jnp.concatenate(
        [jnp.zeros((b_sz, nd_pad), jnp.float32),
         jnp.repeat(leaf_lb2, chunk, axis=1)], axis=1)
    lbs2 = jnp.where(real & (nm > 0), row_lb2, jnp.inf)
    # each chunk's FIRST row decides the scan core's stop test; within a
    # delta chunk the first row is always real (padding is a tail), and
    # within a leaf chunk the sorted main set puts valid rows first — so
    # re-pin the first row of every chunk to its block/delta bound even
    # when that row is individually invalid (empty boundary blocks keep
    # lbs2 = +inf everywhere and are skipped outright)
    first = (jnp.arange(comb_idx.shape[1]) % chunk) == 0
    any_valid = jnp.concatenate(
        [jnp.broadcast_to(jnp.array(n_delta > 0)[None],
                          (b_sz, nd_pad)) if nd_pad else
         jnp.zeros((b_sz, 0), bool),
         jnp.repeat(jnp.isfinite(leaf_lb2), chunk, axis=1)], axis=1)
    lbs2 = jnp.where(first[None, :] & any_valid, row_lb2, lbs2)
    return sids, anchors, nm, lbs2, comb_idx, blk_sorted


@partial(jax.jit, static_argnames=("chunk", "n_pad"))
def device_scan_pack(env_sid, env_anchor, env_nm, lbs, comb_idx,
                     visited_chunks, chunk: int, n_pad: int):
    """LB-sort + pack the exact/range scan's candidate rows ON DEVICE.

    The device twin of `pack_scan_plan`: `lbs` (B, N) are the combined
    candidate set's lower bounds; rows the approximate pass already
    verified — packed positions `< visited_chunks * chunk` of
    `comb_idx` (see device_leaf_pack) — are excluded by scatter-setting
    their bound to +inf (the device pool has no dedup).  Candidates are
    argsorted per query and right-padded to `n_pad` (pow2) columns.

    Returns (sids, anchors, n_master, lbs2, order) — plan arrays
    (B, n_pad) plus the (B, N) sort order the host continuation of an
    overflowed range query replays the tail chunks from.
    """
    b_sz, n = lbs.shape
    pos = jnp.arange(comb_idx.shape[1], dtype=jnp.int32)
    verified = pos[None, :] < (visited_chunks[:, None] * chunk)
    excl = jnp.zeros((b_sz, n), bool).at[
        jnp.arange(b_sz)[:, None], comb_idx].max(verified, mode="drop")
    lbs = jnp.where(excl, jnp.inf, lbs)
    order = jnp.argsort(lbs, axis=1)
    lbs_sorted = jnp.take_along_axis(lbs, order, axis=1)

    pad = n_pad - n
    def pack(col, fill):
        out = jnp.take(col, order).astype(jnp.int32)
        return jnp.pad(out, ((0, 0), (0, pad)), constant_values=fill)

    lbs2 = jnp.pad((lbs_sorted ** 2).astype(jnp.float32),
                   ((0, 0), (0, pad)), constant_values=jnp.inf)
    return (pack(env_sid, 0), pack(env_anchor, 0), pack(env_nm, 0),
            lbs2, order)


@partial(jax.jit, static_argnames=("n_pad", "n_delta", "chunk"))
def device_shard_pack(env_sid, env_anchor, env_nm, lbs, n_pad: int,
                      n_delta: int = 0, chunk: int = 1):
    """LB-sort + pack ONE SHARD's candidate rows on device.

    The per-shard twin of `device_scan_pack`, consumed by the sharded
    distributed scan (distributed/ulisse.py): inside `shard_map` every
    shard packs its own local envelope slice into ascending-lower-bound
    order.  There is no approximate pass on the sharded path — the
    first chunks of the LB order play its bsf-priming role — so the
    scatter-exclusion machinery of `device_scan_pack` is skipped
    entirely (it is the expensive half of that pack on CPU).

    `lbs` (B, N_local) are the shard's lower bounds (env_* are the
    shard-local envelope columns, series ids already localized).  The
    last `n_delta` rows are the shard's unsorted ingestion delta
    (DESIGN.md §15): they are packed FIRST, chunk-padded, in original
    order with their real squared bounds — except each delta chunk's
    head row, pinned to 0.  A delta chunk is unsorted, so its head
    bound says nothing about the rows behind it; the pin keeps the scan
    core's chunk-head stop/skip test (`_first_lb2`) from skipping a
    chunk whose later rows beat the bsf, making the delta region an
    always-visited sweep — exactly the local backend's exhaustive delta
    pass.  The LB-sorted main rows follow, so the ascending-head stop
    logic (and the approximate pass's exactness certificate) applies
    unchanged past the delta region.

    Returns (sids, anchors, n_master, lbs2): (B, n_pad) plan arrays
    right-padded with +inf bounds past the real rows.  `n_pad`,
    `chunk`, and the padded delta width must come from
    `executor.shard_pack_geometry` so packer and scan agree.
    """
    if n_delta == 0:
        pad = n_pad - lbs.shape[1]
        order = jnp.argsort(lbs, axis=1)
        lbs_sorted = jnp.take_along_axis(lbs, order, axis=1)

        def pack(col):
            out = jnp.take(col, order).astype(jnp.int32)
            return jnp.pad(out, ((0, 0), (0, pad)))

        lbs2 = jnp.pad((lbs_sorted ** 2).astype(jnp.float32),
                       ((0, 0), (0, pad)), constant_values=jnp.inf)
        return pack(env_sid), pack(env_anchor), pack(env_nm), lbs2

    b_sz, n = lbs.shape
    n_main = n - n_delta
    nd_pad = -(-n_delta // chunk) * chunk
    # delta block: original order, real bounds, chunk heads pinned
    didx = jnp.arange(nd_pad, dtype=jnp.int32)
    dreal = didx < n_delta
    dsafe = n_main + jnp.minimum(didx, n_delta - 1)

    def dpack(col):
        out = jnp.where(dreal, jnp.take(col, dsafe), 0).astype(jnp.int32)
        return jnp.broadcast_to(out[None, :], (b_sz, nd_pad))

    d_lb2 = jnp.pad((lbs[:, n_main:] ** 2).astype(jnp.float32),
                    ((0, 0), (0, nd_pad - n_delta)),
                    constant_values=jnp.inf)
    # invalid delta envelopes carry lb = +inf; zero their n_master so a
    # pinned head can never expand garbage candidate windows
    d_nm = jnp.where(jnp.isfinite(d_lb2), dpack(env_nm), 0)
    head = ((didx % chunk) == 0) & dreal
    d_lb2 = jnp.where(head[None, :], 0.0, d_lb2)
    # main block: the classic LB-argsort, padded out to n_pad
    m_pad = n_pad - nd_pad
    mlbs = lbs[:, :n_main]
    order = jnp.argsort(mlbs, axis=1)
    lbs_sorted = jnp.take_along_axis(mlbs, order, axis=1)

    def mpack(col):
        out = jnp.take(col[:n_main], order).astype(jnp.int32)
        return jnp.pad(out, ((0, 0), (0, m_pad - n_main)))

    m_lb2 = jnp.pad((lbs_sorted ** 2).astype(jnp.float32),
                    ((0, 0), (0, m_pad - n_main)),
                    constant_values=jnp.inf)
    cat = lambda a, b: jnp.concatenate([a, b], axis=1)  # noqa: E731
    return (cat(dpack(env_sid), mpack(env_sid)),
            cat(dpack(env_anchor), mpack(env_anchor)),
            cat(d_nm, mpack(env_nm)), cat(d_lb2, m_lb2))


@partial(jax.jit, static_argnames=("n_pad",))
def device_range_pack(env_sid, env_anchor, env_nm, lbs, eps2,
                      n_pad: int):
    """Pack the eps-range scan's candidates ON DEVICE — no sort.

    A range query's cut never moves (bsf == eps), so scan order is
    irrelevant: any envelope with lb2 <= eps2 must be verified, no
    other ever can be.  Candidates are therefore *packed to the front
    in original combined-set order* by a binary-search gather over the
    candidate-mask cumsum (an argsort here costs more than the whole
    verification chunk on CPU).  The inclusive cut keeps boundary hits
    with lb == d == eps.

    Returns (sids, anchors, n_master, lbs2, src): plan arrays
    (B, n_pad) with +inf lbs2 past each query's candidate count, and
    `src` — the combined-set envelope index of every packed row (what
    the host continuation of an overflowed query replays from).
    """
    lbs2 = (lbs ** 2).astype(jnp.float32)
    cand = (lbs2 <= eps2[:, None]) & jnp.isfinite(lbs2)
    nc = jnp.sum(cand, axis=1, dtype=jnp.int32)
    cc = jnp.cumsum(cand, axis=1)
    ranks = jnp.arange(n_pad, dtype=jnp.int32) + 1
    src = jax.vmap(jnp.searchsorted, in_axes=(0, None))(cc, ranks)
    src = jnp.minimum(src, lbs2.shape[1] - 1).astype(jnp.int32)
    real = ranks[None, :] <= nc[:, None]

    def pack(col, fill):
        return jnp.where(real, jnp.take(col, src), fill) \
            .astype(jnp.int32)

    lbs2p = jnp.where(real, jnp.take_along_axis(lbs2, src, axis=1),
                      jnp.inf)
    return (pack(env_sid, 0), pack(env_anchor, 0), pack(env_nm, 0),
            lbs2p, src)


# --------------------------------------------------------------------------
# paged access scheduling (host side)
# --------------------------------------------------------------------------
#
# On the paged out-of-core path the packed plan doubles as a *page
# access schedule*: the LB-sorted candidate order fixes exactly which
# series rows chunk i will gather, so the slab (and the pages behind
# it) for chunk i+1 can be faulted + transferred while chunk i
# computes.  These helpers are the planner's side of that contract —
# pure numpy, shared by the executor's prefetch worker and the tests.

def chunk_pages(sids: np.ndarray, i: int, chunk: int, page_rows: int):
    """Resolve plan chunk i's slab: which series rows, which pages.

    `sids` is the packed (B, n_pad) GLOBAL series-id plan (host numpy).
    Returns (uniq, local, pages): the chunk's sorted-unique global
    series ids, the (B, chunk) slab-local remap of the plan columns
    (uniq[local] == the original sids), and the sorted-unique page
    indices those rows live on under `page_rows`-row pages.
    """
    cols = np.ascontiguousarray(sids[:, i * chunk:(i + 1) * chunk])
    uniq = np.unique(cols)
    local = np.searchsorted(uniq, cols).astype(np.int32)
    pages = np.unique(uniq // page_rows)
    return uniq, local, pages


def chunk_page_schedule(sids: np.ndarray, page_rows: int, chunk: int):
    """The full chunk -> page access schedule of a packed plan.

    Returns a list over chunks of sorted-unique page-index arrays —
    what a paged scan would fault, in visit order, if it ran every
    chunk (the scan's early stop only ever truncates this).  Used by
    tests and capacity analysis; the executor resolves chunks lazily
    via `chunk_pages` so a converged scan never schedules dead pages.
    """
    sids = np.asarray(sids)
    n_chunks = sids.shape[1] // chunk
    return [chunk_pages(sids, i, chunk, page_rows)[2]
            for i in range(n_chunks)]


# --------------------------------------------------------------------------
# masked planning (traced qlen over a padded length bucket)
# --------------------------------------------------------------------------

def masked_prepare(q_pad: jnp.ndarray, qlen: jnp.ndarray,
                   p: EnvelopeParams):
    """Prepare a bucket-padded ED query with a *traced* true length.

    q_pad: (Lb,) query padded to the bucket length with arbitrary tail.
    qlen:  () int32 true length, lmin <= qlen <= Lb.

    Returns (qn, qp, seg_mask) where qn is the masked-(Z-)normalized query
    with a zeroed tail, qp its PAA padded to `p.w` segments, and seg_mask
    the (p.w,) validity of each PAA segment (floor(qlen/s) leading True).
    One trace of the enclosing program serves every qlen in the bucket.
    """
    lb = q_pad.shape[-1]
    mask = jnp.arange(lb) < qlen
    if p.znorm:
        qn = masked_znormalize(q_pad, mask, qlen)
    else:
        qn = jnp.where(mask, q_pad, 0.0)
    qp = paa(qn, p.seg_len)                       # (Lb // s,)
    w = p.w
    qp = jnp.pad(qp, (0, w - qp.shape[-1]))
    nseg = qlen // p.seg_len
    seg_mask = jnp.arange(w) < nseg
    return qn, qp, seg_mask
