"""Distributed ULISSE: sharded index build + query answering on a mesh.

Sharding model (DESIGN.md §6/§10): the collection (and therefore the
envelopes) shard over the data-parallel axes; index build is
embarrassingly parallel (each device summarizes its own series); a query
broadcasts Q and every shard runs the SAME device-resident pruned scan
core as the local backend (core/executor.py §8/§9) over its own
LB-ordered leaf pack, with a periodically broadcast global best-so-far
(collectives.global_kth) so each shard prunes against the mesh-wide
candidate pool rather than its local one, one final cross-shard top-k
merge (collectives.ring_topk_merge), and ONE host sync per batch.

The distributed backend is a thin sharding layer over one shared scan
core: `make_sharded_knn_query` / `make_sharded_range_query` compose
`planner.device_shard_pack` (per-shard LB packing), the executor's
`_scan_chunk_step` / `_device_range_core` (the fused gather+verify
chunk machinery of the local device pipeline, DTW tier included), and
the collectives above inside `shard_map` — one program, any mesh size;
the same code runs the 4-device test and the 512-chip dry-run.

`make_batched_distributed_query` below is the PR-1-era unpruned
per-shard verify (top-`verify_top` LB candidates verified, certificate
+ host escalation).  It is retired from the engine's default path but
kept as the `scan_backend="host"` distributed reference oracle and the
benchmark baseline the pruned sharded scan is measured against
(benchmarks/bench_kernels.py::bench_distributed_scan).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import bounds, executor, planner
from repro.core.envelope import build_envelope_set
from repro.core.types import Collection, EnvelopeParams, EnvelopeSet
from repro.distributed import collectives


def shard_collection(mesh, data: jnp.ndarray, axes=("data",)):
    """Place a (S, n) series array sharded over the given mesh axes."""
    spec = P(axes if len(axes) > 1 else axes[0])
    return jax.device_put(data, NamedSharding(mesh, spec))


def shard_host_arrays(sharded) -> list:
    """Per-shard host copies of a sharded (S, n) array, in row order.

    The persistence path (repro.storage.save_distributed) writes these
    as the per-shard payloads: each host copies only its addressable
    shards — no all-gather of the full collection through one host —
    which is what lets the checkpoint-style save scale with the mesh.
    Replicated copies (if an axis is unsharded) are deduplicated by
    row offset.
    """
    by_start = {}
    for s in sharded.addressable_shards:
        start = s.index[0].start or 0
        if start not in by_start:
            by_start[start] = np.asarray(s.data)
    return [by_start[k] for k in sorted(by_start)]


def decode_id(code):
    """codes are (sid, off) int32 pairs stacked on the last axis."""
    return code[..., 0], code[..., 1]


# --------------------------------------------------------------------------
# the sharded device scan (PR 5 tentpole, DESIGN.md §10)
# --------------------------------------------------------------------------

# field order of the sharded index tuple produced by build_sharded_index
# and consumed (in this order) by the query programs' in_specs
SHARDED_INDEX_FIELDS = (
    "data", "csum", "csum2", "csum_lo", "csum2_lo", "center",
    "paa_lo", "paa_hi", "sym_lo", "sym_hi",
    "series_id", "anchor", "n_master", "valid",
)

# the non-data fields, as built per block by build_host_index and
# persisted per shard by repro.storage.save_distributed (DESIGN.md §15)
INDEX_SECTION_FIELDS = SHARDED_INDEX_FIELDS[1:]


def build_host_index(p: EnvelopeParams, breakpoints, data) -> dict:
    """Host-side index rows for one block of series: the 13 non-data
    fields of SHARDED_INDEX_FIELDS as numpy arrays, with series_id
    LOCAL to the block (row index within `data`).

    Row-wise determinism (Collection.from_array / host_prefix_stats and
    build_envelope_set are all per-series) makes a per-block build
    bit-equal to slicing one global build, so concatenating block
    results — with env series_id offset by the series before the block
    — IS the full build.  The per-shard delta model and the persisted
    manifest sections (DESIGN.md §15) both lean on exactly this: a
    shard's [main; delta] index is sections for the saved prefix plus a
    build over the appended tail, never a re-summarization of the
    whole shard.
    """
    coll = Collection.from_array(np.asarray(data, np.float32))
    env = build_envelope_set(coll, p, breakpoints)
    out = {
        "csum": coll.csum, "csum2": coll.csum2,
        "csum_lo": coll.csum_lo, "csum2_lo": coll.csum2_lo,
        "center": coll.center,
        "paa_lo": env.paa_lo, "paa_hi": env.paa_hi,
        "sym_lo": env.sym_lo, "sym_hi": env.sym_hi,
        "series_id": env.series_id, "anchor": env.anchor,
        "n_master": env.n_master, "valid": env.valid,
    }
    return {f: np.asarray(v) for f, v in out.items()}


def build_sharded_index(mesh, p: EnvelopeParams, breakpoints, data,
                        axes=("data",), data_sharded=None):
    """Build the collection + envelope arrays once, each shard's rows on
    its own device, laid out row-sharded over the mesh.

    The PR-1 path rebuilt every shard's envelopes in-graph on every
    query; here the summarization runs once at engine construction —
    through the same host `Collection.from_array` (float64-split prefix
    sums) and `build_envelope_set` as the local backend, so per-shard
    window statistics and envelope bounds are numerically identical to
    a local build over the same series.  `build_envelope_set` flattens
    per series (rows [s*n_env, (s+1)*n_env) belong to series s), so a
    series-divisible mesh shards the envelope rows evenly with plain
    row sharding — no padding, no re-grouping.  Each device summarizes
    only its own rows (series ids offset to global), so no device ever
    holds more than its shard: a collection sized to fill every chip
    never transits one.

    Returns a dict of sharded jax.Arrays keyed by SHARDED_INDEX_FIELDS;
    `data_sharded` (if given) is reused as the "data" entry so the raw
    series are not duplicated on device.
    """
    data = np.asarray(data, np.float32)
    sharding = NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))
    n_env = p.num_envelopes(data.shape[1])
    placement = list(sharding.addressable_devices_indices_map(
        data.shape).items())

    def build(dev, rows):
        with jax.default_device(dev):
            coll = Collection.from_array(data[rows])
            env = build_envelope_set(coll, p,
                                     jax.device_put(breakpoints, dev))
            env.series_id = env.series_id + (rows.start or 0)
        return [jax.device_put(
            getattr(env if f in _ENV_FIELDS else coll, f), dev)
            for f in SHARDED_INDEX_FIELDS]

    # the host prefix sums dominate and release the GIL: one thread per
    # device overlaps them across shards
    with ThreadPoolExecutor(len(placement)) as ex:
        built = list(ex.map(lambda di: build(di[0], di[1][0]), placement))
    pieces = {f: [b[i] for b in built]
              for i, f in enumerate(SHARDED_INDEX_FIELDS)}

    def assemble(f):
        shard = pieces[f][0]
        rows = data.shape[0] * (n_env if f in _ENV_FIELDS else 1)
        return jax.make_array_from_single_device_arrays(
            (rows,) + shard.shape[1:], sharding, pieces[f])

    return {f: data_sharded if f == "data" and data_sharded is not None
            else assemble(f) for f in SHARDED_INDEX_FIELDS}


# SHARDED_INDEX_FIELDS taken from the EnvelopeSet (the rest are
# Collection fields)
_ENV_FIELDS = ("paa_lo", "paa_hi", "sym_lo", "sym_hi", "series_id",
               "anchor", "n_master", "valid")


def _shard_row_index(mesh, axes):
    """Linear shard index over the (possibly multi-axis) row sharding."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _sharded_knn_scan(coll: Collection, sids, anchors, n_master, lbs2,
                      qs, dtw_lo, dtw_hi, *, k: int, g: int, chunk: int,
                      znorm: bool, measure: str, r: int, sb: int,
                      sync_every: int, budget_chunks: int,
                      delta_chunks: int = 0, axis_name,
                      interpret: bool):
    """One shard's half of the globally-pruned k-NN scan (paper Alg. 5/7
    on a mesh).

    Runs the shared chunk step (`executor._scan_chunk_step`) over this
    shard's LB-sorted pack, pruning every chunk with
    min(local pool kth, gkth) where gkth is the mesh-wide squared bsf
    re-broadcast every `sync_every` chunks (collectives.global_kth).
    The loop itself is round-structured: `sync_every` chunk steps, one
    bsf broadcast, one replicated continue-flag all-reduce — the
    while_loop condition must be identical on every shard or the
    collectives inside the body deadlock, so the flag is reduced in the
    body and carried, never recomputed locally in `cond`.

    `budget_chunks` > 0 caps the per-shard scan depth (the distributed
    approximate mode: the first LB-ordered chunks ARE the paper's
    best-first leaf visits); 0 means scan to convergence.
    `delta_chunks` counts leading UNSORTED delta chunks in the pack
    (planner.device_shard_pack with n_delta > 0, pinned heads): they
    are an always-visited exhaustive sweep mirroring the local delta
    pass, so the approximate budget stretches by them — the chunk at
    `budget` is then a main LB-ascending chunk and the certificate
    reasoning below still holds.  Returns
    (pool, stats (B, executor.STATS_WIDTH), cert (B,)) — `cert` is the
    in-graph exactness
    certificate: True iff no shard's first unvisited chunk could still
    improve the final global pool (always True with no budget, because
    that is the loop's only exit).
    """
    b_sz = qs.shape[0]
    n_pad = sids.shape[1]
    n_chunks = n_pad // chunk
    budget = (min(budget_chunks + delta_chunks, n_chunks)
              if budget_chunks else n_chunks)

    heads = executor._chunk_heads(lbs2, chunk)

    def local_active(i, pool, gkth):
        kth = jnp.minimum(pool[0][:, k - 1], gkth)
        f = executor._first_lb2(heads, i)
        return (i < budget) & jnp.isfinite(f) & (f < kth)

    def chunk_step(j, carry):
        i0, pool, gkth, stats = carry
        i = i0 + j
        active = local_active(i, pool, gkth)
        kth = jnp.minimum(pool[0][:, k - 1], gkth)
        pool, ds = executor._scan_chunk_step(
            coll.data, coll.csum, coll.csum2, coll.csum_lo,
            coll.csum2_lo, coll.center, sids, anchors, n_master, lbs2,
            qs, dtw_lo, dtw_hi, i, pool, kth, active, k=k, g=g,
            chunk=chunk, znorm=znorm, measure=measure, r=r, sb=sb,
            interpret=interpret)
        return (i0, pool, gkth, stats + ds)

    def round_body(state):
        i, pool, gkth, _, stats = state
        _, pool, gkth, stats = jax.lax.fori_loop(
            0, sync_every, chunk_step, (i, pool, gkth, stats))
        i = i + sync_every
        gkth = collectives.global_kth(pool[0], k, axis_name)
        rem = jnp.any(local_active(i, pool, gkth))
        cont = jax.lax.pmax(rem.astype(jnp.int32), axis_name) > 0
        return (i, pool, gkth, cont, stats)

    pool0 = (jnp.full((b_sz, k), jnp.inf, jnp.float32),
             jnp.full((b_sz, k), -1, jnp.int32),
             jnp.full((b_sz, k), -1, jnp.int32))
    gkth0 = jnp.full((b_sz,), jnp.inf, jnp.float32)
    cont0 = jax.lax.pmax(
        jnp.any(local_active(jnp.int32(0), pool0, gkth0))
        .astype(jnp.int32), axis_name) > 0
    state = (jnp.int32(0), pool0, gkth0, cont0,
             jnp.zeros((b_sz, executor.STATS_WIDTH), jnp.int32))
    _, pool, _, _, stats = jax.lax.while_loop(
        lambda s: s[3], round_body, state)

    # in-graph exactness certificate: the pack is LB-ascending, so the
    # chunk at `budget` heads everything unvisited; once pruned it stays
    # pruned (kth only shrinks), so checking it against the FINAL bound
    # covers every earlier per-query stop too
    gkth = collectives.global_kth(pool[0], k, axis_name)
    kth = jnp.minimum(pool[0][:, k - 1], gkth)
    f = executor._first_lb2(heads, jnp.int32(budget))
    rem = (budget < n_chunks) & jnp.isfinite(f) & (f < kth)
    cert = jax.lax.pmax(rem.astype(jnp.int32), axis_name) == 0
    return pool, stats, cert


def _shard_prelude(p, breakpoints, use_paa, mesh, axes, data, e_sid,
                   e_anc, e_nm, e_valid, e_paalo, e_paahi, e_symlo,
                   e_symhi, qb, qh, qlen, localized: bool = False):
    """Shared per-shard query prelude: localize series ids, rebuild the
    EnvelopeSet view, compute lower bounds for the batch.  Returns
    (shard_idx, local sids, lbs (B, N_local)).

    `localized`: the env series_id column is ALREADY the row index into
    this shard's data block (the delta/gmap program families — global
    ids of delta rows are not affine in the shard index once several
    append parts exist, so those families carry an explicit local→
    global map instead of localizing here)."""
    s_local = data.shape[0]
    shard_idx = _shard_row_index(mesh, axes)
    if localized:
        lsid = e_sid.astype(jnp.int32)
    else:
        lsid = (e_sid - shard_idx * s_local).astype(jnp.int32)
    env = EnvelopeSet(paa_lo=e_paalo, paa_hi=e_paahi, sym_lo=e_symlo,
                      sym_hi=e_symhi, series_id=lsid, anchor=e_anc,
                      n_master=e_nm, valid=e_valid)
    nseg = p.query_segments(qlen)
    if use_paa:
        e_lo, e_hi = e_paalo, e_paahi
    else:
        e_lo, e_hi = bounds.envelope_breakpoint_bounds(env, breakpoints)
    lbs = planner.env_lower_bounds_batch(qb, qh, e_lo, e_hi, e_valid,
                                         p.seg_len, nseg)
    return shard_idx, lsid, lbs


def make_sharded_knn_query(mesh, p: EnvelopeParams, breakpoints, *,
                           k: int, measure: str = "ed", r: int = 0,
                           use_paa: bool = False, chunk_size: int = 512,
                           sync_every: int = 8, budget_chunks: int = 0,
                           axes=("data",), delta_rows: int = 0,
                           with_gmap: bool = False, interpret=None):
    """Build the jitted sharded k-NN program (exact or, with
    `budget_chunks` > 0, the budget-capped approximate mode).

    Returns query_fn(*sharded_index, qs, dlo, dhi, qb, qh) ->
    (d2 (B, k) ascending squared distances, sid (B, k) GLOBAL series
    ids, off (B, k), stats (P, B, executor.STATS_WIDTH) per-shard
    counter stacks, cert (B,) exactness certificates).  `sharded_index` is the
    build_sharded_index tuple in SHARDED_INDEX_FIELDS order; query
    length is read from qs.shape (one retrace per (B, qlen) shape, no
    per-length maker).

    The delta/ingestion variant (DESIGN.md §15): `with_gmap=True`
    inserts a 15th sharded input after `valid` — gmap (s_local,) int32
    mapping local data row -> GLOBAL series id — and treats the env
    series_id column as already-local row indices (see _shard_prelude).
    `delta_rows` (static) is the per-shard count of trailing UNSORTED
    delta envelope rows; they pack FIRST with pinned chunk heads
    (planner.device_shard_pack) so the scan sweeps them exhaustively
    before the LB-ascending main region.  `delta_rows=0, with_gmap=True`
    is the cold-open no-delta case and runs the identical arithmetic to
    the classic family (the n_delta=0 pack is the classic pack).
    """
    if interpret is None:
        from repro.kernels.common import default_interpret
        interpret = default_interpret()
    axis = axes if len(axes) > 1 else axes[0]
    shards = _shards(mesh, axes)
    g = p.gamma + 1

    def local_fn(data, csum, csum2, cslo, cs2lo, center, paa_lo, paa_hi,
                 sym_lo, sym_hi, e_sid, e_anc, e_nm, e_valid, *rest):
        if with_gmap:
            gmap, qs, dlo, dhi, qb, qh = rest
        else:
            gmap, (qs, dlo, dhi, qb, qh) = None, rest
        qlen = qs.shape[1]
        shard_idx, lsid, lbs = _shard_prelude(
            p, breakpoints, use_paa, mesh, axes, data, e_sid, e_anc,
            e_nm, e_valid, paa_lo, paa_hi, sym_lo, sym_hi, qb, qh, qlen,
            localized=with_gmap)
        n_pad, chunk, nd_pad = executor.shard_pack_geometry(
            e_sid.shape[0], delta_rows, chunk_size)
        sids, anc, nm, lbs2 = planner.device_shard_pack(
            lsid, e_anc, e_nm, lbs, n_pad=n_pad, n_delta=delta_rows,
            chunk=chunk)
        coll = Collection(data=data, csum=csum, csum2=csum2,
                          center=center, csum_lo=cslo, csum2_lo=cs2lo)
        pool, stats, cert = _sharded_knn_scan(
            coll, sids, anc, nm, lbs2, qs, dlo, dhi, k=k, g=g,
            chunk=chunk, znorm=p.znorm, measure=measure, r=r,
            sb=min(128, chunk * g), sync_every=sync_every,
            budget_chunks=budget_chunks, delta_chunks=nd_pad // chunk,
            axis_name=axis, interpret=interpret)
        d2, psid, poff = pool
        if gmap is None:
            gsid = jnp.where(psid >= 0,
                             psid + shard_idx * data.shape[0],
                             -1).astype(jnp.int32)
        else:
            gsid = jnp.where(psid >= 0,
                             jnp.take(gmap, jnp.maximum(psid, 0)),
                             -1).astype(jnp.int32)
        if shards == 1:
            md2, msid, moff = d2, gsid, poff
        elif len(axes) == 1:
            md2, msid, moff = collectives.ring_topk_merge(
                d2, gsid, poff, k, axis, shards)
        else:
            md2, msid, moff = collectives.allgather_topk_merge(
                d2, gsid, poff, k, axis)
        return md2, msid, moff, stats[None], cert

    spec_data = P(axes if len(axes) > 1 else axes[0])
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=tuple([spec_data] * (15 if with_gmap else 14)
                       + [P()] * 5),
        out_specs=(P(), P(), P(), spec_data, P()), check_vma=False)
    return jax.jit(fn)


def make_sharded_range_query(mesh, p: EnvelopeParams, breakpoints, *,
                             capacity: int, n_rows_per_shard: int,
                             measure: str = "ed", r: int = 0,
                             use_paa: bool = False,
                             chunk_size: int = 512, axes=("data",),
                             with_gmap: bool = False, interpret=None):
    """Build the jitted sharded eps-range program.

    Each shard packs its candidates (lb2 <= eps2, sortless — the cut
    never moves) and runs the §9 fixed-capacity hit-buffer core over
    them; there is no bsf to share, so the scan needs NO collectives at
    all — hits stay in per-shard buffers that concatenate on the output
    spec.  Returns (query_fn, chunk): query_fn(*sharded_index, qs, dlo,
    dhi, qb, qh, eps2) -> (bd2 (B, P*cap), bsid GLOBAL, boff, cnt
    (P, B), ovf (P, B), stats (P, B, executor.STATS_WIDTH),
    plan_sid/plan_anc/plan_nm/plan_lbs2 (P, B, n_pad)); the plan arrays (GLOBAL series ids) let
    the host replay chunks [ovf, n_chunks) of an overflowed
    (query, shard) pair through the §9 continuation without re-deriving
    the shard's pack.  `chunk` is the plan-row chunking the program
    scans with — the continuation must resume at row
    `ovf * chunk`, and returning it (like device_range_scan does) keeps
    the engine from re-deriving (and drifting from) the internal
    chunking; `n_rows_per_shard` pins the packing width the same way.

    `with_gmap=True` is the delta/ingestion variant (DESIGN.md §15):
    a 15th sharded input after `valid` — gmap (s_local,) int32, local
    data row -> GLOBAL series id — with env series_id already local.
    Unlike the k-NN pack, the range pack needs NO delta-first region:
    device_range_pack is sortless (the eps cut never moves, order is
    irrelevant), so delta rows pack wherever they land and the §9 core
    handles them untouched; only the id globalization changes.
    """
    if interpret is None:
        from repro.kernels.common import default_interpret
        interpret = default_interpret()
    g = p.gamma + 1
    cap = executor.pow2ceil(capacity)
    n_pad = executor.pow2ceil(n_rows_per_shard)
    chunk = min(executor.pow2ceil(chunk_size), n_pad)

    def local_fn(data, csum, csum2, cslo, cs2lo, center, paa_lo, paa_hi,
                 sym_lo, sym_hi, e_sid, e_anc, e_nm, e_valid, *rest):
        if with_gmap:
            gmap, qs, dlo, dhi, qb, qh, eps2 = rest
        else:
            gmap, (qs, dlo, dhi, qb, qh, eps2) = None, rest
        qlen = qs.shape[1]
        shard_idx, lsid, lbs = _shard_prelude(
            p, breakpoints, use_paa, mesh, axes, data, e_sid, e_anc,
            e_nm, e_valid, paa_lo, paa_hi, sym_lo, sym_hi, qb, qh, qlen,
            localized=with_gmap)
        sids, anc, nm, lbs2, _ = planner.device_range_pack(
            lsid, e_anc, e_nm, lbs, eps2, n_pad=n_pad)
        bd2, bsid, boff, cnt, ovf, st = executor._device_range_core(
            data, csum, csum2, cslo, cs2lo, center, sids, anc, nm,
            lbs2, qs, dlo, dhi, eps2, cap=cap, g=g, chunk=chunk,
            znorm=p.znorm, measure=measure, r=r,
            sb=min(128, chunk * g), interpret=interpret)
        if gmap is None:
            off0 = shard_idx * data.shape[0]
            gbsid = jnp.where(bsid >= 0, bsid + off0, bsid)
            plan_sid = (sids + off0).astype(jnp.int32)
        else:
            gbsid = jnp.where(bsid >= 0,
                              jnp.take(gmap, jnp.maximum(bsid, 0)),
                              bsid)
            plan_sid = jnp.take(gmap, sids).astype(jnp.int32)
        return (bd2, gbsid.astype(jnp.int32), boff, cnt[None],
                ovf[None], st[None], plan_sid[None],
                anc[None], nm[None], lbs2[None])

    spec_data = P(axes if len(axes) > 1 else axes[0])
    row0 = axes if len(axes) > 1 else axes[0]
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=tuple([spec_data] * (15 if with_gmap else 14)
                       + [P()] * 6),
        out_specs=(P(None, row0), P(None, row0), P(None, row0),
                   spec_data, spec_data, spec_data, spec_data,
                   spec_data, spec_data, spec_data), check_vma=False)
    return jax.jit(fn), chunk


def _shards(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def make_batched_distributed_query(mesh, p: EnvelopeParams, breakpoints,
                                   bucket: int, k: int,
                                   axes=("data",), verify_top: int = 128):
    """Build a jitted exact k-NN over a sharded collection, batched over
    queries and generic over query length within a padded bucket.

    Returns query_fn(data_sharded, qs, qlens) -> (dists, codes, exact):
      qs    (batch, bucket) float32 — queries right-padded to the bucket,
      qlens (batch,)        int32   — true lengths (lmin <= qlen <= bucket),
      dists (batch, k), codes (batch, k, 2) int32 (global series_id,
      offset) pairs, exact (batch,) bool exactness certificates.

    The per-shard algorithm is the TPU-native exact search (masked lower
    bounds for every local envelope -> top-`verify_top` candidates
    verified on the MXU) followed by a global per-query top-k merge;
    `verify_top` bounds the verification batch, with correctness kept by
    comparing the k-th verified distance against the tightest unverified
    lower bound (the returned `exact` flags — UlisseEngine escalates
    verify_top internally when a certificate fails).
    """
    axis = axes if len(axes) > 1 else axes[0]
    g = p.gamma + 1

    def local_search(data_shard: jnp.ndarray, qs: jnp.ndarray,
                     qlens: jnp.ndarray):
        coll = Collection.from_array(data_shard)
        env = build_envelope_set(coll, p, breakpoints)
        e_lo, e_hi = bounds.envelope_breakpoint_bounds(env, breakpoints)
        n = data_shard.shape[1]
        vt = min(verify_top, env.size)
        kk = min(k, vt * g)

        shard_idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            shard_idx = shard_idx * mesh.shape[a] + jax.lax.axis_index(a)

        def one_query(q_pad, qlen):
            qn, qp, seg_mask = planner.masked_prepare(q_pad, qlen, p)
            lbs = bounds.masked_interval_mindist(qp, qp, e_lo, e_hi,
                                                 p.seg_len, seg_mask)
            lbs = jnp.where(env.valid, lbs, jnp.inf)

            neg, cand = jax.lax.top_k(-lbs, vt)
            cand_lb = -neg
            sids = jnp.take(env.series_id, cand)
            anchors = jnp.take(env.anchor, cand)
            n_master = jnp.take(env.n_master, cand)
            windows, ok, offs = executor.gather_bucket_windows(
                data_shard, sids, anchors, n_master, qlen, bucket, g)
            mask = jnp.arange(bucket) < qlen
            d2 = executor.masked_ed(windows, qn, mask, qlen, p.znorm)
            d2 = jnp.where(ok, d2, jnp.inf)
            d = jnp.sqrt(jnp.maximum(d2, 0.0))

            gsid = (sids + shard_idx * data_shard.shape[0]).astype(jnp.int32)
            codes = jnp.stack([jnp.repeat(gsid, g),
                               offs.astype(jnp.int32)], axis=-1)
            negd, sel = jax.lax.top_k(-d, kk)
            # exactness certificate: kth verified <= smallest unverified LB
            return -negd, jnp.take(codes, sel, axis=0), jnp.max(cand_lb)

        local_d, local_codes, unverified_lb = jax.vmap(one_query)(qs, qlens)
        all_d = jax.lax.all_gather(local_d, axis, axis=1, tiled=True)
        all_c = jax.lax.all_gather(local_codes, axis, axis=1, tiled=True)
        # fewer gathered candidates than k (k > verify_top * g * shards):
        # pad with +inf rows, which fail the certificate and escalate
        km = min(k, all_d.shape[1])
        negm, idx = jax.lax.top_k(-all_d, km)                   # (B, km)
        merged_d = -negm
        merged_c = jnp.take_along_axis(all_c, idx[..., None], axis=1)
        if km < k:
            b = merged_d.shape[0]
            merged_d = jnp.concatenate(
                [merged_d, jnp.full((b, k - km), jnp.inf)], axis=1)
            merged_c = jnp.concatenate(
                [merged_c, jnp.zeros((b, k - km, 2), jnp.int32)], axis=1)
        exact = merged_d[:, -1] <= jax.lax.pmin(unverified_lb, axis)
        return merged_d, merged_c, exact

    spec_data = P(axes if len(axes) > 1 else axes[0])
    fn = jax.shard_map(local_search, mesh=mesh,
                       in_specs=(spec_data, P(), P()),
                       out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)


def make_distributed_query(mesh, p: EnvelopeParams, breakpoints,
                           qlen: int, k: int, axes=("data",),
                           verify_top: int = 128):
    """Single-query exact k-NN (legacy surface, kept for callers that
    manage their own per-length programs — prefer core.engine.UlisseEngine).

    Returns query_fn(data_sharded, q) -> (dists (k,), codes (k, 2), exact).
    Implemented as the B=1, bucket=qlen case of the batched program.
    """
    batched = make_batched_distributed_query(
        mesh, p, breakpoints, bucket=qlen, k=k, axes=axes,
        verify_top=verify_top)

    def query_fn(data_sharded, q):
        qs = jnp.asarray(q, jnp.float32)[None, :]
        qlens = jnp.full((1,), qlen, jnp.int32)
        d, codes, exact = batched(data_sharded, qs, qlens)
        return d[0], codes[0], exact[0]

    return query_fn


def distributed_index_stats(mesh, p: EnvelopeParams, num_series: int,
                            series_len: int,
                            delta_envelopes: int = 0) -> dict:
    """Analytic size/balance report for the sharded index.

    `delta_envelopes`: envelopes sitting in an ingestion delta buffer
    (`UlisseEngine.delta_size`) on top of the bulk-built set.  They are
    part of every shard's resident working set once the grown index is
    re-opened onto the mesh, so capacity planning that ignored them
    (the pre-PR-5 behavior) under-reported bytes_per_device after
    appends.
    """
    n_env = p.num_envelopes(series_len) * num_series + delta_envelopes
    shards = mesh.size
    return {
        "envelopes_total": n_env,
        "envelopes_delta": delta_envelopes,
        "envelopes_per_device": -(-n_env // shards),
        "bytes_per_device": -(-n_env // shards) * (2 * p.w + 8),
        "query_wire_bytes": mesh.size * 8 * 2,   # k-NN merge traffic
    }
