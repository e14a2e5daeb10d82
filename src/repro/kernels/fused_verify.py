"""Pallas kernels: fused candidate-window gather + verification.

The host-driven exact scan gathers candidate windows into an (M, qlen)
HBM array (`executor.gather_windows`) and then runs a separate distance
kernel over it.  The device-resident scan (`executor.device_exact_scan`)
instead calls these kernels inside its `lax.while_loop`; the candidate
windows never exist as an HBM (let alone host) array.  Three ideas make
the fusion fast:

  * region gather — an envelope's g = gamma+1 candidate windows overlap
    pairwise in qlen-1 points, so each row of a chunk gathers ONE
    (qlen+g-1) region instead of g full windows (a ~g-fold cut in
    gather traffic);
  * banded-Toeplitz correlation — the per-offset query dots
    dots[e, j] = sum_t region[e, j+t] * q[t] are one (rows, reg) @
    (reg, g) matmul against a banded Toeplitz expansion of the query
    (MXU-shaped, ~reg*g flops per envelope, no im2col materialization);
  * prefix-sum window stats — per-window mean/std come from the
    Collection's precomputed centered csum/csum2 (paper Alg. 2's
    accSum/accSqSum) as two O(1) gathers per window, not an O(qlen)
    reduction.

Two fusions cover the ED / DTW cascade: `fused_gather_ed` finishes with
the dot-product ED identity; `fused_gather_lb_keogh` normalizes each
region window in place, accumulates squared LB_Keogh per offset, and
also emits the per-window (mu, sd) so the banded-DP tier can normalize
its survivor windows IDENTICALLY — the LB <= DTW invariant then holds
exactly (both tiers see the same normalized values), which is what makes
on-device pruning sound.

Memory layout on the TPU.  `data` stays in HBM (`memory_space=ANY`);
the chunk's (sid, anchor) pairs arrive as scalar-prefetch SMEM arrays
and drive one DMA per row.  XLA tiles an (S, n) f32 array (8, 128), so
a single row is not addressable: each DMA fetches the row's aligned
8-row group into a double-buffered VMEM landing tile (the next tile of
rows is in flight while the current one computes), the row is selected
from its group, rotated so that lane 0 is its anchor, and the lanes
past the series end are zeroed.  The query operands use block shapes
whose last two dims are whole (`(1, reg, 128)` Toeplitz, `(1, 1, qlen)`
envelopes), which the tiling accepts for any batch size.

The prefix sums arrive as a two-float (hi, lo) split of an exact
float64 accumulation (types.Collection), so the stats path tracks the
host's direct mean/var to ordinary f32 roundoff at ANY series
length/offset (DESIGN.md §8).  Their (S, n+1) shape gets a column-major
device layout from XLA (n+1 is not a lane multiple), so a row span is
strided by S and no DMA can fetch it; the O(1)-per-window stats are
therefore XLA gathers in the kernel wrappers, and the kernels do the
O(qlen) work: the region gather, the Toeplitz matmul and LB_Keogh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, SUBLANES, default_interpret, round_up

_TILE = 128                          # rows gathered + computed per step
_HIGHEST = jax.lax.Precision.HIGHEST   # f32 MXU passes, not one bf16 pass


def toeplitz_query(qs: jnp.ndarray, g: int) -> jnp.ndarray:
    """Banded Toeplitz expansion: qmat[b, i, j] = q_b[i - j] (else 0).

    (B, qlen) -> (B, qlen+g-1, g); region @ qmat computes all g window
    dots at once.  Query-only, so the scan hoists it out of its chunk
    loop.
    """
    qlen = qs.shape[-1]
    reg = qlen + g - 1
    i = jnp.arange(reg)[:, None]
    j = jnp.arange(g)[None, :]
    qpad = jnp.concatenate(
        [qs, jnp.zeros(qs.shape[:-1] + (1,), qs.dtype)], -1)
    idx = jnp.where((i >= j) & (i - j < qlen), i - j, qlen)
    return jnp.take(qpad, idx, axis=-1)


def _region_tiles(sid_ref, anc_ref, data_ref, land, rbuf, sems, compute,
                  *, rows: int, tile: int):
    """Gather grid step b's chunk regions, `tile` rows at a time.

    Row e of the chunk is data[sids[b*rows+e], anchors[...]:]; after
    the gather, rbuf row i holds it with lane 0 at the anchor and zeros
    past the series end, so its windows j < g read lanes [j, j+qlen).
    A window that overruns its series is garbage, and the caller masks
    it via the usual (j < n_master) & (off + qlen <= n) test.  Tile t+1's
    DMAs are started before tile t is waited on, and `compute(r0,
    region)` runs on each finished (tile, wd) region block.
    """
    base = pl.program_id(0) * rows
    s8, n = data_ref.shape
    wd = rbuf.shape[1]
    n_tiles = rows // tile

    def group_of(e):
        sid = jnp.clip(sid_ref[base + e], 0, s8 - 1)
        return pl.multiple_of(sid // SUBLANES * SUBLANES, SUBLANES), sid

    def copy(e, slot, r8):
        return pltpu.make_async_copy(
            data_ref.at[pl.ds(r8, SUBLANES)],
            land.at[slot, e % tile, :, pl.ds(0, n)], sems.at[slot])

    def start_tile(t):
        def body(i, carry):
            r8, _ = group_of(t * tile + i)
            copy(t * tile + i, t % 2, r8).start()
            return carry
        jax.lax.fori_loop(0, tile, body, 0)

    start_tile(0)

    def tile_body(t, carry):
        @pl.when(t + 1 < n_tiles)
        def _():
            start_tile(t + 1)

        def row(i, c):
            e = t * tile + i
            r8, sid = group_of(e)
            copy(e, t % 2, 0).wait()
            grp = land[t % 2, i]                            # (8, wd)
            sub = jax.lax.broadcasted_iota(jnp.int32, grp.shape, 0)
            x = jnp.sum(jnp.where(sub == sid - r8, grp, 0.0), axis=0,
                        keepdims=True)                       # (1, wd)
            anc = jnp.clip(anc_ref[base + e], 0, n)
            x = pltpu.roll(x, (wd - anc) % wd, 1)
            lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            rbuf[pl.ds(i, 1), :] = jnp.where(lane < n - anc, x, 0.0)
            return c

        jax.lax.fori_loop(0, tile, row, 0)
        compute(pl.multiple_of(t * tile, tile), rbuf[...])
        return carry

    jax.lax.fori_loop(0, n_tiles, tile_body, 0)


def _dots_kernel(sid_ref, anc_ref, data_ref, qmat_ref, out_ref, land,
                 rbuf, sems, *, g: int, rows: int, tile: int):
    regp = qmat_ref.shape[1]

    def compute(r0, region):
        dots = jnp.dot(region[:, :regp], qmat_ref[0], precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
        out_ref[0, pl.ds(r0, tile), :] = dots[:, :g]

    _region_tiles(sid_ref, anc_ref, data_ref, land, rbuf, sems, compute,
                  rows=rows, tile=tile)


def _lb_keogh_kernel(sid_ref, anc_ref, data_ref, lo_ref, hi_ref, mu_ref,
                     sd_ref, lb_ref, land, rbuf, sems, *, g: int,
                     rows: int, tile: int):
    qp = lo_ref.shape[2]
    lo = lo_ref[0]                                  # (1, qp), -inf pad
    hi = hi_ref[0]                                  # (1, qp), +inf pad

    def compute(r0, region):
        mu = mu_ref[0, pl.ds(r0, tile), :]
        sd = sd_ref[0, pl.ds(r0, tile), :]
        wd = region.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (tile, g), 1)
        acc = jnp.zeros((tile, g), jnp.float32)
        for j in range(g):   # static offsets: lane rotations, no gather
            w = (pltpu.roll(region, wd - j, 1) if j else region)[:, :qp]
            w = (w - mu[:, j:j + 1]) / sd[:, j:j + 1]
            over = jnp.maximum(w - hi, 0.0)
            under = jnp.maximum(lo - w, 0.0)
            col = jnp.sum(over * over + under * under, axis=1,
                          keepdims=True)
            acc = jnp.where(lane == j, col, acc)
        lb_ref[0, pl.ds(r0, tile), :] = acc

    _region_tiles(sid_ref, anc_ref, data_ref, land, rbuf, sems, compute,
                  rows=rows, tile=tile)


def _region_call(kernel, data, sids, anchors, blocked, *, g: int,
                 rows: int, reg: int, interpret: bool):
    """One grid step per query over the region gather; `blocked` are
    (B, ...) per-query operands, the output is (B, rows, g)."""
    s, n = data.shape
    if s % SUBLANES:
        # the gather fetches whole 8-row groups: pad the last one
        data = jnp.pad(data, ((0, SUBLANES - s % SUBLANES), (0, 0)))
    b = blocked[0].shape[0]
    tile = _TILE if rows % _TILE == 0 else rows
    wd = round_up(max(n, reg), LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [
            pl.BlockSpec((1,) + x.shape[1:], lambda i, *_: (i, 0, 0))
            for x in blocked],
        out_specs=pl.BlockSpec((1, rows, g), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, tile, SUBLANES, wd), jnp.float32),
                        pltpu.VMEM((tile, wd), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(kernel, g=g, rows=rows, tile=tile),
        out_shape=jax.ShapeDtypeStruct((b, rows, g), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(sids, anchors, data, *blocked)
    return out.reshape(b * rows, g)


def _window_stats(sids, anchors, csum, csum2, cslo, cs2lo, *, g: int,
                  qlen: int):
    """(s1, s2, mu_c, sd): centered window sums and Z-norm stats of
    every candidate, each (rows, g).

    The prefix sums arrive as a two-float (hi, lo) split of the exact
    float64 accumulation (see types.Collection); summing the hi and lo
    differences recovers the window sum to ~f32 roundoff of the *window*
    sum — the cancellation error no longer grows with the offset.
    """
    n = csum.shape[1] - 1
    offs = jnp.clip(anchors[:, None] + jnp.arange(g, dtype=jnp.int32), 0,
                    n - qlen)
    row = sids[:, None]

    def wsum(hi, lo):
        return ((hi[row, offs + qlen] - hi[row, offs])
                + (lo[row, offs + qlen] - lo[row, offs]))

    s1 = wsum(csum, cslo)
    s2 = wsum(csum2, cs2lo)
    mu_c = s1 / qlen
    var = s2 / qlen - mu_c * mu_c
    sd = jnp.maximum(jnp.sqrt(jnp.maximum(var, 0.0)), 1e-8)
    return s1, s2, mu_c, sd


@functools.partial(jax.jit,
                   static_argnames=("g", "rows", "znorm", "interpret"))
def fused_gather_ed(data: jnp.ndarray, csum: jnp.ndarray,
                    csum2: jnp.ndarray, csum_lo: jnp.ndarray,
                    csum2_lo: jnp.ndarray, center: jnp.ndarray,
                    sids: jnp.ndarray, anchors: jnp.ndarray,
                    qs: jnp.ndarray, *, g: int, rows: int, znorm: bool,
                    interpret: Optional[bool] = None):
    """Squared ED of B queries' candidate chunks, one grid step each.

    data (S, n) + its Collection prefix sums csum/csum2 with their f32
    residuals csum_lo/csum2_lo (each (S, n+1)) and per-series center
    (S,); sids/anchors (B * rows,) int32 — query b's chunk is rows
    [b*rows, (b+1)*rows); qs (B, qlen) prepared queries (already
    Z-normalized when znorm).  Returns (B * rows, g) float32 — entry
    (e, j) is d2(q_b, data[sids[e], anchors[e]+j : +qlen]); windows
    overrunning their series are garbage (mask with the validity test).
    `interpret=None` follows `default_interpret()`.
    """
    if interpret is None:
        interpret = default_interpret()
    b, qlen = qs.shape
    reg = qlen + g - 1
    qmat = toeplitz_query(qs, g)                 # (B, reg, g)
    qmat = jnp.pad(qmat, ((0, 0), (0, round_up(reg, LANES) - reg),
                          (0, round_up(g, LANES) - g)))
    dots = _region_call(_dots_kernel, data, sids, anchors, [qmat], g=g,
                        rows=rows, reg=reg, interpret=interpret)
    s1, s2, _, sd = _window_stats(sids, anchors, csum, csum2, csum_lo,
                                  csum2_lo, g=g, qlen=qlen)
    if znorm:
        d2 = 2.0 * qlen - 2.0 * dots / sd
    else:
        c = jnp.take(center, sids)[:, None]
        wss = s2 + 2.0 * c * s1 + qlen * c * c   # un-centered sum(w^2)
        qq = jnp.repeat(jnp.sum(qs * qs, axis=1), rows)[:, None]
        d2 = wss - 2.0 * dots + qq
    return jnp.maximum(d2, 0.0)


@functools.partial(jax.jit,
                   static_argnames=("g", "rows", "znorm", "interpret"))
def fused_gather_lb_keogh(data: jnp.ndarray, csum: jnp.ndarray,
                          csum2: jnp.ndarray, csum_lo: jnp.ndarray,
                          csum2_lo: jnp.ndarray, center: jnp.ndarray,
                          sids: jnp.ndarray, anchors: jnp.ndarray,
                          dtw_lo: jnp.ndarray, dtw_hi: jnp.ndarray, *,
                          g: int, rows: int, znorm: bool,
                          interpret: Optional[bool] = None):
    """Fused gather + normalize + squared LB_Keogh, one step per query.

    Layout as in fused_gather_ed; dtw_lo/dtw_hi are the (B, qlen) query
    DTW envelopes.  Returns (lb2, mu, sd) each (B * rows, g) float32 —
    mu/sd are the window normalization the banded-DP tier must reuse on
    LB survivors so its distances can never undercut the bound (raw
    mode returns mu=0 / sd=1).
    """
    if interpret is None:
        interpret = default_interpret()
    b, qlen = dtw_lo.shape
    if znorm:
        _, _, mu_c, sd = _window_stats(sids, anchors, csum, csum2,
                                       csum_lo, csum2_lo, g=g, qlen=qlen)
        mu = mu_c + jnp.take(center, sids)[:, None]
    else:
        mu = jnp.zeros((b * rows, g), jnp.float32)
        sd = jnp.ones((b * rows, g), jnp.float32)
    # lanes past qlen get an envelope no value can leave: they add 0
    pad = ((0, 0), (0, round_up(qlen, LANES) - qlen))
    lo = jnp.pad(dtw_lo, pad, constant_values=-jnp.inf)[:, None, :]
    hi = jnp.pad(dtw_hi, pad, constant_values=jnp.inf)[:, None, :]
    lb2 = _region_call(
        _lb_keogh_kernel, data, sids, anchors,
        [lo, hi, mu.reshape(b, rows, g), sd.reshape(b, rows, g)], g=g,
        rows=rows, reg=qlen + g - 1, interpret=interpret)
    return lb2, mu, sd
