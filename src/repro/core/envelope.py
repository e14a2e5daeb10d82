"""ULISSE Envelope construction (paper §4, Algorithms 1 and 2).

The paper builds each Envelope with running sums over a sliding window; here
the same recurrences are expressed as prefix-sum gathers so that *all*
anchors of *all* series are built in one data-parallel pass:

  non-normalized (Alg. 1):  a (n_env, gamma+1, w) grid of master-series PAA
    coefficients, min/max-reduced over the master axis;
  Z-normalized (Alg. 2):    a scan over subsequence lengths l' in
    [lmin, lmax]; each step normalizes every master's segment sums by the
    (offset, l') window statistics — O(M * gamma * w) work per envelope,
    identical to the paper's complexity, but batched.

Segments not covered by any represented subsequence get (-inf, +inf) bounds
so they contribute zero to every lower bound (these appear when a series is
barely longer than lmin near its tail).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import isax
from repro.core.types import Collection, EnvelopeParams, EnvelopeSet

_NEG = jnp.float32(-jnp.inf)
_POS = jnp.float32(jnp.inf)


def _anchors(series_len: int, p: EnvelopeParams) -> jnp.ndarray:
    n_env = p.num_envelopes(series_len)
    return jnp.arange(n_env, dtype=jnp.int32) * (p.gamma + 1)


def _master_offsets(series_len: int, p: EnvelopeParams):
    """(n_env, g) master offsets and validity (master fits lmin)."""
    a = _anchors(series_len, p)                                   # (n_env,)
    g = jnp.arange(p.gamma + 1, dtype=jnp.int32)                  # (g,)
    off = a[:, None] + g[None, :]                                 # (n_env, g)
    valid = off + p.lmin <= series_len
    return off, valid


def _segment_sums(csum: jnp.ndarray, off: jnp.ndarray, p: EnvelopeParams):
    """Segment sums for each master offset: (n_env, g, w) + in-series mask."""
    n = csum.shape[-1] - 1
    z = jnp.arange(p.w, dtype=jnp.int32)
    start = off[..., None] + z * p.seg_len                        # (n_env, g, w)
    end = start + p.seg_len
    seg_ok = end <= n
    sums = jnp.take(csum, jnp.clip(end, 0, n)) - jnp.take(csum, jnp.clip(start, 0, n))
    return sums, seg_ok


def _masked_minmax(vals: jnp.ndarray, mask: jnp.ndarray, axis):
    lo = jnp.min(jnp.where(mask, vals, _POS), axis=axis)
    hi = jnp.max(jnp.where(mask, vals, _NEG), axis=axis)
    return lo, hi


def _finalize(lo: jnp.ndarray, hi: jnp.ndarray):
    """Mark never-touched segments as unconstrained (-inf, +inf)."""
    untouched = lo > hi  # +inf > -inf only when no value was accumulated
    lo = jnp.where(untouched, _NEG, lo)
    hi = jnp.where(untouched, _POS, hi)
    return lo, hi


def build_envelopes_raw(series: jnp.ndarray, p: EnvelopeParams):
    """Alg. 1 — non Z-normalized Envelopes for one series.

    series: (n,) float32. Returns (paa_lo, paa_hi): (n_env, w), n_master
    (n_env,).  Lemma 1 makes masters sufficient: every shorter subsequence's
    PAA prefix coincides with its equi-offset master's prefix.
    """
    n = series.shape[-1]
    csum = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                            jnp.cumsum(series.astype(jnp.float32))])
    off, master_ok = _master_offsets(n, p)
    sums, seg_ok = _segment_sums(csum, off, p)
    paa_vals = sums / p.seg_len
    mask = master_ok[..., None] & seg_ok
    lo, hi = _masked_minmax(paa_vals, mask, axis=1)
    lo, hi = _finalize(lo, hi)
    return lo, hi, jnp.sum(master_ok, axis=1).astype(jnp.int32)


def build_envelopes_znorm(series: jnp.ndarray, p: EnvelopeParams):
    """Alg. 2 — Z-normalized Envelopes for one series.

    Scans subsequence lengths l' = lmin..lmax (the paper's Second loop);
    each step evaluates Eq. 2 for every (anchor, master-offset, segment):

        paaNorm(o, l', z) = (segsum(o, z)/s - mu(o, l')) / sigma(o, l')

    subject to (z+1)*s <= l' (segment inside the subsequence) and
    o + l' <= n (subsequence inside the series).
    """
    n = series.shape[-1]
    x = series.astype(jnp.float32)
    center = jnp.mean(x)
    xc = x - center  # shift-invariant: improves float32 conditioning of var
    zero = jnp.zeros((1,), jnp.float32)
    csum = jnp.concatenate([zero, jnp.cumsum(xc)])
    csum2 = jnp.concatenate([zero, jnp.cumsum(xc * xc)])

    off, master_ok = _master_offsets(n, p)              # (n_env, g)
    sums, seg_ok = _segment_sums(csum, off, p)          # (n_env, g, w)
    base_mask = master_ok[..., None] & seg_ok
    seg_mean = sums / p.seg_len

    z_idx = jnp.arange(p.w, dtype=jnp.int32)
    lo0 = jnp.full(seg_mean.shape[:1] + (p.w,), _POS)
    hi0 = jnp.full(seg_mean.shape[:1] + (p.w,), _NEG)

    def step(carry, lprime):
        lo, hi = carry
        end = off + lprime
        sub_ok = end <= n                                # (n_env, g)
        s1 = jnp.take(csum, jnp.clip(end, 0, n)) - jnp.take(csum, jnp.clip(off, 0, n))
        s2 = jnp.take(csum2, jnp.clip(end, 0, n)) - jnp.take(csum2, jnp.clip(off, 0, n))
        mu = s1 / lprime
        var = jnp.maximum(s2 / lprime - mu * mu, 0.0)
        sigma = jnp.maximum(jnp.sqrt(var), 1e-8)
        # segment z inside subsequence of length l': (z+1)*s <= l'
        seg_in = (z_idx + 1) * p.seg_len <= lprime       # (w,)
        vals = (seg_mean - mu[..., None]) / sigma[..., None]
        mask = base_mask & sub_ok[..., None] & seg_in[None, None, :]
        step_lo, step_hi = _masked_minmax(vals, mask, axis=1)
        return (jnp.minimum(lo, step_lo), jnp.maximum(hi, step_hi)), None

    lengths = jnp.arange(p.lmin, p.lmax + 1, dtype=jnp.int32)
    (lo, hi), _ = jax.lax.scan(step, (lo0, hi0), lengths)
    lo, hi = _finalize(lo, hi)
    return lo, hi, jnp.sum(master_ok, axis=1).astype(jnp.int32)


# Series per envelope-build program.  The Z-normalized builder's scan
# over subsequence lengths keeps (series, n_env, g, w) temporaries live,
# so one program over a million series of 256 needs ~26 GB of device
# memory; blocks of 2^15 series need under 1 GB.  The builder is
# per-series, so a blocked build is bit-identical to an unblocked one.
_BUILD_BLOCK = 1 << 15


@partial(jax.jit, static_argnames=("p",))
def _build_block(data: jnp.ndarray, p: EnvelopeParams,
                 breakpoints: jnp.ndarray):
    builder = build_envelopes_znorm if p.znorm else build_envelopes_raw
    lo, hi, n_master = jax.vmap(builder, in_axes=(0, None))(data, p)
    return (lo, hi, n_master, isax.symbolize(lo, breakpoints),
            isax.symbolize(hi, breakpoints))


def build_envelope_set(collection: Collection, p: EnvelopeParams,
                       breakpoints: jnp.ndarray) -> EnvelopeSet:
    """Build the full (unsorted) EnvelopeSet of a collection (paper Alg. 3).

    vmaps the per-series builder over blocks of `_BUILD_BLOCK` stacked
    series (the last block zero-padded, so every block reuses one
    program), then flattens to a struct-of-arrays EnvelopeSet with the
    bounds symbolized by iSAX.
    """
    n = collection.series_len
    n_env = p.num_envelopes(n)
    if n_env == 0:
        raise ValueError(f"series_len={n} shorter than lmin={p.lmin}")

    data = collection.data
    S = collection.num_series
    parts = []
    for s0 in range(0, S, _BUILD_BLOCK):
        blk = data[s0:s0 + _BUILD_BLOCK]
        if S > _BUILD_BLOCK and blk.shape[0] < _BUILD_BLOCK:
            blk = jnp.pad(blk, ((0, _BUILD_BLOCK - blk.shape[0]), (0, 0)))
        parts.append(_build_block(blk, p, breakpoints))
    lo, hi, n_master, sym_lo, sym_hi = (
        jnp.concatenate(x)[:S] if len(parts) > 1 else x[0]
        for x in zip(*parts))

    series_id = jnp.repeat(jnp.arange(S, dtype=jnp.int32), n_env)
    anchor = jnp.tile(_anchors(n, p), S)
    n_master = n_master.reshape(S * n_env)
    return EnvelopeSet(
        paa_lo=lo.reshape(S * n_env, p.w),
        paa_hi=hi.reshape(S * n_env, p.w),
        sym_lo=sym_lo.reshape(S * n_env, p.w),
        sym_hi=sym_hi.reshape(S * n_env, p.w),
        series_id=series_id, anchor=anchor, n_master=n_master,
        valid=n_master > 0,
    )
