"""Core parameter and data types for the ULISSE framework.

All series-level conventions are 0-based:
  - a subsequence (o, l) of series D is D[o : o + l];
  - a *master series* at offset o is D[o : o + min(|D| - o, lmax)];
  - an Envelope anchored at `a` represents every subsequence (o, l) with
    o in [a, a + gamma] and l in [lmin, lmax] that fits inside D.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class EnvelopeParams:
    """Static parameters of the ULISSE summarization (paper §4).

    Attributes:
      lmin / lmax: query length range [l_min, l_max].
      gamma: number of *additional* master series per Envelope; one Envelope
        represents masters at offsets a .. a + gamma (paper's gamma).
      seg_len: PAA segment length `s`.
      card: iSAX alphabet cardinality (paper uses 256 = 8 bits).
      znorm: whether the index represents Z-normalized subsequences.
    """

    lmin: int
    lmax: int
    gamma: int
    seg_len: int
    card: int = 256
    znorm: bool = True

    def __post_init__(self):
        if self.lmin > self.lmax:
            raise ValueError(f"lmin={self.lmin} > lmax={self.lmax}")
        if self.lmin < self.seg_len:
            raise ValueError("lmin must be >= seg_len (need >= 1 PAA segment)")
        if self.gamma < 0:
            raise ValueError("gamma must be >= 0")
        if self.card < 2 or self.card > 256:
            raise ValueError("card must be in [2, 256]")

    @property
    def w(self) -> int:
        """Number of PAA segments covering the longest subsequence."""
        return self.lmax // self.seg_len

    @property
    def n_master(self) -> int:
        """Max number of master series represented by one Envelope."""
        return self.gamma + 1

    def num_envelopes(self, series_len: int) -> int:
        """Number of Envelopes extracted from one series of length n.

        Anchors are a_j = j * (gamma + 1) while a_j + lmin <= n.
        """
        if series_len < self.lmin:
            return 0
        n_start = series_len - self.lmin + 1  # valid master start positions
        return -(-n_start // (self.gamma + 1))  # ceil division

    def query_segments(self, qlen: int) -> int:
        """Number of PAA segments of the longest multiple-of-s query prefix."""
        if not (self.lmin <= qlen <= self.lmax):
            raise ValueError(f"query length {qlen} outside [{self.lmin}, {self.lmax}]")
        return qlen // self.seg_len


def host_prefix_stats(rows: np.ndarray):
    """Per-row f64-accumulated hi/lo split prefix sums, on host.

    The ONE implementation of the Collection's row statistics: both
    `Collection.from_array` (whole collection) and the paged
    `PayloadStore` (one page at a time) call it, so per-page prefix
    sums are bit-identical to whole-collection ones by construction —
    every field is purely row-wise (mean, cumsum, hi/lo split all
    operate within a row), never coupling rows.

    Returns np float32 arrays
    (center (R,), csum (R, n+1), csum_lo, csum2, csum2_lo).
    """
    host = np.asarray(rows, np.float64)
    center64 = host.mean(axis=-1)
    centered = host - center64[:, None]
    zeros = np.zeros((host.shape[0], 1), np.float64)
    csum64 = np.concatenate(
        [zeros, np.cumsum(centered, axis=-1)], axis=-1)
    csum2_64 = np.concatenate(
        [zeros, np.cumsum(centered * centered, axis=-1)], axis=-1)

    def split(x64):
        hi = x64.astype(np.float32)
        lo = (x64 - hi.astype(np.float64)).astype(np.float32)
        return hi, lo

    csum, csum_lo = split(csum64)
    csum2, csum2_lo = split(csum2_64)
    return (center64.astype(np.float32), csum, csum_lo, csum2, csum2_lo)


@dataclasses.dataclass(frozen=True)
class PageBlock:
    """One cached page of a paged payload store: a fixed-size block of
    series rows with their precomputed prefix-sum statistics, all HOST
    numpy float32 (pages are assembled into device slabs by the paged
    scan driver; they never live on device themselves).

    `start` is the first global series id of the page; rows r of the
    page hold global series `start + r`.
    """

    start: int                 # first global series id
    data: np.ndarray           # (R, n) raw values
    csum: np.ndarray           # (R, n + 1) centered cumsum, hi part
    csum_lo: np.ndarray        # (R, n + 1) residual
    csum2: np.ndarray          # (R, n + 1) squared-centered cumsum, hi
    csum2_lo: np.ndarray       # (R, n + 1) residual
    center: np.ndarray         # (R,)

    @classmethod
    def from_rows(cls, start: int, rows: np.ndarray) -> "PageBlock":
        rows = np.ascontiguousarray(rows, np.float32)
        center, csum, csum_lo, csum2, csum2_lo = host_prefix_stats(rows)
        return cls(start=start, data=rows, csum=csum, csum_lo=csum_lo,
                   csum2=csum2, csum2_lo=csum2_lo, center=center)

    @property
    def num_rows(self) -> int:
        return self.data.shape[0]

    @property
    def nbytes(self) -> int:
        return (self.data.nbytes + self.csum.nbytes
                + self.csum_lo.nbytes + self.csum2.nbytes
                + self.csum2_lo.nbytes + self.center.nbytes)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Collection:
    """A data series collection: fixed-length series stacked in one array.

    `data` is (num_series, series_len) float32.  Running sums are kept for
    O(1) window statistics (paper Alg. 2 keeps accSum / accSqSum; here they
    are materialized as cumulative arrays so every (offset, length) window's
    mean / std is a 2-gather).  Series are centered per-series before the
    squared cumsum to keep float32 variance computation well-conditioned
    (Z-normalization is invariant to per-series shifts).

    The prefix sums are accumulated in float64 and stored as a two-float
    (hi, lo) split: `csum` holds the float32 rounding of the exact sum and
    `csum_lo` the float32 residual.  A window sum recovered as
    (hi[e]-hi[s]) + (lo[e]-lo[s]) has error ~eps_f32 * |window sum| instead
    of ~eps_f32 * |prefix sum| — the catastrophic-cancellation term that
    grows with series length / offset is gone, so device-scan distances
    track the host's direct mean/var to float32 roundoff at any offset.
    """

    data: jnp.ndarray          # (S, n) raw values
    csum: jnp.ndarray          # (S, n + 1) centered cumsum, f32 hi part
    csum2: jnp.ndarray         # (S, n + 1) squared-centered cumsum, hi part
    center: jnp.ndarray        # (S,) per-series mean removed before csum/csum2
    csum_lo: jnp.ndarray = None    # (S, n + 1) f32 residual of csum
    csum2_lo: jnp.ndarray = None   # (S, n + 1) f32 residual of csum2

    @classmethod
    def from_array(cls, data) -> "Collection":
        data = jnp.asarray(data, jnp.float32)
        if data.ndim == 1:
            data = data[None]
        if isinstance(data, jax.core.Tracer):
            # traced context (distributed shard programs build per-shard
            # Collections in-graph): float32 sums, zero residuals — those
            # programs verify via masked windows, not the prefix sums
            center = jnp.mean(data, axis=-1)
            centered = data - center[:, None]
            zeros = jnp.zeros((data.shape[0], 1), jnp.float32)
            csum = jnp.concatenate(
                [zeros, jnp.cumsum(centered, axis=-1)], axis=-1)
            csum2 = jnp.concatenate(
                [zeros, jnp.cumsum(centered * centered, axis=-1)], axis=-1)
            return cls(data=data, csum=csum, csum2=csum2, center=center,
                       csum_lo=jnp.zeros_like(csum),
                       csum2_lo=jnp.zeros_like(csum2))
        center, csum, csum_lo, csum2, csum2_lo = \
            host_prefix_stats(np.asarray(data))
        return cls(data=data, csum=jnp.asarray(csum),
                   csum2=jnp.asarray(csum2),
                   center=jnp.asarray(center),
                   csum_lo=jnp.asarray(csum_lo),
                   csum2_lo=jnp.asarray(csum2_lo))

    @property
    def num_series(self) -> int:
        return self.data.shape[0]

    @property
    def series_len(self) -> int:
        return self.data.shape[1]

    def window_stats(self, sid, off, length):
        """(mean, std) of windows data[sid, off : off + length] (vectorized)."""
        s1 = (self.csum[sid, off + length] - self.csum[sid, off]) \
            + (self.csum_lo[sid, off + length] - self.csum_lo[sid, off])
        s2 = (self.csum2[sid, off + length] - self.csum2[sid, off]) \
            + (self.csum2_lo[sid, off + length] - self.csum2_lo[sid, off])
        mu_c = s1 / length
        var = jnp.maximum(s2 / length - mu_c * mu_c, 0.0)
        return mu_c + self.center[sid], jnp.sqrt(var)

    def tree_flatten(self):
        return (self.data, self.csum, self.csum2, self.center,
                self.csum_lo, self.csum2_lo), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EnvelopeSet:
    """A flat array-of-structs set of ULISSE Envelopes.

    Shapes: N = number of envelopes, w = PAA segments.
      paa_lo / paa_hi : (N, w) float32 — real-valued L / U PAA bounds.
      sym_lo / sym_hi : (N, w) int32   — iSAX(L) / iSAX(U) symbols
                        (numpy, on the host, in a UlisseIndex).
      series_id       : (N,)  int32    — source series in the Collection.
      anchor          : (N,)  int32    — first master offset `a`.
      n_master        : (N,)  int32    — number of valid masters (<= gamma+1).
      valid           : (N,)  bool     — padding mask (False = padding row).

    Segments never touched by any represented subsequence carry
    paa_lo=-inf / paa_hi=+inf so they contribute zero to every lower bound.
    """

    paa_lo: jnp.ndarray
    paa_hi: jnp.ndarray
    sym_lo: jnp.ndarray
    sym_hi: jnp.ndarray
    series_id: jnp.ndarray
    anchor: jnp.ndarray
    n_master: jnp.ndarray
    valid: jnp.ndarray

    @property
    def size(self) -> int:
        return self.paa_lo.shape[0]

    @property
    def w(self) -> int:
        return self.paa_lo.shape[1]

    def tree_flatten(self):
        return (
            self.paa_lo, self.paa_hi, self.sym_lo, self.sym_hi,
            self.series_id, self.anchor, self.n_master, self.valid,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def array_module(x):
    """numpy for a host array, jax.numpy otherwise — so a per-field
    operation on an EnvelopeSet leaves each field where it lives (a
    UlisseIndex keeps its symbols on the host)."""
    return np if isinstance(x, np.ndarray) else jnp


def concat_envelope_sets(sets) -> EnvelopeSet:
    """Row-concatenate EnvelopeSets; a field held on the host by any
    part stays on the host."""
    def cat(*xs):
        if any(array_module(x) is np for x in xs):
            return np.concatenate([np.asarray(x) for x in xs])
        return jnp.concatenate(xs, axis=0)
    return jax.tree_util.tree_map(cat, *sets)


def concat_collections(a: Collection, b: Collection) -> Collection:
    """Stack two same-length collections along the series axis.

    Every Collection field is per-series (row-wise), so concatenating the
    precomputed fields equals `Collection.from_array` of the concatenated
    raw data — the invariant incremental ingestion relies on.
    """
    if a.series_len != b.series_len:
        raise ValueError(
            f"cannot concat collections of series_len {a.series_len} "
            f"and {b.series_len}")
    return Collection(
        data=jnp.concatenate([a.data, b.data], axis=0),
        csum=jnp.concatenate([a.csum, b.csum], axis=0),
        csum2=jnp.concatenate([a.csum2, b.csum2], axis=0),
        center=jnp.concatenate([a.center, b.center], axis=0),
        csum_lo=jnp.concatenate([a.csum_lo, b.csum_lo], axis=0),
        csum2_lo=jnp.concatenate([a.csum2_lo, b.csum2_lo], axis=0),
    )
