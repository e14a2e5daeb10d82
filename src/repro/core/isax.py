"""iSAX symbolization (paper §3.1) + symbol breakpoint geometry.

The real-value space is cut by `card - 1` breakpoints into `card` regions.
For Z-normalized data the breakpoints are standard-normal quantiles (the
classic iSAX choice); for non Z-normalized collections they can be affinely
calibrated to the collection's PAA distribution (`calibrate_breakpoints`),
which is what makes ULISSE's non-normalized mode useful on arbitrary scales.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri


def gaussian_breakpoints(card: int) -> jnp.ndarray:
    """(card - 1,) standard-normal quantile breakpoints."""
    qs = jnp.arange(1, card, dtype=jnp.float32) / card
    return ndtri(qs).astype(jnp.float32)


def calibrate_breakpoints(card: int, sample_paa: jnp.ndarray) -> jnp.ndarray:
    """Affine-calibrate Gaussian breakpoints to a sample of PAA coefficients.

    Used for the non Z-normalized index, where coefficients live on the raw
    scale of the data (paper indexes raw PAA values; a fixed N(0,1) grid
    would collapse all symbols to the extremes).
    """
    bp = gaussian_breakpoints(card)
    mu = jnp.mean(sample_paa)
    sd = jnp.maximum(jnp.std(sample_paa), 1e-6)
    return (mu + sd * bp).astype(jnp.float32)


def symbolize(vals: jnp.ndarray, breakpoints: jnp.ndarray) -> jnp.ndarray:
    """Map real values to symbol indices in [0, card-1].

    symbol k <=> value in [bp[k-1], bp[k])  (bp[-1] = -inf, bp[card-1] = +inf).
    -inf maps to 0, +inf maps to card-1, so "unconstrained" envelope segments
    land on the extreme symbols whose outer breakpoints are +-inf.
    """
    return jnp.searchsorted(breakpoints, vals, side="right").astype(jnp.int32)


def beta_lower(sym: jnp.ndarray, breakpoints: jnp.ndarray) -> jnp.ndarray:
    """beta_l(symbol): lower breakpoint of the symbol's region (-inf for 0)."""
    padded = jnp.concatenate([jnp.array([-jnp.inf], jnp.float32), breakpoints])
    return jnp.take(padded, sym)


def beta_upper(sym: jnp.ndarray, breakpoints: jnp.ndarray) -> jnp.ndarray:
    """beta_u(symbol): upper breakpoint of the symbol's region (+inf for last)."""
    padded = jnp.concatenate([breakpoints, jnp.array([jnp.inf], jnp.float32)])
    return jnp.take(padded, sym)


def pack_sort_key(sym_lo: jnp.ndarray, bits_per_symbol: int = 8) -> jnp.ndarray:
    """Coarse lexicographic iSAX(L) key packed into an int32 (3 symbols).

    Cheap single-key variant of `argsort_by_isax` for shard-local bucketing.
    """
    n_sym = min(3, sym_lo.shape[-1])
    key = jnp.zeros(sym_lo.shape[:-1], jnp.int32)
    for i in range(n_sym):
        key = (key << bits_per_symbol) | sym_lo[..., i].astype(jnp.int32)
    return key


def argsort_by_isax(sym_lo) -> np.ndarray:
    """Stable lexicographic argsort of envelopes by their iSAX(L) word.

    The ULISSE tree accommodates envelopes by iSAX(L) (paper §5.3); the
    TPU-native index replaces pointer chasing with a *sorted* envelope array
    plus a dense block hierarchy, so locality only needs this sort.  A
    host-side (numpy) sort: symbols are < 256 (EnvelopeParams caps card
    at 256), so four consecutive columns pack into one uint32 key, most
    significant first, with the same order — a 16-symbol word is a
    4-key lexsort.
    """
    sym_lo = np.asarray(sym_lo).astype(np.uint32)
    cols = sym_lo.shape[-1]
    sym_lo = np.pad(sym_lo, [(0, 0)] * (sym_lo.ndim - 1)
                    + [(0, -cols % 4)])
    keys = [(sym_lo[..., c] << 24) | (sym_lo[..., c + 1] << 16)
            | (sym_lo[..., c + 2] << 8) | sym_lo[..., c + 3]
            for c in range(0, sym_lo.shape[-1], 4)]
    # lexsort: the last key is primary
    return np.lexsort(tuple(reversed(keys)))
