"""The ULISSE index (paper §5) — TPU-native layout.

The paper bulk-loads Envelopes into an iSAX tree (inner nodes = envelope
unions, leaves = envelope lists + raw-data pointers) and *additionally*
keeps a flat in-memory envelope list for the exact-search sequential scan
(Alg. 3 line 13).  On an accelerator the pointer tree is replaced by:

  level 0:  the flat EnvelopeSet, lexicographically sorted by iSAX(L) —
            exactly the paper's in-memory list, but sorted so that
            tree-sibling envelopes are physically adjacent;
  level 1+: dense *block* levels: block b at level k is the elementwise
            union (min-L / max-U) of its children — the same envelope-union
            invariant a ULISSE inner node maintains on its subtree.

Best-first tree descent becomes batched top-k over block lower bounds;
pruning semantics are preserved because union(envelopes) only widens
intervals, so mindist(block) <= mindist(member) (tested property).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bounds, isax
from repro.core.envelope import build_envelope_set
from repro.core.paa import paa
from repro.core.types import (Collection, EnvelopeParams, EnvelopeSet,
                              array_module, concat_envelope_sets)

_NEG = jnp.float32(-jnp.inf)
_POS = jnp.float32(jnp.inf)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockLevel:
    """One dense inner level: (Nb, w) envelope unions over child ranges."""

    paa_lo: jnp.ndarray   # (Nb, w)
    paa_hi: jnp.ndarray   # (Nb, w)
    valid: jnp.ndarray    # (Nb,) any child valid

    @property
    def size(self) -> int:
        return self.paa_lo.shape[0]

    def tree_flatten(self):
        return (self.paa_lo, self.paa_hi, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class UlisseIndex:
    """Sorted envelope array + block hierarchy + the raw collection.

    `delta` is the unsorted ingestion buffer of the storage subsystem
    (`repro.storage`): envelopes of series appended after the last build
    or `compact`.  The search layer treats main + delta as one candidate
    set (`search_envelopes`); the block hierarchy covers main only, so
    the approximate descent sweeps the (small) delta exhaustively.

    The iSAX symbols of `envelopes` and `delta` live on the host (the
    sort key, saves and compaction read them).  What the lower bounds
    read of them, the breakpoint intervals [beta_l(iSAX(L)),
    beta_u(iSAX(U))] of every row of `search_envelopes()`, is held on
    the device as `intervals`, two f32 (N, w) arrays in the place the
    symbols took.  They are computed once per index object — build,
    `append` (a new object), `compact`, `open` — never per query.
    """

    envelopes: EnvelopeSet            # sorted by iSAX(L)
    levels: List[BlockLevel]          # coarse -> fine (levels[-1] is finest)
    collection: Collection
    breakpoints: jnp.ndarray          # (card-1,)
    params: EnvelopeParams = None     # static aux
    delta: Optional[EnvelopeSet] = None   # unsorted ingestion buffer
    # (e_lo, e_hi) of search_envelopes(), set by __post_init__
    intervals: Tuple[jnp.ndarray, jnp.ndarray] = dataclasses.field(
        init=False, repr=False, default=None)

    def __post_init__(self):
        self.envelopes = host_symbols(self.envelopes)
        if self.delta is not None:
            self.delta = host_symbols(self.delta)
        self.intervals = envelope_intervals(self.search_envelopes(),
                                            self.breakpoints)

    def tree_flatten(self):
        return (self.envelopes, self.levels, self.collection,
                self.breakpoints, self.delta), self.params

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:4], params=aux, delta=children[4])

    @property
    def num_envelopes(self) -> int:
        return self.envelopes.size

    @property
    def block_size(self) -> int:
        """Children per block (uniform across levels)."""
        if not self.levels:
            return self.envelopes.size
        return self.envelopes.size // self.levels[-1].size

    @property
    def num_levels(self) -> int:
        return len(self.levels)

    def search_envelopes(self) -> EnvelopeSet:
        """The full candidate set: main sorted envelopes ++ delta buffer.

        Rows [0, envelopes.size) are the sorted (padded) main set — block
        b covers rows [b*block_size, (b+1)*block_size) of THIS set too —
        and rows [envelopes.size, ...) are the unsorted delta.  The
        concatenation is cached until the delta buffer is replaced.
        """
        if self.delta is None:
            return self.envelopes
        cached = getattr(self, "_combined_cache", None)
        if cached is None or cached[0] is not self.delta:
            combined = concat_envelope_sets([self.envelopes, self.delta])
            self._combined_cache = cached = (self.delta, combined)
        return cached[1]

    def lb_intervals(self, use_paa: bool = False):
        """The envelope side (e_lo, e_hi), each (N, w), of every lower
        bound over `search_envelopes()`: the held breakpoint intervals
        (paper Eq. 5 / Eq. 8), or the raw L/U PAA bounds when `use_paa`
        (`QuerySpec.use_paa_bounds`)."""
        if use_paa:
            env = self.search_envelopes()
            return env.paa_lo, env.paa_hi
        return self.intervals


SYMBOL_FIELDS = ("sym_lo", "sym_hi")


def host_symbols(env: EnvelopeSet) -> EnvelopeSet:
    """`env` with its iSAX symbols on the host (a no-op when they are)."""
    return dataclasses.replace(env, **{
        f: np.asarray(getattr(env, f)) for f in SYMBOL_FIELDS})


def envelope_intervals(env: EnvelopeSet, breakpoints):
    """The breakpoint intervals of `env` on the device, counted in
    `ulisse_envelope_interval_builds_total`: once per build, append,
    compact or open, and never per query."""
    obs.get_registry().inc(
        "ulisse_envelope_interval_builds_total",
        help_text="Envelope breakpoint-interval builds (index build, "
                  "append, compact, open)")
    return bounds.envelope_breakpoint_bounds(env, breakpoints)


# Padding-row fill per EnvelopeSet field.  +inf lo / -inf hi make
# padding rows unreachable by every lower bound.  The storage Writer
# consumes this table too, so its on-disk padding is bit-identical to
# an in-memory build's — keep it the single source of truth.
PAD_FILL = {"paa_lo": jnp.inf, "paa_hi": -jnp.inf, "sym_lo": 0,
            "sym_hi": 0, "series_id": 0, "anchor": 0, "n_master": 0,
            "valid": False}


def _pad_envelopes(env: EnvelopeSet, multiple: int) -> EnvelopeSet:
    n = env.size
    pad = (-n) % multiple
    if pad == 0:
        return env

    def pad_arr(x, fill):
        cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return array_module(x).pad(x, cfg, constant_values=fill)

    return EnvelopeSet(**{
        field: pad_arr(getattr(env, field), fill)
        for field, fill in PAD_FILL.items()})


def _sort_envelopes(env: EnvelopeSet) -> EnvelopeSet:
    """Padding/invalid rows last, then lexicographic by iSAX(L).

    The order is computed on the host: it runs once per build, an
    XLA:TPU sort over more than ~16k rows takes over a minute to compile
    (compiling for a v5e), and numpy's stable lexsort of millions of
    rows takes seconds."""
    words = np.concatenate([~np.asarray(env.valid)[:, None],
                            np.asarray(env.sym_lo)], axis=1)
    order = isax.argsort_by_isax(words)
    return jax.tree_util.tree_map(
        lambda x: array_module(x).take(x, order, axis=0), env)


def _block_reduce(paa_lo, paa_hi, valid, block: int) -> BlockLevel:
    nb = paa_lo.shape[0] // block
    w = paa_lo.shape[1]
    lo = paa_lo.reshape(nb, block, w)
    hi = paa_hi.reshape(nb, block, w)
    v = valid.reshape(nb, block)
    # union only over valid children (invalid rows carry +inf/-inf already)
    return BlockLevel(
        paa_lo=jnp.min(lo, axis=1),
        paa_hi=jnp.max(hi, axis=1),
        valid=jnp.any(v, axis=1),
    )


def default_breakpoints(p: EnvelopeParams, data: jnp.ndarray) -> jnp.ndarray:
    """Default iSAX breakpoints: N(0,1) quantiles (Z-normalized mode) or
    quantiles calibrated on a PAA sample of the collection (raw mode) —
    shared by the local and distributed backends so their quantization
    never diverges."""
    if p.znorm:
        return isax.gaussian_breakpoints(p.card)
    sample = paa(data[: min(1024, data.shape[0])], p.seg_len)
    return isax.calibrate_breakpoints(p.card, sample)


def build_block_levels(env: EnvelopeSet, block_size: int,
                       num_levels: int) -> List[BlockLevel]:
    """Dense block hierarchy (coarse -> fine) over a sorted, padded set."""
    levels: List[BlockLevel] = []
    lo, hi, valid = env.paa_lo, env.paa_hi, env.valid
    for _ in range(num_levels):
        lvl = _block_reduce(lo, hi, valid, block_size)
        levels.append(lvl)
        lo, hi, valid = lvl.paa_lo, lvl.paa_hi, lvl.valid
    levels.reverse()  # coarse -> fine
    return levels


def index_from_envelopes(env: EnvelopeSet, collection: Collection,
                         p: EnvelopeParams, breakpoints: jnp.ndarray,
                         block_size: int = 64,
                         num_levels: int = 2) -> UlisseIndex:
    """Sort/pad an (unsorted) EnvelopeSet and build the block hierarchy.

    The second half of `build_index`, exposed so the storage subsystem
    (out-of-core builds, delta compaction) can produce indexes from
    envelope sets it assembled itself.  The sort is *stable*, which is
    what makes compaction reproduce a from-scratch build bit-for-bit:
    equal iSAX keys stay in series order regardless of how the set was
    assembled (see repro/storage/delta.py).
    """
    env = _sort_envelopes(host_symbols(env))
    env = _pad_envelopes(env, block_size ** max(num_levels, 1))
    levels = build_block_levels(env, block_size, num_levels)
    return UlisseIndex(envelopes=env, levels=levels, collection=collection,
                       breakpoints=breakpoints, params=p)


def build_index(collection: Collection, p: EnvelopeParams,
                breakpoints: Optional[jnp.ndarray] = None,
                block_size: int = 64, num_levels: int = 2) -> UlisseIndex:
    """ULISSE index computation (paper Alg. 3) on the whole collection.

    breakpoints: defaults to `default_breakpoints` — see isax.py.
    """
    if breakpoints is None:
        breakpoints = default_breakpoints(p, collection.data)

    env = build_envelope_set(collection, p, breakpoints)
    return index_from_envelopes(env, collection, p, breakpoints,
                                block_size=block_size,
                                num_levels=num_levels)


def index_stats(index: UlisseIndex, p: EnvelopeParams) -> dict:
    """Size accounting mirroring the paper's index-property tables."""
    n_env = int(np.asarray(jnp.sum(index.search_envelopes().valid)))
    # paper stores 2w 1-byte symbols + a disk pointer per Envelope
    paper_bytes = n_env * (2 * p.w + 8)
    n_sub = 0
    n = index.collection.series_len
    for l in range(p.lmin, p.lmax + 1):
        n_sub += max(n - l + 1, 0) * index.collection.num_series
    return {
        "num_envelopes": n_env,
        "num_blocks": [lvl.size for lvl in index.levels],
        "index_bytes": paper_bytes,
        # computed from shape, not .data — stats on a freshly opened
        # index must not materialize the lazily-mmap'd raw series
        "raw_bytes": index.collection.num_series
        * index.collection.series_len * 4,
        "subsequences_represented": n_sub,
    }
