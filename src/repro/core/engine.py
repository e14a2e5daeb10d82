"""`UlisseEngine`: one planner/executor surface over local, batched, and
distributed ULISSE search.

The paper's value proposition — a *single* index answering k-NN and
eps-range queries of any length in [lmin, lmax], under ED or DTW, raw or
Z-normalized (§6) — is exposed through a single call:

    engine = UlisseEngine.from_collection(coll, params)      # local
    engine = UlisseEngine.distributed(mesh, params, data)    # sharded
    res = engine.search(q, QuerySpec(k=5))                   # one query
    ress = engine.search(q_batch, QuerySpec(k=5))            # many queries

`QuerySpec` absorbs the formerly scattered kwargs of approx_knn /
exact_knn / range_query / make_distributed_query.  Both backends route
`scan_backend="device"` (the default) through the same device-resident
scan core: locally the one-sync pipeline of DESIGN.md §8/§9;
distributed, the sharded pruned scan of §10 — every shard runs the
scan core over its own LB-ordered pack inside shard_map, prunes
against the periodically broadcast global best-so-far, and one
cross-shard merge returns the exact answer, so exactness is structural
and the full measure/mode/range matrix works on a mesh.  Up to
`max_batch` queries batch into one device program; one compiled
program object serves every query length (retraced per shape).
`scan_backend="host"` keeps the reference oracles: the chunked
host-driven loops locally, and distributed the legacy PR-1 unpruned
per-shard verify whose exactness certificate is enforced by an
internal escalation loop (doubled `verify_top` until it holds).
"""
from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor, planner
from repro.core.executor import SearchResult, SearchStats, TopK
from repro.core.index import UlisseIndex, build_index
from repro.core.types import Collection, EnvelopeParams
from repro.obs import device_stage, span


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """Everything about a query except its values.

    measure: "ed" | "dtw" (DTW needs a warping window r > 0).
    k:       neighbors returned (k-NN queries; ignored when eps is set).
    eps:     when set, the query is an eps-range query (all subsequences
             within eps), mode/k are ignored.
    mode:    "exact" (paper Alg. 5 guarantee) | "approx" (Alg. 4 descent).
    approx_first:   seed the exact scan with an approximate pass (Alg. 5
                    line 1; disable to measure the pure scan).
    scan_backend:   "device" (default) runs every query shape —
                    approximate pass, exact scan, and eps-range — as
                    device programs with ONE host sync per same-length
                    query batch; on the distributed backend this is the
                    sharded pruned scan (every shard runs the device
                    scan core over its own LB pack, pruning against the
                    broadcast global bsf — DESIGN.md §10) and supports
                    the full measure/mode/range matrix.  "host" keeps
                    the chunked host-driven loops — the reference paths
                    the device pipeline is asserted equal against
                    (distributed "host" is the legacy PR-1 unpruned
                    per-shard verify: exact ED k-NN only).
    chunk_size:     exact-scan verification chunk (envelopes per step).
    verify_top:     legacy distributed host backend only: per-shard
                    verification batch (initial value; the engine
                    doubles it on certificate failure).  The sharded
                    device scan needs no escalation — its pruned scan
                    runs to convergence, so exactness is structural.
    sync_every:     sharded scan only: chunks each shard scans between
                    global bsf broadcasts (1 = share after every chunk;
                    large values approach independent per-shard scans
                    merged once at the end).
    max_leaves:     approx-descent leaf budget (per shard, in chunks of
                    `chunk_size`, on the distributed device backend).
    range_capacity: on-device hit-buffer rows per range query (rounded
                    up to a power of two); a query whose hits exceed it
                    falls back to a host continuation for the scan tail
                    (DESIGN.md §9).
    use_paa_bounds: use raw L/U PAA bounds instead of the quantized iSAX
                    breakpoints in the exact scan (tighter, beyond-paper).
    """

    measure: str = "ed"
    r: int = 0
    k: int = 1
    eps: Optional[float] = None
    mode: str = "exact"
    approx_first: bool = True
    scan_backend: str = "device"
    chunk_size: int = 512
    verify_top: int = 128
    sync_every: int = 8
    max_leaves: int = 8
    range_capacity: int = 2048
    use_paa_bounds: bool = False

    def __post_init__(self):
        if self.measure not in ("ed", "dtw"):
            raise ValueError(f"unknown measure {self.measure!r}")
        if self.mode not in ("exact", "approx"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.scan_backend not in ("device", "host"):
            raise ValueError(
                f"unknown scan_backend {self.scan_backend!r}")
        if self.measure == "dtw" and self.r <= 0:
            raise ValueError("DTW search needs a warping window r > 0")
        if self.k < 1:
            raise ValueError("k must be >= 1")
        if self.eps is not None and self.eps < 0:
            raise ValueError("eps must be >= 0")
        if self.chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if self.verify_top < 1:
            raise ValueError("verify_top must be >= 1")
        if self.sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        if self.range_capacity < 1:
            raise ValueError("range_capacity must be >= 1")

    @property
    def is_range(self) -> bool:
        return self.eps is not None


def _pow2_bucket(qlen: int, cap: int) -> int:
    return planner.length_bucket(qlen, cap)


def _knn_budget(spec: "QuerySpec") -> int:
    """Per-shard approx leaf budget folded into the sharded knn program
    (0 = exact: the pruned scan runs to convergence)."""
    return spec.max_leaves if spec.mode == "approx" else 0


# --------------------------------------------------------------------------
# R4 source of truth (repro.analysis retrace-key-coverage): one entry per
# compiled-program family.  `key` is THE cache-key constructor the engine
# itself uses (the auditor calls the same callable, so declaration cannot
# drift from behavior); `not_in_key` declares, with a reason, every
# QuerySpec field deliberately absent from the key — a field in neither
# is a finding, which is exactly what happens when someone adds a
# trace-relevant QuerySpec field and forgets to hash it.
# --------------------------------------------------------------------------

PROGRAM_KEY_SPECS = {
    "sharded_knn": {
        "key": lambda s: ("knn", s.k, s.measure, s.r, s.chunk_size,
                          s.sync_every, _knn_budget(s), s.use_paa_bounds),
        "not_in_key": {
            "eps": "selects the range family instead of this one",
            "approx_first": "local-backend composition knob; the "
                            "sharded scan always seeds in-graph",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "range_capacity": "range family only",
            # mode/max_leaves ARE in the key, folded through the
            # _knn_budget extra
        },
    },
    "sharded_range": {
        "key": lambda s: ("range", s.range_capacity, s.measure, s.r,
                          s.chunk_size, s.use_paa_bounds),
        "not_in_key": {
            "k": "a range query returns every hit, k is ignored",
            "eps": "runtime operand (the (B,) eps2 array), not a trace "
                   "constant",
            "mode": "range queries have no exact/approx split",
            "approx_first": "range queries run no approximate pass",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "sync_every": "the eps cut never moves, so the range scan "
                          "broadcasts no global bsf",
            "max_leaves": "approx-descent knob, knn family only",
        },
    },
    "sharded_delta_knn": {
        # the delta/ingestion k-NN family (DESIGN.md §15): same spec
        # fields as sharded_knn, distinct prefix — the program differs
        # structurally (15th gmap input, delta-first pack).  The
        # per-shard delta geometry (delta env rows) joins the key at
        # the call site like legacy_host_knn's bucket: it is engine
        # state, not a QuerySpec field, and every append changes it.
        "key": lambda s: ("delta_knn", s.k, s.measure, s.r,
                          s.chunk_size, s.sync_every, _knn_budget(s),
                          s.use_paa_bounds),
        "not_in_key": {
            "eps": "selects the range family instead of this one",
            "approx_first": "local-backend composition knob; the "
                            "sharded scan always seeds in-graph",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "range_capacity": "range family only",
            # mode/max_leaves ARE in the key, folded through the
            # _knn_budget extra
        },
    },
    "sharded_delta_range": {
        # delta/ingestion range family: gmap globalization only — the
        # range pack is sortless, so no delta-first region; the
        # per-shard row count (main + delta env rows) joins the key at
        # the call site (engine state, changes on append/compact)
        "key": lambda s: ("delta_range", s.range_capacity, s.measure,
                          s.r, s.chunk_size, s.use_paa_bounds),
        "not_in_key": {
            "k": "a range query returns every hit, k is ignored",
            "eps": "runtime operand (the (B,) eps2 array), not a trace "
                   "constant",
            "mode": "range queries have no exact/approx split",
            "approx_first": "range queries run no approximate pass",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "sync_every": "the eps cut never moves, so the range scan "
                          "broadcasts no global bsf",
            "max_leaves": "approx-descent knob, knn family only",
        },
    },
    "local_scan": {
        # the real cache is executor._device_scan_program's lru_cache on
        # (k, g, chunk, znorm, measure, r, sb, interpret); the
        # spec-derived components are exactly these
        "key": lambda s: ("local_scan", s.k, s.measure, s.r,
                          s.chunk_size),
        "not_in_key": {
            "eps": "selects the range family instead of this one",
            "mode": "selects program composition (approx stage alone vs "
                    "seeded scan); each constituent is keyed by its own "
                    "static chunk",
            "approx_first": "composition knob — adds/removes the "
                            "leaf-pack stage, never retraces the core",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "sync_every": "sharded scan only",
            "max_leaves": "shapes the leaf pack (n_pad); jit retraces "
                          "on operand shape, not via the key",
            "range_capacity": "range family only",
            "use_paa_bounds": "changes LB operand values only — same "
                              "program, different data",
        },
    },
    "local_range": {
        "key": lambda s: ("local_range", s.range_capacity, s.measure,
                          s.r, s.chunk_size),
        "not_in_key": {
            "k": "a range query returns every hit, k is ignored",
            "eps": "runtime operand (the (B,) eps2 array), not a trace "
                   "constant",
            "mode": "range queries have no exact/approx split",
            "approx_first": "range queries run no approximate pass",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "sync_every": "sharded scan only",
            "max_leaves": "approx-descent knob, knn family only",
            "use_paa_bounds": "changes LB operand values only — same "
                              "program, different data",
        },
    },
    "local_paged": {
        # the real cache is executor._paged_scan_chunk_program's
        # lru_cache on (k, g, chunk, znorm, measure, r, sb, interpret);
        # the spec-derived components match local_scan exactly — the
        # paged chunk program IS one monolithic body iteration.  The
        # slab row count is operand shape (pow2-padded), so jit
        # retraces per slab-size bucket, not via the key.
        "key": lambda s: ("local_paged", s.k, s.measure, s.r,
                          s.chunk_size),
        "not_in_key": {
            "eps": "selects the paged range family instead of this one",
            "mode": "selects program composition (approx stage alone vs "
                    "seeded scan); each constituent is keyed by its own "
                    "static chunk",
            "approx_first": "composition knob — adds/removes the "
                            "leaf-pack stage, never retraces the core",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "sync_every": "sharded scan only (the paged early-stop "
                          "cadence is a host-loop constant, not traced)",
            "max_leaves": "shapes the leaf pack (n_pad); jit retraces "
                          "on operand shape, not via the key",
            "range_capacity": "range family only",
            "use_paa_bounds": "changes LB operand values only — same "
                              "program, different data",
        },
    },
    "local_paged_range": {
        "key": lambda s: ("local_paged_range", s.range_capacity,
                          s.measure, s.r, s.chunk_size),
        "not_in_key": {
            "k": "a range query returns every hit, k is ignored",
            "eps": "runtime operand (the (B,) eps2 array), not a trace "
                   "constant",
            "mode": "range queries have no exact/approx split",
            "approx_first": "range queries run no approximate pass",
            "scan_backend": "selects whether this family compiles at all",
            "verify_top": "legacy host-backend escalation knob",
            "sync_every": "sharded scan only (the paged early-stop "
                          "cadence is a host-loop constant, not traced)",
            "max_leaves": "approx-descent knob, knn family only",
            "use_paa_bounds": "changes LB operand values only — same "
                              "program, different data",
        },
    },
    "legacy_host_knn": {
        # bucket joins the key at the call site (shape-derived, not a
        # QuerySpec field); verify_top enters clamped to the per-shard
        # row cap
        "key": lambda s: ("legacy", s.k, s.verify_top),
        "not_in_key": {
            "measure": "rejected at dispatch (legacy path is exact ED "
                       "k-NN only)",
            "r": "DTW-only parameter; rejected at dispatch",
            "eps": "rejected at dispatch",
            "mode": "rejected at dispatch",
            "approx_first": "the legacy path runs no approximate pass",
            "scan_backend": "selects whether this family compiles at all",
            "chunk_size": "host-loop batching knob, not traced",
            "sync_every": "sharded pruned scan only",
            "max_leaves": "approx-descent knob",
            "range_capacity": "range family only",
            "use_paa_bounds": "rejected at dispatch",
        },
    },
}


def _shards_of(mesh, axes) -> int:
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    return shards


def _require_divisible(num_series: int, mesh, axes) -> int:
    """Refuse meshes that do not divide the collection evenly.

    A truncated rows-per-shard table under-counts the verification cap,
    so escalation would declare a shard "fully verified" while rows
    were never checked — silent wrong answers.  Returns the shard
    count.
    """
    shards = _shards_of(mesh, axes)
    if num_series % shards != 0:
        raise ValueError(
            f"num_series={num_series} is not divisible by the "
            f"{shards}-shard mesh {dict(mesh.shape)}; pad the "
            "collection to a multiple of the shard count (or pick a "
            "divisible mesh) before UlisseEngine.distributed/open")
    return shards


class UlisseEngine:
    """Unified query facade over one ULISSE index (local or sharded)."""

    def __init__(self, *, index: Optional[UlisseIndex] = None,
                 params: Optional[EnvelopeParams] = None,
                 mesh=None, sharded_data=None,
                 breakpoints=None, axes=("data",),
                 num_series: int = 0, series_len: int = 0,
                 max_batch: int = 8,
                 memory_budget_bytes: Optional[int] = None,
                 shard_blocks=None, delta_blocks=None,
                 delta_gmaps=None, cold_sections=None):
        self._index = index
        self.params = params if params is not None else index.params
        if memory_budget_bytes is None:
            env = os.environ.get("ULISSE_MEMORY_BUDGET_BYTES", "")
            memory_budget_bytes = int(env) if env else None
        # host-memory budget for the raw payload (local backend): when a
        # lazily-opened collection's payload exceeds it, queries run the
        # paged out-of-core scan with the store's page cache capped to
        # this many bytes; None (and any budget the payload fits in —
        # whole-collection residency is the one-page special case) keeps
        # today's materialize-once behavior.  Answers are bit-equal
        # either way (DESIGN.md §14).
        self.memory_budget_bytes = memory_budget_bytes
        self._mesh = mesh
        self._sharded = sharded_data
        self._breakpoints = breakpoints
        self._axes = tuple(axes)
        self._num_series = num_series
        self._series_len = series_len
        self.max_batch = max_batch
        self._programs = {}           # (bucket, k, verify_top) -> compiled fn
        if mesh is not None:
            self._shards = shards = _require_divisible(
                num_series, mesh, self._axes)
            self._env_rows_per_shard = (
                self.params.num_envelopes(series_len)
                * (num_series // shards))
            if series_len < self.params.lmax:
                raise ValueError("series shorter than lmax")
            # per-shard ingestion state (DESIGN.md §15): main raw
            # blocks (np or mmap; None = derive lazily from the device
            # copy), unsorted delta blocks, per-shard global ids of the
            # delta rows (NOT affine in the shard index once several
            # append parts exist), and — for the O(index) cold open —
            # mmap'd precomputed index sections covering each shard's
            # [main; delta] prefix as of the save.
            self._shard_main = (list(shard_blocks)
                                if shard_blocks is not None else None)
            self._shard_delta = (
                list(delta_blocks) if delta_blocks is not None
                else [np.zeros((0, series_len), np.float32)] * shards)
            self._delta_gmaps = (
                [np.asarray(g, np.int64) for g in delta_gmaps]
                if delta_gmaps is not None
                else [np.zeros((0,), np.int64)] * shards)
            self._delta_total = int(sum(b.shape[0]
                                        for b in self._shard_delta))
            self._cold_sections = cold_sections
            if sharded_data is None and shard_blocks is None:
                raise ValueError(
                    "distributed engine needs sharded_data or "
                    "shard_blocks")

    # ------------------------------------------------------------------
    # constructors
    # ------------------------------------------------------------------

    @classmethod
    def from_index(cls, index: UlisseIndex, max_batch: int = 8,
                   memory_budget_bytes: Optional[int] = None
                   ) -> "UlisseEngine":
        """Wrap an already-built local index."""
        return cls(index=index, max_batch=max_batch,
                   memory_budget_bytes=memory_budget_bytes)

    @classmethod
    def from_collection(cls, collection: Collection, params: EnvelopeParams,
                        breakpoints=None, block_size: int = 64,
                        num_levels: int = 2, max_batch: int = 8,
                        memory_budget_bytes: Optional[int] = None
                        ) -> "UlisseEngine":
        """Build the index and the engine in one step (local backend)."""
        return cls(index=build_index(collection, params, breakpoints,
                                     block_size=block_size,
                                     num_levels=num_levels),
                   max_batch=max_batch,
                   memory_budget_bytes=memory_budget_bytes)

    @classmethod
    def distributed(cls, mesh, params: EnvelopeParams, data,
                    breakpoints=None, axes=("data",),
                    max_batch: int = 8) -> "UlisseEngine":
        """Shard `data` (S, n) over the mesh and serve queries from it."""
        from repro.core.index import default_breakpoints
        from repro.distributed.ulisse import shard_collection

        # host-side: device_put from host places each shard on its own
        # device, never the whole collection on one
        data = np.asarray(data, np.float32)
        # fail before sharding/breakpoint work (jax's own device_put
        # divisibility error is far less actionable)
        _require_divisible(int(data.shape[0]), mesh, axes)
        if breakpoints is None:
            breakpoints = default_breakpoints(params, data)
        return cls(params=params, mesh=mesh,
                   sharded_data=shard_collection(mesh, data, axes),
                   breakpoints=breakpoints, axes=axes,
                   num_series=int(data.shape[0]),
                   series_len=int(data.shape[1]), max_batch=max_batch)

    # ------------------------------------------------------------------
    # persistence (repro.storage) — open / save / from_writer
    # ------------------------------------------------------------------

    @classmethod
    def open(cls, path: str, *, params: Optional[EnvelopeParams] = None,
             mesh=None, axes=("data",), max_batch: Optional[int] = None,
             mmap: bool = True,
             memory_budget_bytes: Optional[int] = None) -> "UlisseEngine":
        """Open a saved index (see repro.storage, DESIGN.md §7).

        Without `mesh`: the local backend over the stored sorted
        envelopes + block levels; raw series are mmap'd lazily, so the
        cold open reads O(index), not O(raw data).  With `mesh`: a
        distributed save carrying per-shard index sections (DESIGN.md
        §15) whose shard count matches the mesh reopens O(index) too —
        manifest + mmap handles only, no re-summarization; the raw
        payload bytes flow at first search, when the assembled index
        device_puts.  Any other combination (old save, local save,
        mesh size != saved shard count) falls back to re-sharding the
        raw payload and re-summarizing on the new mesh (elastic, like
        before — appended delta rows survive the re-shard).

        `params`: optional expected EnvelopeParams; a mismatch with the
        stored ones raises IndexCompatibilityError instead of silently
        returning wrong distances.
        """
        from repro.storage import store
        if mesh is not None:
            cold = store.load_distributed_sections(path, params)
            if cold is not None:
                (stored, bp, manifest, mains, deltas,
                 dgmaps, sections) = cold
                axes_t = tuple(manifest.get("axes", list(axes)))
                if _shards_of(mesh, axes_t) == len(mains):
                    return cls(
                        params=stored, mesh=mesh, breakpoints=bp,
                        axes=axes_t,
                        num_series=int(sum(m.shape[0] for m in mains)),
                        series_len=int(manifest["series_len"]),
                        max_batch=(manifest.get("max_batch", 8)
                                   if max_batch is None else max_batch),
                        shard_blocks=mains, delta_blocks=deltas,
                        delta_gmaps=dgmaps, cold_sections=sections)
            stored, bp, data, manifest = store.load_raw_data(path, params)
            return cls.distributed(
                mesh, stored, data, breakpoints=bp,
                axes=tuple(manifest.get("axes", list(axes))),
                max_batch=(manifest.get("max_batch", 8)
                           if max_batch is None else max_batch))
        return cls.from_index(store.open_index(path, params=params,
                                               mmap=mmap),
                              max_batch=8 if max_batch is None
                              else max_batch,
                              memory_budget_bytes=memory_budget_bytes)

    def save(self, path: str) -> str:
        """Persist this engine's index to `path` (atomic commit).

        Local backend: sorted envelopes + levels + breakpoints + raw
        shards (+ the delta buffer, if series were appended and not yet
        compacted).  Distributed backend: per-shard raw payloads
        (main + delta, with the delta rows' global-id map) PLUS the
        per-shard index sections — envelope rows and prefix sums for
        each shard's [main; delta] block — so the next
        `open(path, mesh=...)` on a matching mesh reads O(index)
        instead of re-running summarization (DESIGN.md §15).
        """
        from repro.storage import store
        if self.is_distributed:
            mains = [np.asarray(b, np.float32)
                     for b in self._shard_main_blocks()]
            sections = [self._shard_index_rows(s)
                        for s in range(self._shards)]
            return store.save_distributed(
                path, self.params, self._breakpoints, mains,
                axes=self._axes, max_batch=self.max_batch,
                delta_blocks=self._shard_delta,
                delta_gmaps=self._delta_gmaps, sections=sections)
        return store.save_index(path, self._index)

    @classmethod
    def from_writer(cls, writer, *, mmap: bool = True, mesh=None,
                    memory_budget_bytes: Optional[int] = None
                    ) -> "UlisseEngine":
        """Finalize a `repro.storage.Writer` bulk build and open it."""
        return cls.open(writer.finalize(), mmap=mmap, mesh=mesh,
                        memory_budget_bytes=memory_budget_bytes)

    # ------------------------------------------------------------------
    # incremental ingestion (delta + compaction, repro.storage.delta)
    # ------------------------------------------------------------------

    def validate_append(self, series) -> int:
        """Check (without mutating) that `series` is appendable here.

        Raises the same ValueError `append` would; returns the row
        count.  Read-only and cheap — the serving tier's client-side
        admission gate calls this on the submitting thread so malformed
        parts are rejected at submit time instead of poisoning the
        writer lane (DESIGN.md §11/§15).
        """
        arr = np.asarray(series, np.float32)
        if arr.ndim == 1:
            arr = arr[None]
        if arr.ndim != 2:
            raise ValueError(
                f"expected (n,) or (S, n) series, got {arr.shape}")
        n = (self._series_len if self.is_distributed
             else self._index.collection.series_len)
        if arr.shape[1] != n:
            raise ValueError(
                f"appended series_len {arr.shape[1]} != index "
                f"series_len {n} (collections are fixed-width)")
        if self.is_distributed and arr.shape[0] % self._shards != 0:
            raise ValueError(
                f"appended part of {arr.shape[0]} series is not "
                f"divisible by the {self._shards}-shard mesh; pad the "
                "part to a multiple of the shard count (row-sharded "
                "delta placement follows the build layout)")
        return int(arr.shape[0])

    def append(self, series) -> None:
        """Ingest new series: immediately searchable via the delta set.

        O(new series) work on either backend — envelopes of the
        appended series land in an unsorted delta buffer searched
        alongside the main sorted set; no re-sort, no block rebuild.
        Distributed: the part row-shards over the mesh like
        `build_sharded_index` (shard s takes rows [s*q, (s+1)*q) of
        the part), so the part size must divide by the shard count;
        each shard's delta rows keep their GLOBAL ids in a per-shard
        map (DESIGN.md §15).  Call `compact()` once a batch of appends
        has accumulated.
        """
        if self.is_distributed:
            arr = np.asarray(series, np.float32)
            if arr.ndim == 1:
                arr = arr[None]
            self.validate_append(arr)
            self._shard_main_blocks()     # pin main before state grows
            q = arr.shape[0] // self._shards
            base = self._num_series + self._delta_total
            for s in range(self._shards):
                self._shard_delta[s] = np.concatenate(
                    [self._shard_delta[s], arr[s * q:(s + 1) * q]])
                self._delta_gmaps[s] = np.concatenate(
                    [self._delta_gmaps[s],
                     base + s * q + np.arange(q, dtype=np.int64)])
            self._delta_total += int(arr.shape[0])
            self._invalidate_distributed_caches()
            return
        from repro.storage import delta as _delta
        self._index = _delta.extend_index(self._index, series)

    def compact(self) -> None:
        """Merge the delta buffer into the main sorted set (rebuilds
        block levels; bit-identical to a from-scratch build).

        Distributed: the mesh-wide merge — delta rows fold into the
        main payload in GLOBAL id order (original series, then append
        parts in arrival order) and the collection re-shards evenly,
        which is EXACTLY the layout `UlisseEngine.distributed` builds
        from the concatenated data, so the compacted engine is
        bit-identical to a from-scratch sharded build with the same
        breakpoints (asserted in tests/test_distributed_ingest.py).
        Cold-open index sections are dropped (they describe the
        pre-compaction shard layout); the next save rewrites them.
        """
        if self.is_distributed:
            if self._delta_total == 0 and self._cold_sections is None:
                return
            from repro.distributed.ulisse import shard_collection
            full = self._host_data()
            total = self._num_series + self._delta_total
            shards = self._shards
            self._num_series = total
            self._delta_total = 0
            r = total // shards
            self._shard_main = [full[s * r:(s + 1) * r]
                                for s in range(shards)]
            self._shard_delta = [
                np.zeros((0, self._series_len), np.float32)] * shards
            self._delta_gmaps = [np.zeros((0,), np.int64)] * shards
            self._cold_sections = None
            self._env_rows_per_shard = (
                self.params.num_envelopes(self._series_len) * r)
            self._sharded = shard_collection(
                self._mesh, jnp.asarray(full), self._axes)
            self._invalidate_distributed_caches(clear_programs=True)
            self._host_data_cache = full
            return
        from repro.storage import delta as _delta
        self._index = _delta.compact_index(self._index)

    def _invalidate_distributed_caches(self,
                                       clear_programs: bool = False):
        """Drop device-resident index assemblies (and, on compact, the
        compiled programs whose static geometry changed)."""
        self._sharded_index = None
        self._delta_index = None
        self._host_data_cache = None
        if clear_programs:
            self._programs.clear()

    @property
    def delta_size(self) -> int:
        """Envelopes waiting in the ingestion delta (0 when compacted).

        Distributed: the mesh-wide count across every shard's delta
        buffer — feed it to `distributed_index_stats(delta_envelopes=
        ...)` for capacity planning."""
        if self.is_distributed:
            return (self.params.num_envelopes(self._series_len)
                    * self._delta_total)
        if self._index.delta is None:
            return 0
        return self._index.delta.size

    def _paged_store(self):
        """The PayloadStore behind the paged out-of-core scan, or None.

        Paging engages only when ALL of: local backend, a
        `memory_budget_bytes` is set, the collection is a still-lazy
        PayloadStore, and its payload does not fit the budget — the
        fitting case materializes exactly as before (whole-collection
        residency is the one-page special case), so the resident fast
        path never changes behind a small index.  Keeps the store's
        cache limit synced to the engine budget.
        """
        if self.is_distributed or self.memory_budget_bytes is None \
                or self._index is None:
            return None
        from repro.storage.store import PayloadStore
        coll = self._index.collection
        if not isinstance(coll, PayloadStore) or coll.is_materialized:
            return None
        if coll.payload_bytes <= self.memory_budget_bytes:
            return None
        if coll.cache_limit_bytes != self.memory_budget_bytes:
            coll.cache_limit_bytes = self.memory_budget_bytes
        return coll

    def page_cache_stats(self) -> Optional[dict]:
        """Monotone page-cache counters of the paged store (hits,
        misses, evicted_bytes, cache_bytes, cached_pages) — None when
        the engine is not paging.  The serving tier mirrors deltas of
        these into the obs registry after each dispatch."""
        store = self._paged_store()
        return None if store is None else store.stats()

    @property
    def is_distributed(self) -> bool:
        return self._mesh is not None

    @property
    def index(self) -> Optional[UlisseIndex]:
        """The local index (None for the distributed backend)."""
        return self._index

    def device_arrays(self) -> dict:
        """The device-resident collection and index arrays by field name
        (row-sharded over the mesh on the distributed backend, which
        builds them here if no query has yet) — what the engine keeps
        on its devices, for memory accounting."""
        if self.is_distributed:
            from repro.distributed.ulisse import SHARDED_INDEX_FIELDS
            return dict(zip(SHARDED_INDEX_FIELDS,
                            self._ensure_sharded_index()))
        coll = self._index.collection
        env = self._index.search_envelopes()
        out = {f: getattr(coll, f) for f in (
            "data", "csum", "csum2", "csum_lo", "csum2_lo", "center")}
        out.update(paa_lo=env.paa_lo, paa_hi=env.paa_hi)
        # the breakpoint intervals the plan reads, in the place of the
        # iSAX symbols (those stay on the host)
        out["interval_lo"], out["interval_hi"] = self._index.intervals
        out.update({f: getattr(env, f) for f in (
            "series_id", "anchor", "n_master", "valid")})
        return out

    @property
    def raw_data(self) -> np.ndarray:
        """The (S, n) raw series this engine serves (gathered to host,
        appended-but-uncompacted series included, global id order)."""
        if self.is_distributed:
            return self._host_data()
        return np.asarray(self._index.collection.data)

    # ------------------------------------------------------------------
    # the one entry point
    # ------------------------------------------------------------------

    def search(self, queries, spec: QuerySpec = QuerySpec()
               ) -> Union[SearchResult, List[SearchResult]]:
        """Answer one query (1-D input -> SearchResult) or a batch (2-D
        array or sequence of 1-D arrays -> list of SearchResult), under
        any measure/mode/shape the spec describes."""
        single, qs = self._normalize_queries(queries)
        if self.is_distributed:
            if spec.scan_backend == "device":
                # the sharded pruned scan (DESIGN.md §10): every shard
                # runs the device scan core over its own LB-ordered
                # pack, pruning against the broadcast global bsf; one
                # host sync per batch, full measure/mode/range matrix
                if spec.is_range:
                    results = self._distributed_range_device(qs, spec)
                else:
                    results = self._distributed_knn_device(qs, spec)
            else:
                results = self._search_distributed(qs, spec)
        elif spec.scan_backend == "device":
            # the one-sync local pipeline: every query shape — k-NN
            # (approx-seeded or pure scan), approximate-only, eps-range
            # — runs as device programs over a shared per-length plan,
            # with one host readback per same-length batch
            if spec.is_range:
                results = self._local_range_device(qs, spec)
            elif spec.mode == "exact":
                results = self._local_exact_device(qs, spec)
            else:
                results = self._local_approx_device(qs, spec)
        else:
            results = [self._search_local(q, spec) for q in qs]
        return results[0] if single else results

    def warmup(self, lengths: Sequence[int],
               batch_sizes: Sequence[int] = (1,),
               spec: QuerySpec = QuerySpec()) -> int:
        """Pre-trace the per-(batch, length) device programs.

        Runs one throwaway search per (length, batch-size) pair on a
        deterministic synthetic query so the jit caches hold every
        program shape the given traffic mix needs BEFORE the first real
        request arrives — first-request latency becomes serving
        latency, not compile latency.  Batch sizes round up to their
        pow2 bucket exactly as real dispatches do, so warming
        `batch_sizes=(max_batch,)` plus `(1,)` covers the common fills.
        Returns the number of (length, batch) shapes exercised.
        """
        traced = 0
        for qlen in sorted({int(x) for x in lengths}):
            self._bucket(qlen)            # validates the length range
            # non-degenerate values: znormalize needs a nonzero std
            q = np.sin(np.linspace(0.0, 6.0, qlen)).astype(np.float32)
            for bsz in sorted({int(x) for x in batch_sizes}):
                if bsz < 1:
                    raise ValueError("batch sizes must be >= 1")
                self.search([q] * bsz, spec)
                traced += 1
        return traced

    # ------------------------------------------------------------------
    # static-analysis surface (repro.analysis, DESIGN.md §13)
    # ------------------------------------------------------------------

    def audit_programs(self, specs: Optional[Sequence[QuerySpec]] = None,
                       *, batch: int = 2,
                       qlen: Optional[int] = None) -> List[dict]:
        """Trace every compiled program this engine emits for `specs`.

        The auditor's hook: nothing executes — each record carries the
        abstract ClosedJaxpr of one program family plus a zero-arg
        `lower` thunk (for compiled-HLO corroboration).  Record keys:

          name          unique display name,
          family        PROGRAM_KEY_SPECS family (or "prepare"),
          backend       "local" | "distributed",
          jaxpr         ClosedJaxpr of the whole program,
          lower         () -> jax Lowered (compile for HLO text),
          taint_invars  top-level invar indices of the float64-split
                        hi/lo prefix sums (R3 taint sources),
          spec          the QuerySpec that selected the family.

        Default specs cover the measure x shape matrix of this
        backend; reuses the same program getters as `search`, so an
        audited jaxpr IS the served program (cache-key included)."""
        if specs is None:
            specs = [QuerySpec(),
                     QuerySpec(measure="dtw", r=4),
                     QuerySpec(eps=1.0),
                     QuerySpec(measure="dtw", r=4, eps=1.0),
                     QuerySpec(mode="approx")]
            if self.is_distributed and not self._delta_active():
                # the legacy host oracle predates per-shard delta
                # buffers and raises at dispatch on a delta-carrying
                # engine — nothing to audit there
                specs.append(QuerySpec(scan_backend="host"))
        records, seen = [], set()
        for spec in specs:
            if self.is_distributed:
                recs = self._audit_distributed(spec, batch, qlen)
            else:
                recs = self._audit_local(spec, batch, qlen)
            for rec in recs:
                if rec["name"] not in seen:
                    seen.add(rec["name"])
                    records.append(rec)
        return records

    def _audit_local(self, spec: QuerySpec, batch: int,
                     qlen: Optional[int]) -> List[dict]:
        from repro.kernels.common import default_interpret
        p, index = self.params, self._index
        qlen = qlen or p.lmin
        g = p.gamma + 1
        n_pad = executor.pow2ceil(index.search_envelopes().size)
        chunk = min(executor.pow2ceil(spec.chunk_size), n_pad)
        sb = min(128, chunk * g)
        interpret = default_interpret()

        def sds(a):
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

        def f32(*s):
            return jax.ShapeDtypeStruct(s, jnp.float32)

        def i32(*s):
            return jax.ShapeDtypeStruct(s, jnp.int32)

        qargs = [f32(batch, qlen)] * 3
        store = self._paged_store()
        if store is not None:
            # paged engine: the served programs are the one-chunk slab
            # programs (reading index.collection.data here would
            # materialize the payload the budget forbids); slab rows
            # audit at the largest possible pow2 bucket
            rows = executor.pow2ceil(store.num_series)
            n = store.series_len
            coll = [f32(rows, n), f32(rows, n + 1), f32(rows, n + 1),
                    f32(rows, n + 1), f32(rows, n + 1), f32(rows)]
            plan = [i32(batch, chunk), i32(batch, chunk),
                    i32(batch, chunk), f32(batch, chunk),
                    i32(batch, chunk)]
            if spec.is_range:
                family = "local_paged_range"
                cap = executor.pow2ceil(spec.range_capacity)
                fn = executor._paged_range_chunk_program(
                    cap, g, chunk, p.znorm, spec.measure, spec.r, sb,
                    interpret)
                args = coll + plan + qargs + [
                    f32(batch), f32(batch, cap), i32(batch, cap),
                    i32(batch, cap), i32(batch), i32(batch), i32(),
                    i32()]
            else:
                family = "local_paged"
                fn = executor._paged_scan_chunk_program(
                    spec.k, g, chunk, p.znorm, spec.measure, spec.r,
                    sb, interpret)
                args = coll + plan + qargs + [f32(batch, spec.k),
                                              i32(batch, spec.k),
                                              i32(batch, spec.k)]
        else:
            c = index.collection
            coll = [sds(c.data), sds(c.csum), sds(c.csum2),
                    sds(c.csum_lo), sds(c.csum2_lo), sds(c.center)]
            plan = [i32(batch, n_pad), i32(batch, n_pad),
                    i32(batch, n_pad), f32(batch, n_pad)]
            if spec.is_range:
                family = "local_range"
                fn = executor._device_range_program(
                    executor.pow2ceil(spec.range_capacity), g, chunk,
                    p.znorm, spec.measure, spec.r, sb, interpret)
                args = coll + plan + qargs + [f32(batch)]
            else:
                family = "local_scan"
                fn = executor._device_scan_program(
                    spec.k, g, chunk, p.znorm, spec.measure, spec.r,
                    sb, interpret)
                args = coll + plan + qargs + [f32(batch, spec.k),
                                              i32(batch, spec.k),
                                              i32(batch, spec.k)]
        prep = jax.jit(lambda q: planner.prepare_query_batch(
            q, p.seg_len, p.znorm, spec.measure, spec.r))
        qsd = f32(batch, qlen)
        return [
            {"name": f"{family}[{spec.measure},b{batch}]",
             "family": family, "backend": "local",
             "jaxpr": jax.make_jaxpr(fn)(*args),
             "lower": (lambda fn=fn, args=args: fn.lower(*args)),
             # csum/csum2 + their float64-split low halves
             "taint_invars": (1, 2, 3, 4), "spec": spec},
            {"name": f"prepare[{spec.measure},b{batch}]",
             "family": "prepare", "backend": "local",
             "jaxpr": jax.make_jaxpr(prep)(qsd),
             "lower": (lambda prep=prep, qsd=qsd: prep.lower(qsd)),
             "taint_invars": (), "spec": spec},
        ]

    def _audit_distributed(self, spec: QuerySpec, batch: int,
                           qlen: Optional[int]) -> List[dict]:
        from repro.distributed.ulisse import SHARDED_INDEX_FIELDS
        qlen = qlen or self.params.lmin
        q = np.sin(np.linspace(0.0, 6.0, qlen)).astype(np.float32)
        if spec.scan_backend == "host":
            bucket = self._bucket(qlen)
            fn = self._program(
                bucket, spec,
                min(spec.verify_top, self._env_rows_per_shard))
            qpad = np.zeros((batch, bucket), np.float32)
            qpad[:, :qlen] = q
            args = (self._sharded, jnp.asarray(qpad),
                    jnp.full((batch,), qlen, jnp.int32))
            family, taint = "legacy_host_knn", ()
        else:
            delta = self._delta_active()
            index_arrs = (self._ensure_delta_index() if delta
                          else self._ensure_sharded_index())
            # the sharded index tuple leads the argument list, so the
            # csum-carrying fields' positions ARE the taint indices
            # (the delta families' trailing gmap input sits past them)
            taint = tuple(i for i, f in enumerate(SHARDED_INDEX_FIELDS)
                          if "csum" in f)
            _, qstack, dlo, dhi, qb, qh = self._stack_prepared(
                [q] * batch, spec)
            if spec.is_range:
                family = ("sharded_delta_range" if delta
                          else "sharded_range")
                fn, _ = (self._sharded_delta_range_program(spec)
                         if delta else self._sharded_range_program(spec))
                args = (*index_arrs, qstack, dlo, dhi, qb, qh,
                        jnp.full((batch,), float(spec.eps) ** 2,
                                 jnp.float32))
            else:
                family = ("sharded_delta_knn" if delta
                          else "sharded_knn")
                fn = (self._sharded_delta_knn_program(spec) if delta
                      else self._sharded_knn_program(spec))
                args = (*index_arrs, qstack, dlo, dhi, qb, qh)
        mode = ("-approx" if spec.mode == "approx"
                and not spec.is_range else "")
        return [
            {"name": f"{family}[{spec.measure}{mode},b{batch}]",
             "family": family, "backend": "distributed",
             "jaxpr": jax.make_jaxpr(fn)(*args),
             "lower": (lambda fn=fn, args=args: fn.lower(*args)),
             "taint_invars": taint, "spec": spec},
        ]

    def _normalize_queries(self, queries):
        if isinstance(queries, (list, tuple)):
            qs = [np.asarray(q, np.float32) for q in queries]
        else:
            arr = np.asarray(queries, np.float32)
            if arr.ndim == 1:
                return True, [arr]
            qs = [arr[i] for i in range(arr.shape[0])]
        return False, qs

    # ------------------------------------------------------------------
    # local backend (host-driven planner/executor pipeline)
    # ------------------------------------------------------------------

    def _search_local(self, q, spec: QuerySpec) -> SearchResult:
        """Host-driven reference paths (scan_backend="host")."""
        with span("query.host", qlen=len(q),
                  shape="range" if spec.is_range else spec.mode):
            if spec.is_range:
                return self._local_range(q, spec)
            if spec.mode == "approx":
                return self._local_approx(q, spec)
            return self._local_exact(q, spec)

    def _local_approx(self, q, spec: QuerySpec) -> SearchResult:
        pool, stats, _ = self._local_approx_impl(q, spec)
        return self._host_knn_result(q, spec, pool, stats)

    def _host_knn_result(self, q, spec: QuerySpec, pool,
                         stats) -> SearchResult:
        """The host pool as a result: reported ED distances go through
        the same float64 rescore as every device path.  Unfilled pool
        rows (+inf: k exceeded the valid windows) are dropped, as the
        device paths drop theirs."""
        real = np.isfinite(pool.d)
        return self._knn_result_rows(q, spec, pool.d[real], pool.s[real],
                                     pool.o[real], stats)

    def _local_approx_impl(self, q, spec: QuerySpec,
                           pq: Optional[planner.PreparedQuery] = None):
        """Best-first descent over the block hierarchy (paper Alg. 4).

        Visits fine blocks ("leaves") in lower-bound order; stops when a
        leaf's lower bound exceeds the k-th bsf (=> answer already exact),
        capped at max_leaves.

        Returns (pool, stats, verified) — the squared-distance pool (the
        exact scan seeds from it directly; a sqrt->square round-trip
        would perturb pruning at exact-tie boundaries), and the combined
        candidate-set indices of every envelope verified (the device
        scan excludes them instead of deduplicating its pool).
        """
        index = self._index
        if pq is None:
            pq = planner.prepare_query(q, self.params, spec.measure,
                                       spec.r)
        stats = SearchStats(
            envelopes_total=int(index.search_envelopes().size))
        pool = TopK(spec.k)
        verified: list = []

        # The ingestion delta has no block cover: sweep it exhaustively
        # up front (it is small pre-compaction).  This primes the bsf
        # for the descent and keeps the exact_from_approx certificate
        # honest — every candidate outside the block hierarchy has been
        # verified, so "leaf LB >= kth bsf" still implies exactness.
        # Chunked like the exact scan so a huge uncompacted delta never
        # gathers one unbounded window tensor.
        if index.delta is not None:
            dvalid = index.envelopes.size \
                + np.nonzero(np.asarray(index.delta.valid))[0]
            for start in range(0, len(dvalid), spec.chunk_size):
                executor.verify_envelopes(
                    index, pq, dvalid[start:start + spec.chunk_size],
                    pool, stats)
            verified.append(dvalid)

        order, blk_lb = planner.plan_leaf_order(index, pq)
        stats.lb_computations += index.levels[-1].size
        block_size = index.envelopes.size // index.levels[-1].size

        n_leaves = min(spec.max_leaves, len(order))
        exhausted = False
        for leaf_rank in range(n_leaves):
            b = int(order[leaf_rank])
            if not np.isfinite(blk_lb[b]):
                # blocks are LB-sorted: everything left is invalid, so
                # every finite-LB leaf has been verified
                exhausted = True
                break
            if blk_lb[b] ** 2 >= pool.kth:
                stats.exact_from_approx = True
                break
            env_idx = np.arange(b * block_size, (b + 1) * block_size)
            valid = np.asarray(index.envelopes.valid)[env_idx]
            executor.verify_envelopes(index, pq, env_idx[valid], pool, stats)
            verified.append(env_idx[valid])
            stats.leaves_visited += 1
            # NOTE deviation from Alg. 4 line 22: the paper stops after the
            # first non-improving leaf to save random disk I/O.  Batched
            # device leaves are cheap and the quantized block bounds tie at
            # zero often, so we keep visiting up to max_leaves — strictly
            # better answers for the same asymptotics (see DESIGN.md §3).
        else:
            exhausted = (n_leaves == len(order)
                         or not np.isfinite(blk_lb[int(order[n_leaves])]))
        if exhausted:
            # the descent ran out of finite-LB leaves: every valid block
            # (and the delta) has been verified, so the answer is
            # provably exact and the exact scan can be skipped entirely
            stats.exact_from_approx = True
        ver = (np.concatenate(verified).astype(np.int64) if verified
               else np.zeros((0,), np.int64))
        return pool, stats, ver

    def _local_exact(self, q, spec: QuerySpec) -> SearchResult:
        """Exact k-NN: approximate pass for a bsf, then the LB-sorted
        chunked scan over the flat envelope list with bsf pruning
        (paper Alg. 5) — the host-driven reference path."""
        index = self._index
        pq = planner.prepare_query(q, self.params, spec.measure, spec.r)
        if spec.approx_first:
            # thread the approx pass's squared pool straight through —
            # re-pushing sqrt(d2)**2 perturbs exact-tie pruning
            pool, stats, _ = self._local_approx_impl(q, spec, pq)
            if stats.exact_from_approx:
                return self._host_knn_result(q, spec, pool, stats)
        else:
            stats = SearchStats(
                envelopes_total=int(index.search_envelopes().size))
            pool = TopK(spec.k)

        order, lbs_sorted = planner.plan_scan_order(index, pq,
                                                    spec.use_paa_bounds)
        n = index.search_envelopes().size   # main ++ ingestion delta
        stats.lb_computations += n
        stats.chunks_planned = -(-n // spec.chunk_size)

        pos = 0
        while pos < n:
            if not np.isfinite(lbs_sorted[pos]):
                break
            if lbs_sorted[pos] ** 2 >= pool.kth:
                break  # every remaining envelope is pruned
            end = min(pos + spec.chunk_size, n)
            sel = order[pos:end]
            fin = np.isfinite(lbs_sorted[pos:end])
            keep = fin & ((lbs_sorted[pos:end] ** 2) < pool.kth)
            if keep.any():
                executor.verify_envelopes(index, pq, sel[keep], pool, stats)
            # same convention as the device chunk step: envelopes cut by
            # the bsf LB test inside a visited chunk count as pruned
            stats.envelopes_pruned += int((fin & ~keep).sum())
            stats.chunks_visited += 1
            pos = end
        return self._host_knn_result(q, spec, pool, stats)

    # -- the one-sync device pipeline (DESIGN.md §8/§9) ----------------

    def _group_by_len(self, qs):
        by_len = {}
        for i, q in enumerate(qs):
            by_len.setdefault(len(q), []).append(i)
        return sorted(by_len.items())

    def _padded_batches(self, qs, idxs):
        """max_batch-sized sub-batches of one length group, the query
        list padded to the pow2 batch bucket by repeating the last
        query.  Scan rows are independent (a padded duplicate row never
        touches another row's pool), so results are bit-identical to
        the unpadded program while compiles stay bounded at
        log2(max_batch)+1 batch shapes per length — the property the
        serving tier's variable dispatch fills rely on."""
        for sub, b in self._device_batches(idxs):
            queries = [qs[i] for i in sub]
            queries += [queries[-1]] * (b - len(sub))
            yield sub, queries, b

    def _stack_prepared(self, queries, spec: QuerySpec):
        """Shared per-length-group query prep: ONE jitted batched call
        (planner.prepare_query_batch), device arrays, no sync."""
        q = jnp.asarray(np.stack(queries), jnp.float32)
        qn, dlo, dhi, qb, qh = planner.prepare_query_batch(
            q, self.params.seg_len, self.params.znorm, spec.measure,
            spec.r)
        nseg = self.params.query_segments(q.shape[1])
        return nseg, qn, dlo, dhi, qb, qh

    def _device_approx_stage(self, qstack, dlo, dhi, qb, qh, nseg: int,
                             k: int, spec: QuerySpec):
        """Batched device approximate pass (paper Alg. 4 as ONE program).

        Delta sweep + best-first leaf visits run as the scan core over
        the pow2-padded leaf order (planner.device_leaf_pack): each
        chunk is one leaf carrying its block's squared LB, so the
        core's per-chunk stop reproduces the host descent's "next leaf
        cannot improve" break.  Seeds the (B, k) pool ON DEVICE and
        derives the exactness certificate there too — nothing syncs.

        Returns (pool (d2, sid, off), stats, cert, leaf_v, comb_idx,
        visited_chunks, chunk, nblk, planned) — all device arrays but
        the static ints (`planned` is the pack's chunk count, the
        approx stage's share of `SearchStats.chunks_planned`).
        """
        index, p = self._index, self.params
        env = index.search_envelopes()
        n_main = index.envelopes.size
        fine = index.levels[-1]
        nblk = fine.size
        block_size = n_main // nblk
        chunk = executor.pow2ceil(block_size)
        n_leaves = min(spec.max_leaves, nblk)
        b, qlen = qstack.shape

        mark = device_stage("device.approx_plan", qlen=qlen, batch=b)
        blk_lb = planner.block_lower_bounds_batch(
            qb, qh, fine.paa_lo, fine.paa_hi, fine.valid, p.seg_len,
            nseg)
        (asids, aanc, anm, albs2, comb_idx,
         blk_sorted) = planner.device_leaf_pack(
            env.series_id, env.anchor, env.n_master, env.valid, blk_lb,
            n_main=n_main, block_size=block_size, chunk=chunk,
            n_leaves=n_leaves)
        mark.ready(blk_sorted)
        mark = device_stage("device.approx_scan", qlen=qlen, batch=b)
        neg = jnp.full((b, k), -1, jnp.int32)
        seed = (jnp.full((b, k), jnp.inf, jnp.float32), neg, neg)
        store = self._paged_store()
        if store is None:
            ad2, asid, aoff, ast = executor.device_exact_scan(
                index.collection, asids, aanc, anm, albs2, qstack, dlo,
                dhi, *seed, k=k, g=p.gamma + 1, measure=spec.measure,
                r=spec.r, znorm=p.znorm, chunk_size=chunk)
        else:
            # paged: the leaf plan comes back to host (a planned
            # transfer — the plan IS the page access schedule) and the
            # host-driven paged scan prefetches slabs along it
            asids_h, aanc_h, anm_h, albs2_h = jax.device_get(
                (asids, aanc, anm, albs2))
            ad2, asid, aoff, ast = executor.paged_exact_scan(
                store, asids_h, aanc_h, anm_h, albs2_h, qstack, dlo,
                dhi, *seed, k=k, g=p.gamma + 1, measure=spec.measure,
                r=spec.r, znorm=p.znorm, chunk_size=chunk)

        n_delta = env.size - n_main
        nd_chunks = -(-n_delta // chunk)
        visited = ast[:, 0]
        leaf_v = jnp.clip(visited - nd_chunks, 0, n_leaves)
        # certificate (== host's exact_from_approx): the first unvisited
        # leaf cannot improve the pool, or no finite-LB leaf is left
        kth2 = ad2[:, k - 1]
        next_lb = blk_sorted[jnp.arange(b),
                             jnp.minimum(leaf_v, blk_sorted.shape[1] - 1)]
        cert = ((leaf_v >= nblk) | ~jnp.isfinite(next_lb)
                | (next_lb.astype(jnp.float32) ** 2 >= kth2))
        mark.ready(cert)
        return ((ad2, asid, aoff), ast, cert, leaf_v, comb_idx, visited,
                chunk, nblk, asids.shape[1] // chunk)

    def _local_host_data(self) -> np.ndarray:
        """Host copy of the local collection's raw series (cached per
        collection identity, so a rebuilt/extended index invalidates
        it) — feeds the f64 ED polish off the hot path."""
        cached = getattr(self, "_local_host_cache", None)
        coll = self._index.collection
        if cached is None or cached[0] is not coll.data:
            cached = (coll.data, np.asarray(coll.data))
            self._local_host_cache = cached
        return cached[1]

    def _ed_rescore(self, q, sid, off, data=None) -> np.ndarray:
        """Direct float64 ED of the reported (sid, off) windows — the
        polish every ED result path shares.  Two reasons: the kernel's
        MXU dot-identity ED cancels catastrophically near d = 0 (error
        ~ eps_f32 * 2L on d2), and XLA re-tiles the (inlined) kernel
        reduction per program shape, so raw device d2 for the SAME
        subsequence rounds differently between the resident and paged
        programs.  Selection already happened on device values; this
        re-scores only the *reported* rows — O(rows * qlen) host work
        after the readback, no extra device sync.  `data`: host series
        override (the distributed backend passes its gathered host
        copy; local reads the cached index copy — a bare np.asarray
        here cost one full device->host collection transfer PER RESULT
        ROW, the R2 host-sync-budget violation the auditor pins).
        """
        if data is None:
            store = self._paged_store()
            if store is not None:
                # paged: gather ONLY the reported rows through the page
                # cache — materializing the payload here would defeat
                # the memory budget for a rows*qlen read
                data = store.take_rows(sid)
                ridx = np.arange(len(sid))
            else:
                data = self._local_host_data()
                ridx = sid
        else:
            ridx = sid
        w = data[ridx[:, None],
                 off[:, None] + np.arange(len(q))].astype(np.float64)
        qn = np.asarray(q, np.float64)
        if self.params.znorm:
            qn = (qn - qn.mean()) / max(qn.std(), 1e-8)
            mu = w.mean(1, keepdims=True)
            sd = np.maximum(w.std(1, keepdims=True), 1e-8)
            w -= mu       # in place: range hit sets reach thousands of
            w /= sd       # rows, so the temporaries are worth dodging
        w -= qn
        np.square(w, out=w)
        return w.sum(1)

    def _knn_result_rows(self, q, spec: QuerySpec, d2, sid, off,
                         stats, data=None) -> SearchResult:
        # drop unfilled pool rows (sid -1): with k > candidates the pool
        # keeps +inf filler, which must not surface as phantom neighbors
        filled = sid >= 0
        d2 = d2[filled].astype(np.float64)
        sid = sid[filled].astype(np.int64)
        off = off[filled].astype(np.int64)
        if spec.measure == "ed" and len(d2):
            d2 = self._ed_rescore(q, sid, off, data)
            order = np.argsort(d2, kind="stable")
            d2, sid, off = d2[order], sid[order], off[order]
        return SearchResult(dists=np.sqrt(np.maximum(d2, 0.0)),
                            series=sid, offsets=off, stats=stats)

    def _local_exact_device(self, qs, spec: QuerySpec):
        """Exact k-NN, fully device-resident (paper Alg. 5 incl. its
        line-1 approximate pass), ONE host sync per same-length batch.

        Per length group: batched device approx pass -> its verified
        rows are scatter-excluded from the LB order on device
        (planner.device_scan_pack — the dedup-free pool never sees a
        subsequence twice) -> the seeded exact scan.  A query whose
        certificate already proves exactness self-skips the scan: every
        unverified envelope's LB is then >= its kth, so its first chunk
        is born inactive.  The single readback collects pools, stats
        and certificates together.
        """
        index = self._index
        k, g = spec.k, self.params.gamma + 1
        results: List[Optional[SearchResult]] = [None] * len(qs)
        env = index.search_envelopes()
        n_comb = env.size
        for qlen, idxs in self._group_by_len(qs):
            for sub, queries, b in self._padded_batches(qs, idxs):
                with span("query.exact_device", qlen=qlen, batch=b) as sp:
                    with span("prepare"):
                        (nseg, qstack, dlo, dhi, qb,
                         qh) = self._stack_prepared(queries, spec)
                    if spec.approx_first:
                        with span("approx_pass"):
                            (seed, ast, cert, leaf_v, comb_idx, visited,
                             achunk, nblk,
                             aplan) = self._device_approx_stage(
                                qstack, dlo, dhi, qb, qh, nseg, k, spec)
                    else:
                        seed = (jnp.full((b, k), jnp.inf, jnp.float32),
                                jnp.full((b, k), -1, jnp.int32),
                                jnp.full((b, k), -1, jnp.int32))
                        ast = jnp.zeros((b, executor.STATS_WIDTH),
                                        jnp.int32)
                        cert = jnp.zeros((b,), bool)
                        leaf_v = jnp.zeros((b,), jnp.int32)
                        comb_idx = jnp.full((b, 1), n_comb, jnp.int32)
                        visited = jnp.zeros((b,), jnp.int32)
                        achunk, nblk, aplan = 1, 0, 0
                    with span("pack"):
                        mark = device_stage("device.plan", qlen=qlen,
                                            batch=b)
                        lbs = planner.env_lower_bounds_batch(
                            qb, qh, *index.lb_intervals(
                                spec.use_paa_bounds),
                            env.valid, self.params.seg_len, nseg)
                        n_pad = executor.pow2ceil(n_comb)
                        (ssids, sanc, snm, slbs2,
                         _) = planner.device_scan_pack(
                            env.series_id, env.anchor, env.n_master,
                            lbs, comb_idx, visited, chunk=achunk,
                            n_pad=n_pad)
                        mark.ready(slbs2)
                    with span("device_scan"):
                        mark = device_stage("device.scan", qlen=qlen,
                                            batch=b)
                        store = self._paged_store()
                        if store is None:
                            d2, sid, off, st = executor.device_exact_scan(
                                index.collection, ssids, sanc, snm,
                                slbs2, qstack, dlo, dhi, *seed, k=k,
                                g=g, measure=spec.measure, r=spec.r,
                                znorm=self.params.znorm,
                                chunk_size=spec.chunk_size)
                        else:
                            # paged: plan readback (planned transfer),
                            # then the prefetching host-driven scan
                            (ssids_h, sanc_h, snm_h,
                             slbs2_h) = jax.device_get(
                                (ssids, sanc, snm, slbs2))
                            d2, sid, off, st = executor.paged_exact_scan(
                                store, ssids_h, sanc_h, snm_h, slbs2_h,
                                qstack, dlo, dhi, *seed, k=k, g=g,
                                measure=spec.measure, r=spec.r,
                                znorm=self.params.znorm,
                                chunk_size=spec.chunk_size)
                        mark.ready(st)
                        # THE one host sync of the batch
                        (d2, sid, off, st, ast, cert,
                         leaf_v) = jax.device_get(
                            (d2, sid, off, st, ast, cert, leaf_v))
                    # planned = the exact-scan pack's chunk count (the
                    # approx stage's leaf plan is reported separately
                    # via leaves_visited, mirroring chunks_visited
                    # which counts scan chunks only)
                    planned = n_pad // min(
                        executor.pow2ceil(spec.chunk_size), n_pad)
                    with span("merge"):
                        for row, i in enumerate(sub):
                            stats = SearchStats(
                                envelopes_total=n_comb,
                                lb_computations=n_comb
                                + (nblk if spec.approx_first else 0),
                                leaves_visited=int(leaf_v[row]),
                                exact_from_approx=bool(cert[row]),
                                chunks_visited=int(st[row, 0]),
                                chunks_planned=planned,
                                envelopes_checked=(int(ast[row, 1])
                                                   + int(st[row, 1])),
                                true_dist_computations=(
                                    int(ast[row, 2]) + int(st[row, 2])),
                                dtw_lb_keogh=(int(ast[row, 3])
                                              + int(st[row, 3])),
                                dtw_full=(int(ast[row, 4])
                                          + int(st[row, 4])),
                                envelopes_pruned=(int(ast[row, 5])
                                                  + int(st[row, 5])))
                            results[i] = self._knn_result_rows(
                                qs[i], spec, d2[row], sid[row],
                                off[row], stats)
                    # loop trips of the batched scan: lanes run in
                    # lockstep until the last one stops, and a lane
                    # that stops stays stopped; padded lanes repeat
                    # the last query and count as unused
                    fill = len(sub)
                    sp.set(chunks=int(st[:fill, 0].sum()),
                           steps=int(st[:, 0].max()), lanes=b, fill=fill)
        return results

    def _local_approx_device(self, qs, spec: QuerySpec):
        """Batched device approximate k-NN (paper Alg. 4): the approx
        stage alone, one host sync per same-length batch."""
        k = spec.k
        results: List[Optional[SearchResult]] = [None] * len(qs)
        n_comb = self._index.search_envelopes().size
        for qlen, idxs in self._group_by_len(qs):
            for sub, queries, b in self._padded_batches(qs, idxs):
                with span("query.approx_device", qlen=qlen, batch=b):
                    with span("prepare"):
                        (nseg, qstack, dlo, dhi, qb,
                         qh) = self._stack_prepared(queries, spec)
                    with span("device_scan"):
                        ((ad2, asid, aoff), ast, cert, leaf_v, _, _, _,
                         nblk, aplan) = self._device_approx_stage(
                            qstack, dlo, dhi, qb, qh, nseg, k, spec)
                        (ad2, asid, aoff, ast, cert,
                         leaf_v) = jax.device_get(
                            (ad2, asid, aoff, ast, cert, leaf_v))
                    with span("merge"):
                        for row, i in enumerate(sub):
                            stats = SearchStats(
                                envelopes_total=n_comb,
                                lb_computations=nblk,
                                leaves_visited=int(leaf_v[row]),
                                exact_from_approx=bool(cert[row]),
                                envelopes_checked=int(ast[row, 1]),
                                true_dist_computations=int(ast[row, 2]),
                                dtw_lb_keogh=int(ast[row, 3]),
                                dtw_full=int(ast[row, 4]),
                                envelopes_pruned=int(ast[row, 5]),
                                chunks_visited=int(ast[row, 0]),
                                chunks_planned=aplan)
                            results[i] = self._knn_result_rows(
                                qs[i], spec, ad2[row], asid[row],
                                aoff[row], stats)
        return results

    def _local_range_device(self, qs, spec: QuerySpec):
        """Batched device eps-range (Alg. 5 with bsf := eps), one host
        sync per same-length batch on the no-overflow path.

        The scan carries a fixed-capacity hit buffer on device
        (executor.device_range_scan).  A query that overflows it syncs
        its plan order back and finishes chunks [ovf, n_chunks) through
        the host reference path — the buffer holds exactly the hits of
        the chunks before `ovf`, so the union is exact with no dedup
        (DESIGN.md §9).
        """
        results: List[Optional[SearchResult]] = [None] * len(qs)
        for qlen, idxs in self._group_by_len(qs):
            for sub, queries, b in self._padded_batches(qs, idxs):
                self._range_device_sub(qs, sub, queries, b, spec,
                                       results)
        return results

    def _range_device_sub(self, qs, sub, queries, b: int,
                          spec: QuerySpec, results) -> None:
        """One padded same-length sub-batch of the device range scan."""
        index, p = self._index, self.params
        env = index.search_envelopes()
        n_comb = env.size
        eps2 = float(spec.eps) ** 2
        with span("query.range_device", qlen=len(queries[0]),
                  batch=b) as qsp:
            with span("prepare"):
                nseg, qstack, dlo, dhi, qb, qh = self._stack_prepared(
                    queries, spec)
            with span("pack"):
                lbs = planner.env_lower_bounds_batch(
                    qb, qh, *index.lb_intervals(spec.use_paa_bounds),
                    env.valid, p.seg_len, nseg)
                n_pad = executor.pow2ceil(n_comb)
                (ssids, sanc, snm, slbs2,
                 order) = planner.device_range_pack(
                    env.series_id, env.anchor, env.n_master, lbs,
                    jnp.full((b,), eps2, jnp.float32), n_pad=n_pad)
            with span("device_scan"):
                store = self._paged_store()
                plan_h = None
                if store is None:
                    (bd2, bsid, boff, cnt, ovf, st,
                     chunk) = executor.device_range_scan(
                        index.collection, ssids, sanc, snm, slbs2,
                        qstack, dlo, dhi,
                        jnp.full((b,), eps2, jnp.float32),
                        capacity=spec.range_capacity, g=p.gamma + 1,
                        measure=spec.measure, r=spec.r, znorm=p.znorm,
                        chunk_size=spec.chunk_size)
                else:
                    # paged: plan readback (planned transfer), then the
                    # prefetching host-driven scan
                    plan_h = jax.device_get((ssids, sanc, snm, slbs2))
                    (bd2, bsid, boff, cnt, ovf, st,
                     chunk) = executor.paged_range_scan(
                        store, *plan_h, qstack, dlo, dhi,
                        np.full((b,), eps2, np.float32),
                        capacity=spec.range_capacity, g=p.gamma + 1,
                        measure=spec.measure, r=spec.r, znorm=p.znorm,
                        chunk_size=spec.chunk_size)
                # THE one host sync of the batch (overflow excepted)
                bd2, bsid, boff, cnt, ovf, st = jax.device_get(
                    (bd2, bsid, boff, cnt, ovf, st))
            n_chunks = n_pad // chunk
            order_h = slbs2_h = None
            overflows = 0
            for row, i in enumerate(sub):
                stats = SearchStats(
                    envelopes_total=n_comb, lb_computations=n_comb,
                    chunks_visited=int(st[row, 0]),
                    chunks_planned=n_chunks,
                    envelopes_checked=int(st[row, 1]),
                    true_dist_computations=int(st[row, 2]),
                    dtw_lb_keogh=int(st[row, 3]),
                    dtw_full=int(st[row, 4]),
                    envelopes_pruned=int(st[row, 5]))
                c = int(cnt[row])
                rows: list = []
                if c:
                    rows.append(np.stack(
                        [bsid[row, :c].astype(np.float64),
                         boff[row, :c].astype(np.float64),
                         bd2[row, :c].astype(np.float64)], axis=1))
                o = int(ovf[row])
                if o < n_chunks:     # buffer overflowed: host tail
                    stats.range_overflows += 1
                    overflows += 1
                    with span("host_continuation", query=i):
                        if store is not None:
                            # paged: replay the packed plan's tail
                            # against store-gathered windows — the
                            # payload never materializes
                            self._host_range_tail(
                                qs[i], spec, plan_h[0][row],
                                plan_h[1][row], plan_h[2][row],
                                plan_h[3][row], o * chunk, chunk, eps2,
                                rows, stats, store=store)
                        else:       # resident: replay via the env table
                            if order_h is None:    # lazy: overflow only
                                order_h = np.asarray(order)
                                slbs2_h = np.asarray(slbs2, np.float64)
                            pq = planner.prepare_query(
                                qs[i], p, spec.measure, spec.r)
                            sink = TopK(1)   # unused (collector path)
                            pos = o * chunk
                            while pos < n_pad:
                                seg = slbs2_h[row, pos:pos + chunk]
                                # packed rows are all true candidates
                                # (lb2 <= eps2); +inf = the padding tail
                                keep = np.isfinite(seg)
                                if not keep[0]:
                                    break
                                executor.verify_envelopes(
                                    index, pq,
                                    order_h[row, pos:pos + chunk][keep],
                                    sink, stats, eps2=eps2,
                                    collector=rows)
                                stats.chunks_visited += 1
                                pos += chunk
                with span("merge", query=i):
                    results[i] = self._range_result_rows(
                        rows, stats, q=qs[i], spec=spec)
            qsp.set(overflows=overflows)

    def _local_range(self, q, spec: QuerySpec) -> SearchResult:
        """All subsequences within eps of Q (Alg. 5 with bsf := eps)."""
        index = self._index
        pq = planner.prepare_query(q, self.params, spec.measure, spec.r)
        env = index.search_envelopes()      # main ++ ingestion delta
        stats = SearchStats(envelopes_total=int(env.size))
        eps2 = float(spec.eps) ** 2

        lbs = np.asarray(planner.env_lower_bounds(
            pq.paa_lo, pq.paa_hi, *index.lb_intervals(spec.use_paa_bounds),
            env.valid, self.params.seg_len, pq.nseg), np.float64)
        stats.lb_computations += env.size
        cand = np.nonzero((lbs ** 2) <= eps2)[0]
        stats.chunks_planned = -(-len(cand) // spec.chunk_size)
        rows: list = []
        pool = TopK(1)  # unused sink for API symmetry
        for start in range(0, len(cand), spec.chunk_size):
            executor.verify_envelopes(
                index, pq, cand[start:start + spec.chunk_size], pool,
                stats, eps2=eps2, collector=rows)
            stats.chunks_visited += 1
        return self._range_result_rows(rows, stats, q=q, spec=spec)

    # ------------------------------------------------------------------
    # distributed backend, device path: the sharded pruned scan
    # (DESIGN.md §10) — per-shard LB packs through the §8/§9 scan core
    # inside shard_map, a broadcast global bsf, one final cross-shard
    # merge, ONE host sync per batch
    # ------------------------------------------------------------------

    def _delta_active(self) -> bool:
        """True when queries must run the delta/gmap program families:
        per-shard delta rows exist, or the engine cold-opened from
        index sections (no global device payload to fall back to).
        The n_delta=0 cold case runs identical arithmetic to the
        classic family — the n_delta=0 pack IS the classic pack."""
        return (self._delta_total > 0 or self._cold_sections is not None
                or self._sharded is None)

    def _shard_main_blocks(self) -> list:
        """Per-shard host views of the MAIN payload (row order).  Warm
        engines derive them once from the device copy; cold-opened
        engines carry mmap handles from the store."""
        if self._shard_main is None:
            full = np.asarray(self._sharded)
            r = self._num_series // self._shards
            self._shard_main = [full[s * r:(s + 1) * r]
                                for s in range(self._shards)]
        return self._shard_main

    def _host_data(self) -> np.ndarray:
        """Host copy of the full (S, n) collection in GLOBAL id order
        (gathered once, cached) — feeds the f64 ED polish and the
        range-overflow continuation; never touched on the scan fast
        path.  With per-shard delta blocks the global order interleaves
        across shards (each append part row-sharded), so delta rows
        scatter back through their per-shard gmaps."""
        if getattr(self, "_host_data_cache", None) is None:
            if self._delta_total == 0 and self._sharded is not None:
                self._host_data_cache = np.asarray(self._sharded)
            else:
                total = self._num_series + self._delta_total
                out = np.empty((total, self._series_len), np.float32)
                mains = self._shard_main_blocks()
                r = self._num_series // self._shards
                for s in range(self._shards):
                    out[s * r:(s + 1) * r] = mains[s]
                    if self._shard_delta[s].shape[0]:
                        out[self._delta_gmaps[s]] = self._shard_delta[s]
                self._host_data_cache = out
        return self._host_data_cache

    def _delta_env_rows(self) -> int:
        """Per-shard envelope rows sitting in the delta buffer (static
        geometry of the delta k-NN pack; joins the program cache key)."""
        return (self.params.num_envelopes(self._series_len)
                * (self._delta_total // self._shards))

    def _shard_index_rows(self, s: int) -> dict:
        """Host index arrays (INDEX_SECTION_FIELDS) for shard `s`'s
        [main; delta] block, env series_id LOCAL to the block.

        Cold sections cover the block's saved prefix; only the series
        appended since (the delta tail) are summarized — appends only
        ever extend a shard's tail, so a saved section stays a valid
        prefix until compact() reshuffles the layout.  Per-series
        determinism (see distributed.ulisse.build_host_index) makes
        the concatenation bit-equal to summarizing the whole block.
        """
        from repro.distributed.ulisse import (INDEX_SECTION_FIELDS,
                                              build_host_index)
        mains = self._shard_main_blocks()
        dblk = self._shard_delta[s]
        r_m = mains[s].shape[0]
        blocks = []
        cov = 0
        if self._cold_sections is not None:
            sec = self._cold_sections[s]
            cov = int(sec["csum"].shape[0])
            blocks.append({f: np.asarray(sec[f])
                           for f in INDEX_SECTION_FIELDS})
        if cov < r_m + dblk.shape[0]:
            if cov < r_m:
                tail = (np.concatenate([mains[s][cov:], dblk])
                        if dblk.shape[0] else np.asarray(mains[s][cov:]))
            else:
                tail = dblk[cov - r_m:]
            idx = build_host_index(self.params, self._breakpoints, tail)
            idx["series_id"] = (idx["series_id"] + cov).astype(np.int32)
            blocks.append(idx)
        if len(blocks) == 1:
            return blocks[0]
        return {f: np.concatenate([b[f] for b in blocks])
                for f in INDEX_SECTION_FIELDS}

    def _shard_gmap(self, s: int) -> np.ndarray:
        """gmap for shard `s`: local data row -> GLOBAL series id.  The
        main prefix is affine by construction (contiguous row split);
        the delta tail carries the recorded per-part ids."""
        r_m = self._num_series // self._shards
        return np.concatenate(
            [np.arange(s * r_m, (s + 1) * r_m, dtype=np.int64),
             self._delta_gmaps[s]])

    def _ensure_delta_index(self):
        """Device arrays for the delta/gmap program families: the 14
        SHARDED_INDEX_FIELDS plus gmap, built once lazily.

        Per-shard [main; delta] blocks concatenate host-side in shard
        order — equal block sizes per shard (appends divide by the
        shard count), so the contiguous row split of NamedSharding
        lands each shard exactly on its own block.  This is where a
        cold-opened engine first touches the payload bytes: open()
        itself reads manifest + mmap handles only (DESIGN.md §15)."""
        if getattr(self, "_delta_index", None) is None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            from repro.distributed.ulisse import INDEX_SECTION_FIELDS
            mains = self._shard_main_blocks()
            rows = [self._shard_index_rows(s)
                    for s in range(self._shards)]
            spec = P(self._axes if len(self._axes) > 1
                     else self._axes[0])
            sharding = NamedSharding(self._mesh, spec)

            def put(field):
                blocks = [rows[s][field] for s in range(self._shards)]
                return jax.device_put(
                    blocks[0] if len(blocks) == 1
                    else np.concatenate(blocks), sharding)

            data = [np.concatenate([np.asarray(mains[s]),
                                    self._shard_delta[s]])
                    if self._shard_delta[s].shape[0]
                    else np.asarray(mains[s])
                    for s in range(self._shards)]
            gmap = [self._shard_gmap(s).astype(np.int32)
                    for s in range(self._shards)]
            arrs = (jax.device_put(
                        data[0] if len(data) == 1
                        else np.concatenate(data), sharding),)
            arrs += tuple(put(f) for f in INDEX_SECTION_FIELDS)
            arrs += (jax.device_put(
                        gmap[0] if len(gmap) == 1
                        else np.concatenate(gmap), sharding),)
            self._delta_index = arrs
        return self._delta_index

    def _ensure_sharded_index(self):
        """Per-shard device-resident index arrays, built once lazily.

        The legacy host path re-summarized every shard in-graph on
        every query; the device path pays the envelope build once and
        keeps collection prefix sums + envelope rows sharded on the
        mesh — numerically identical to a local build over the same
        series (same host float64-split prefix sums)."""
        if getattr(self, "_sharded_index", None) is None:
            from repro.distributed.ulisse import (SHARDED_INDEX_FIELDS,
                                                  build_sharded_index)
            arrs = build_sharded_index(
                self._mesh, self.params, self._breakpoints,
                self._host_data(), self._axes,
                data_sharded=self._sharded)
            self._sharded_index = tuple(arrs[f]
                                        for f in SHARDED_INDEX_FIELDS)
        return self._sharded_index

    def _device_batches(self, idxs):
        """max_batch-sized sub-batches, padded to a power of two (a
        lone query runs a 1-row program; compiles stay bounded at
        log2(max_batch)+1 shapes per length)."""
        for start in range(0, len(idxs), self.max_batch):
            sub = idxs[start:start + self.max_batch]
            yield sub, min(_pow2_bucket(len(sub), self.max_batch),
                           self.max_batch)

    def _sharded_knn_program(self, spec: QuerySpec):
        budget = _knn_budget(spec)
        key = PROGRAM_KEY_SPECS["sharded_knn"]["key"](spec)
        fn = self._programs.get(key)
        if fn is None:
            from repro.distributed.ulisse import make_sharded_knn_query
            fn = make_sharded_knn_query(
                self._mesh, self.params, self._breakpoints, k=spec.k,
                measure=spec.measure, r=spec.r,
                use_paa=spec.use_paa_bounds,
                chunk_size=spec.chunk_size,
                sync_every=spec.sync_every, budget_chunks=budget,
                axes=self._axes)
            self._programs[key] = fn
        return fn

    def _sharded_range_program(self, spec: QuerySpec):
        """Returns (query_fn, chunk) — the maker reports the plan-row
        chunking its program scans with, so the overflow continuation
        resumes at exactly the right row instead of re-deriving it."""
        key = PROGRAM_KEY_SPECS["sharded_range"]["key"](spec)
        entry = self._programs.get(key)
        if entry is None:
            from repro.distributed.ulisse import \
                make_sharded_range_query
            entry = make_sharded_range_query(
                self._mesh, self.params, self._breakpoints,
                capacity=spec.range_capacity,
                n_rows_per_shard=self._env_rows_per_shard,
                measure=spec.measure, r=spec.r,
                use_paa=spec.use_paa_bounds,
                chunk_size=spec.chunk_size, axes=self._axes)
            self._programs[key] = entry
        return entry

    def _sharded_delta_knn_program(self, spec: QuerySpec):
        """The delta/gmap k-NN family.  The per-shard delta geometry
        joins the key at the call site (like legacy_host_knn's bucket):
        it is engine state every append changes, and the maker bakes it
        in statically (delta-first pack width, stretched budget)."""
        d_rows = self._delta_env_rows()
        key = (PROGRAM_KEY_SPECS["sharded_delta_knn"]["key"](spec)
               + (d_rows,))
        fn = self._programs.get(key)
        if fn is None:
            from repro.distributed.ulisse import make_sharded_knn_query
            fn = make_sharded_knn_query(
                self._mesh, self.params, self._breakpoints, k=spec.k,
                measure=spec.measure, r=spec.r,
                use_paa=spec.use_paa_bounds,
                chunk_size=spec.chunk_size,
                sync_every=spec.sync_every,
                budget_chunks=_knn_budget(spec), axes=self._axes,
                delta_rows=d_rows, with_gmap=True)
            self._programs[key] = fn
        return fn

    def _sharded_delta_range_program(self, spec: QuerySpec):
        """The delta/gmap range family — same (query_fn, chunk) contract
        as _sharded_range_program; the packing width (main + delta env
        rows per shard) joins the key at the call site."""
        rows = self._env_rows_per_shard + self._delta_env_rows()
        key = (PROGRAM_KEY_SPECS["sharded_delta_range"]["key"](spec)
               + (rows,))
        entry = self._programs.get(key)
        if entry is None:
            from repro.distributed.ulisse import \
                make_sharded_range_query
            entry = make_sharded_range_query(
                self._mesh, self.params, self._breakpoints,
                capacity=spec.range_capacity, n_rows_per_shard=rows,
                measure=spec.measure, r=spec.r,
                use_paa=spec.use_paa_bounds,
                chunk_size=spec.chunk_size, axes=self._axes,
                with_gmap=True)
            self._programs[key] = entry
        return entry

    def _sharded_stats(self, st, row, n_env, extra_lb=0,
                       chunks_planned=0) -> SearchStats:
        """Fold the (P, B, executor.STATS_WIDTH) per-shard counter stack
        into SearchStats (sums over shards; the per-shard chunk counts
        are kept in `shard_chunks` for pruning diagnostics/tests)."""
        return SearchStats(
            envelopes_total=n_env,
            lb_computations=n_env + extra_lb,
            chunks_visited=int(st[:, row, 0].sum()),
            chunks_planned=chunks_planned,
            envelopes_checked=int(st[:, row, 1].sum()),
            true_dist_computations=int(st[:, row, 2].sum()),
            dtw_lb_keogh=int(st[:, row, 3].sum()),
            dtw_full=int(st[:, row, 4].sum()),
            envelopes_pruned=int(st[:, row, 5].sum()),
            shard_chunks=[int(x) for x in st[:, row, 0]])

    def _distributed_knn_device(self, qs, spec: QuerySpec):
        """Sharded k-NN (exact, or budget-capped approximate): one
        program retraced per (B, qlen) shape, one host sync per
        sub-batch.  Exactness is structural — the pruned scan only
        terminates when every shard's next LB-ordered chunk is beaten
        by the global kth — so there is no verify_top escalation loop
        to run; approximate mode reads the in-graph certificate."""
        budget = _knn_budget(spec)
        if self._delta_active():
            index_arrs = self._ensure_delta_index()
            fn = self._sharded_delta_knn_program(spec)
            d_rows = self._delta_env_rows()
            n_rows = self._env_rows_per_shard + d_rows
        else:
            index_arrs = self._ensure_sharded_index()
            fn = self._sharded_knn_program(spec)
            d_rows, n_rows = 0, self._env_rows_per_shard
        n_env = (self.params.num_envelopes(self._series_len)
                 * (self._num_series + self._delta_total))
        # per-shard plan geometry (mirrors make_sharded_knn_query):
        # pow2-padded rows per shard, chunked like the local scan,
        # delta rows chunk-padded ahead of the main region
        n_pad, chunk, _ = executor.shard_pack_geometry(
            n_rows, d_rows, spec.chunk_size)
        planned = self._shards * (n_pad // chunk)
        results: List[Optional[SearchResult]] = [None] * len(qs)
        for qlen, idxs in self._group_by_len(qs):
            self._bucket(qlen)             # length-range validation
            for sub, b in self._device_batches(idxs):
                queries = [qs[i] for i in sub]
                queries += [queries[0]] * (b - len(sub))
                with span("query.sharded_knn", qlen=qlen, batch=b,
                          shards=self._shards):
                    with span("prepare"):
                        (_, qstack, dlo, dhi, qb,
                         qh) = self._stack_prepared(queries, spec)
                    with span("device_scan"):
                        d2, sid, off, st, cert = jax.device_get(
                            fn(*index_arrs, qstack, dlo, dhi, qb, qh))
                    with span("merge"):
                        for row, i in enumerate(sub):
                            stats = self._sharded_stats(
                                st, row, n_env, chunks_planned=planned)
                            if budget:
                                stats.exact_from_approx = bool(cert[row])
                            results[i] = self._knn_result_rows(
                                qs[i], spec, d2[row], sid[row],
                                off[row], stats, data=self._host_data())
        return results

    def _distributed_range_device(self, qs, spec: QuerySpec):
        """Sharded eps-range: per-shard §9 hit buffers (no collectives
        — the eps cut never moves), concatenated on readback; a
        (query, shard) pair that overflows its buffer is finished by
        the host continuation over that shard's returned plan tail
        (union exact, no dedup — the buffer holds exactly the hits of
        the chunks before `ovf`)."""
        if self._delta_active():
            index_arrs = self._ensure_delta_index()
            fn, chunk = self._sharded_delta_range_program(spec)
        else:
            index_arrs = self._ensure_sharded_index()
            fn, chunk = self._sharded_range_program(spec)
        eps2 = float(spec.eps) ** 2
        cap = executor.pow2ceil(spec.range_capacity)
        n_env = (self.params.num_envelopes(self._series_len)
                 * (self._num_series + self._delta_total))
        results: List[Optional[SearchResult]] = [None] * len(qs)
        for qlen, idxs in self._group_by_len(qs):
            self._bucket(qlen)
            for sub, b in self._device_batches(idxs):
                queries = [qs[i] for i in sub]
                queries += [queries[0]] * (b - len(sub))
                with span("query.sharded_range", qlen=qlen, batch=b,
                          shards=self._shards):
                    with span("prepare"):
                        (_, qstack, dlo, dhi, qb,
                         qh) = self._stack_prepared(queries, spec)
                    with span("device_scan"):
                        out = fn(*index_arrs, qstack, dlo, dhi, qb, qh,
                                 jnp.full((b,), eps2, jnp.float32))
                        # THE one host sync of the batch (overflow
                        # excepted: plan arrays stay on device)
                        bd2, bsid, boff, cnt, ovf, st = jax.device_get(
                            out[:6])
                    plan, plan_h = out[6:], None
                    n_chunks = plan[3].shape[2] // chunk
                    for row, i in enumerate(sub):
                        stats = self._sharded_stats(
                            st, row, n_env,
                            chunks_planned=self._shards * n_chunks)
                        rows: list = []
                        for sh in range(self._shards):
                            c = int(cnt[sh, row])
                            if c:
                                lo = sh * cap
                                rows.append(np.stack(
                                    [bsid[row, lo:lo + c]
                                     .astype(np.float64),
                                     boff[row, lo:lo + c]
                                     .astype(np.float64),
                                     bd2[row, lo:lo + c]
                                     .astype(np.float64)], axis=1))
                            o = int(ovf[sh, row])
                            if o < n_chunks:   # buffer spilled
                                stats.range_overflows += 1
                                with span("host_continuation",
                                          query=i, shard=sh):
                                    if plan_h is None:  # overflow only
                                        plan_h = jax.device_get(plan)
                                    self._host_range_tail(
                                        qs[i], spec,
                                        plan_h[0][sh, row],
                                        plan_h[1][sh, row],
                                        plan_h[2][sh, row],
                                        plan_h[3][sh, row], o * chunk,
                                        chunk, eps2, rows, stats)
                        with span("merge", query=i):
                            results[i] = self._range_result_rows(
                                rows, stats, q=qs[i], spec=spec,
                                data=self._host_data())
        return results

    def _range_result_rows(self, rows, stats, q=None, spec=None,
                           data=None) -> SearchResult:
        if rows:
            out = np.concatenate(rows, axis=0)
            sid = out[:, 0].astype(np.int64)
            off = out[:, 1].astype(np.int64)
            d2 = out[:, 2]
            if q is not None and spec is not None \
                    and spec.measure == "ed":
                # membership was decided per-path (device f32 d2 vs
                # eps2; host tail f64); the REPORTED distances get the
                # shared f64 rescore so resident/paged/host/distributed
                # paths answer bit-equal on the same hit set
                d2 = self._ed_rescore(q, sid, off, data)
            order = np.argsort(d2, kind="stable")
            return SearchResult(
                dists=np.sqrt(np.maximum(d2[order], 0.0)),
                series=sid[order], offsets=off[order], stats=stats)
        return SearchResult(dists=np.zeros((0,)),
                            series=np.zeros((0,), np.int64),
                            offsets=np.zeros((0,), np.int64),
                            stats=stats)

    def _host_range_tail(self, q, spec: QuerySpec, sids, anc, nm, lbs2,
                         start: int, chunk: int, eps2: float,
                         rows: list, stats: SearchStats, *,
                         store=None) -> None:
        """§9 overflow continuation for one (query, shard) pair: replay
        the packed plan's chunks from `start` against the host data
        copy.  The plan rows are all true candidates (lb2 <= eps2,
        GLOBAL series ids) in the exact order the device scanned — the
        buffer holds the hits of chunks [0, start/chunk), this collects
        the rest, so the union is exact with no dedup.  Windows gather
        through numpy fancy indexing (a jitted device gather would ship
        the full host collection back to a device per call); the
        distance tiers are executor.verify_windows, shared with the
        index-driven reference path so the cut rules live once.

        `store`: paged local backend — gather each chunk's rows through
        the PayloadStore's page cache (`take_rows`) instead of a full
        host copy, so the continuation stays within the memory budget.
        """
        p = self.params
        g = p.gamma + 1
        if store is None:
            data = self._host_data()
            n = data.shape[1]
        else:
            n = store.series_len
        qlen = len(q)
        pq = planner.prepare_query(q, p, spec.measure, spec.r)
        sink = TopK(1)   # unused (collector path)
        pos = start
        while pos < len(lbs2):
            keep = np.isfinite(lbs2[pos:pos + chunk])
            if not keep[0]:
                break   # candidates are a packed prefix; +inf = tail
            csid = sids[pos:pos + chunk][keep].astype(np.int64)
            canc = anc[pos:pos + chunk][keep].astype(np.int64)
            cnm = nm[pos:pos + chunk][keep].astype(np.int64)
            # same masters-that-fit test as gather_windows, in numpy
            offs = canc[:, None] + np.arange(g)
            ok = ((np.arange(g)[None, :] < cnm[:, None])
                  & (offs + qlen <= n))
            offs_c = np.clip(offs, 0, n - qlen)
            all_sid = np.repeat(csid, g)
            if store is None:
                win = data[all_sid[:, None],
                           offs_c.reshape(-1)[:, None] + np.arange(qlen)]
            else:
                crows = store.take_rows(csid)    # (len(csid), n) f32
                ridx = np.repeat(np.arange(len(csid)), g)
                win = crows[ridx[:, None],
                            offs_c.reshape(-1)[:, None] + np.arange(qlen)]
            stats.envelopes_checked += int(keep.sum())
            executor.verify_windows(
                jnp.asarray(win, jnp.float32), all_sid,
                offs.reshape(-1), ok.reshape(-1), pq, p.znorm, sink,
                stats, eps2=eps2, collector=rows)
            stats.chunks_visited += 1
            pos += chunk

    # ------------------------------------------------------------------
    # distributed backend, legacy host path (PR-1 unpruned per-shard
    # verify + escalation) — kept as the scan_backend="host" reference
    # oracle and the benchmark baseline of the sharded scan
    # ------------------------------------------------------------------

    def _bucket(self, qlen: int) -> int:
        p = self.params
        if not (p.lmin <= qlen <= p.lmax):
            raise ValueError(
                f"query length {qlen} outside [{p.lmin}, {p.lmax}]")
        return _pow2_bucket(qlen, p.lmax)

    def _program(self, bucket: int, spec: QuerySpec, verify_top: int):
        # the escalation loop doubles verify_top past spec.verify_top,
        # so the clamped live value re-enters the declared key through
        # replace(); bucket is shape-derived (pow2 of qlen), appended
        # outside the QuerySpec-coverage contract
        k = spec.k
        key = PROGRAM_KEY_SPECS["legacy_host_knn"]["key"](
            dataclasses.replace(spec, verify_top=verify_top)) + (bucket,)
        fn = self._programs.get(key)
        if fn is None:
            from repro.distributed.ulisse import \
                make_batched_distributed_query
            fn = make_batched_distributed_query(
                self._mesh, self.params, self._breakpoints, bucket=bucket,
                k=k, axes=self._axes, verify_top=verify_top)
            self._programs[key] = fn
        return fn

    def _search_distributed(self, qs: List[np.ndarray],
                            spec: QuerySpec) -> List[SearchResult]:
        if (spec.measure != "ed" or spec.is_range or spec.mode != "exact"
                or spec.use_paa_bounds):
            raise NotImplementedError(
                "the legacy distributed host backend answers exact ED "
                "k-NN with quantized breakpoint bounds only; use "
                "scan_backend='device' (the default) for distributed "
                "DTW / range / approximate / use_paa_bounds queries")
        if self._delta_active():
            raise NotImplementedError(
                "the legacy distributed host backend predates per-"
                "shard delta buffers and cold-opened index sections; "
                "compact() first, or use scan_backend='device' (the "
                "default), which searches the delta in-graph")
        results: List[Optional[SearchResult]] = [None] * len(qs)
        by_bucket = {}
        for i, q in enumerate(qs):
            by_bucket.setdefault(self._bucket(len(q)), []).append(i)
        for bucket, idxs in sorted(by_bucket.items()):
            for start in range(0, len(idxs), self.max_batch):
                chunk = idxs[start:start + self.max_batch]
                for i, res in zip(chunk,
                                  self._run_chunk(qs, chunk, bucket, spec)):
                    results[i] = res
        return results

    def _run_chunk(self, qs, chunk, bucket: int,
                   spec: QuerySpec) -> List[SearchResult]:
        """One padded device batch, with internal exactness escalation:
        queries whose certificate fails are re-packed into a (smaller)
        batch and retried with doubled verify_top until the certificate
        holds or the whole shard is verified.

        The batch dimension pads to the next power of two (capped at
        max_batch) so a lone query runs a 1-row program instead of
        paying for max_batch rows; jit re-specializes per batch shape,
        bounding compiles at log2(max_batch)+1 per (bucket, spec)."""
        out: List[Optional[SearchResult]] = [None] * len(chunk)
        pending = list(range(len(chunk)))          # rows into `chunk`
        vt = spec.verify_top
        escalations = 0
        cap = self._env_rows_per_shard
        while pending:
            B = min(_pow2_bucket(len(pending), self.max_batch),
                    self.max_batch)
            qpad = np.zeros((B, bucket), np.float32)
            qlens = np.full((B,), self.params.lmin, np.int32)
            for row, ci in enumerate(pending):
                q = qs[chunk[ci]]
                qpad[row, : len(q)] = q
                qlens[row] = len(q)
            fn = self._program(bucket, spec, min(vt, cap))
            d, codes, exact = fn(self._sharded, jnp.asarray(qpad),
                                 jnp.asarray(qlens))
            d = np.asarray(d)
            codes = np.asarray(codes)
            exact_np = np.asarray(exact) | (vt >= cap)
            still = []
            for row, ci in enumerate(pending):
                if exact_np[row]:
                    out[ci] = self._distributed_result(
                        d[row], codes[row], escalations, min(vt, cap))
                else:
                    still.append(ci)
            pending = still
            if pending:
                vt *= 2
                escalations += 1
        return out

    def _distributed_result(self, d, codes, escalations: int,
                            verified_rows: int) -> SearchResult:
        stats = SearchStats(
            envelopes_total=(self.params.num_envelopes(self._series_len)
                             * self._num_series),
            envelopes_checked=verified_rows * self._shards,
            escalations=escalations)
        return SearchResult(dists=np.asarray(d, np.float64),
                            series=codes[:, 0].astype(np.int64),
                            offsets=codes[:, 1].astype(np.int64),
                            stats=stats)
