"""Save / open whole indexes (the storage subsystem's reader half).

`save_index` serializes a built `UlisseIndex` — main sorted envelopes,
block levels, breakpoints, row-sharded raw series, and the delta buffer
if one exists — under the atomic commit protocol of `format.py`.

`open_index` is the cold-open path: it reads the manifest and the
envelope/level payloads (they are needed by the very first lower-bound
computation) but wraps the raw series in a `LazyCollection`, so opening
an index costs O(index) I/O, not O(raw data); the series shards are
mmap'd and materialized only when verification first gathers windows.

The distributed backend stores no envelopes (its shard programs
summarize raw series on device — see distributed/ulisse.py), so its
on-disk form is just the shard table + per-shard raw payloads; restore
re-shards onto ANY mesh, like train/checkpoint.py's elastic restore.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.index import SYMBOL_FIELDS, BlockLevel, UlisseIndex
from repro.core.types import (Collection, EnvelopeParams, EnvelopeSet,
                              PageBlock)
from repro.storage import format as fmt

# struct-of-arrays fields of an EnvelopeSet, in constructor order
ENV_FIELDS = ("paa_lo", "paa_hi", "sym_lo", "sym_hi",
              "series_id", "anchor", "n_master", "valid")
LEVEL_FIELDS = ("paa_lo", "paa_hi", "valid")
SORT_ORDER = "isax_lo_lex_stable"   # (invalid, sym_lo[0..w)) stable lexsort

DEFAULT_PAGE_ROWS = 256             # series rows per payload page


class PayloadStore:
    """The tiered payload: fixed-size series-row pages over the stored
    shards, with an LRU page cache under byte accounting.

    Duck-types `Collection` two ways:

      * whole-resident (`materialize()` / `.data` / `.csum` / ...):
        builds the real Collection on first touch, exactly like the old
        LazyCollection — the one-page special case the engine uses when
        the payload fits `memory_budget_bytes`;
      * paged (`load_page` / `take_rows` / `read_rows`): fixed
        `page_rows`-row `PageBlock`s whose hi/lo prefix sums are
        computed per page through the SAME `host_prefix_stats` helper
        `Collection.from_array` uses, so paged answers are bit-equal to
        whole-resident ones.  Pages go through an LRU cache bounded by
        `cache_limit_bytes` (seismiqb-style `cache_bytes`/`reset_cache`
        accounting); `stats()` exposes monotone hit/miss/evicted-bytes
        counters for the obs registry.

    Size queries (`num_series`, `series_len`) stay cold — they come
    from the manifest.  `with_appended` supports incremental ingestion
    on a cold-open index (`UlisseEngine.append` via storage.delta):
    appended parts queue as host row blocks — O(new series) memory, NO
    shard read — and fold per-page into whatever page covers them, so
    cold-open -> append -> search never pays an O(raw data)
    materialization.

    Thread-safe for concurrent `load_page`/`take_rows`: the paged scan
    driver's prefetch worker loads page t+1 while the main thread
    consumes page t.
    """

    def __init__(self, path: Optional[str], shards: List[dict],
                 num_series: int, series_len: int,
                 pending: Optional[list] = None,
                 page_rows: int = DEFAULT_PAGE_ROWS,
                 cache_limit_bytes: Optional[int] = None,
                 mem: Optional[np.ndarray] = None):
        self._path = path
        self._shards = list(shards)
        self._mem = mem
        self._num_stored = num_series
        self._series_len = series_len
        self._pending: list = list(pending or [])
        self._page_rows = int(page_rows)
        if self._page_rows < 1:
            raise ValueError("page_rows must be >= 1")
        self._coll: Optional[Collection] = None
        self._sources: Optional[list] = None
        self._lock = threading.RLock()
        self._cache: "OrderedDict[int, PageBlock]" = OrderedDict()
        self._cache_bytes = 0
        self._limit = cache_limit_bytes
        self._hits = 0
        self._misses = 0
        self._evicted_bytes = 0

    @classmethod
    def from_arrays(cls, data, page_rows: int = DEFAULT_PAGE_ROWS,
                    cache_limit_bytes: Optional[int] = None
                    ) -> "PayloadStore":
        """An in-memory paged store (tests / audits): same page and
        cache semantics, backed by one host array instead of shards."""
        arr = np.ascontiguousarray(data, np.float32)
        if arr.ndim == 1:
            arr = arr[None]
        return cls(None, [], arr.shape[0], arr.shape[1],
                   page_rows=page_rows,
                   cache_limit_bytes=cache_limit_bytes, mem=arr)

    # -- shape (cold: manifest-known, no I/O) --------------------------

    @property
    def num_series(self) -> int:
        return self._num_stored \
            + sum(p.shape[0] for p in self._pending)

    @property
    def series_len(self) -> int:
        return self._series_len

    @property
    def is_materialized(self) -> bool:
        return self._coll is not None

    @property
    def page_rows(self) -> int:
        return self._page_rows

    @property
    def num_pages(self) -> int:
        return -(-self.num_series // self._page_rows)

    @property
    def payload_bytes(self) -> int:
        """Host bytes of the FULL paged payload (raw rows + the four
        (n+1)-wide prefix-sum planes + centers, all float32) — what the
        engine compares against `memory_budget_bytes`."""
        s, n = self.num_series, self._series_len
        return 4 * (s * n + 4 * s * (n + 1) + s)

    # -- ingestion -----------------------------------------------------

    def with_appended(self, part: Collection) -> "PayloadStore":
        """A new PayloadStore with `part`'s series appended (O(new)).

        The part's raw rows are exported to host ONCE, here (append
        time, between dispatches) — page loads during a measured search
        never touch a device array.  The page cache restarts empty: the
        boundary page's contents change when pending rows fold into it,
        and appends are rare next to page loads.
        """
        if part.series_len != self._series_len:
            raise ValueError(
                f"appended series_len {part.series_len} != stored "
                f"series_len {self._series_len}")
        rows = np.ascontiguousarray(np.asarray(part.data), np.float32)
        return PayloadStore(self._path, self._shards, self._num_stored,
                            self._series_len,
                            pending=self._pending + [rows],
                            page_rows=self._page_rows,
                            cache_limit_bytes=self._limit, mem=self._mem)

    # -- row extents over shards + pending -----------------------------

    def _extents(self) -> list:
        """[(start_row, rows_array)] covering [0, num_series): mmap'd
        shard payloads (opened once, lazily) followed by pending parts."""
        if self._sources is None:
            exts: list = []
            start = 0
            if self._mem is not None:
                exts.append((0, self._mem))
                start = self._mem.shape[0]
            else:
                for e in self._shards:
                    exts.append((start, fmt.load_array(
                        self._path, e, mmap=True)))
                    start += int(e["shape"][0])
            for p in self._pending:
                exts.append((start, p))
                start += p.shape[0]
            self._sources = exts
        return self._sources

    def read_rows(self, lo: int, hi: int) -> np.ndarray:
        """Raw rows [lo, hi) as one (hi-lo, n) float32 block.

        Single-extent ranges return a zero-copy view (mmap slice);
        ranges spanning extents are copied into one preallocated
        destination — never concatenated, never more than the result's
        own bytes of transient memory.
        """
        exts = self._extents()
        for start, arr in exts:
            if start <= lo and hi <= start + arr.shape[0]:
                return arr[lo - start:hi - start]
        out = np.empty((hi - lo, self._series_len), np.float32)
        for start, arr in exts:
            a = max(lo, start)
            b = min(hi, start + arr.shape[0])
            if a < b:
                out[a - lo:b - lo] = arr[a - start:b - start]
        return out

    # -- the page cache ------------------------------------------------

    def load_page(self, p: int) -> PageBlock:
        """Page `p` (rows [p*R, (p+1)*R)), through the LRU cache.

        The block build (shard read + per-page prefix sums) runs
        OUTSIDE the lock so a prefetch worker's load overlaps the
        consumer's cache hits.  A block bigger than the whole budget is
        returned uncached — `cache_bytes` never exceeds the limit.
        """
        with self._lock:
            blk = self._cache.get(p)
            if blk is not None:
                self._hits += 1
                self._cache.move_to_end(p)
                return blk
        lo = p * self._page_rows
        hi = min(lo + self._page_rows, self.num_series)
        if not 0 <= lo < hi:
            raise IndexError(
                f"page {p} outside [0, {self.num_pages})")
        blk = PageBlock.from_rows(lo, self.read_rows(lo, hi))
        with self._lock:
            self._misses += 1
            raced = self._cache.get(p)
            if raced is not None:
                return raced
            if self._limit is None or blk.nbytes <= self._limit:
                while (self._limit is not None and self._cache
                       and self._cache_bytes + blk.nbytes > self._limit):
                    _, old = self._cache.popitem(last=False)
                    self._cache_bytes -= old.nbytes
                    self._evicted_bytes += old.nbytes
                if (self._limit is None
                        or self._cache_bytes + blk.nbytes <= self._limit):
                    self._cache[p] = blk
                    self._cache_bytes += blk.nbytes
            return blk

    def take_rows(self, sids) -> np.ndarray:
        """Raw rows for (possibly unsorted) global series ids, gathered
        through the page cache: (len(sids), n) float32."""
        sids = np.asarray(sids, np.int64).ravel()
        out = np.empty((sids.size, self._series_len), np.float32)
        pages = sids // self._page_rows
        for p in np.unique(pages):
            blk = self.load_page(int(p))
            m = pages == p
            out[m] = blk.data[sids[m] - blk.start]
        return out

    @property
    def cache_bytes(self) -> int:
        with self._lock:
            return self._cache_bytes

    @property
    def cache_limit_bytes(self) -> Optional[int]:
        return self._limit

    @cache_limit_bytes.setter
    def cache_limit_bytes(self, limit: Optional[int]) -> None:
        with self._lock:
            self._limit = limit
            while (limit is not None and self._cache
                   and self._cache_bytes > limit):
                _, old = self._cache.popitem(last=False)
                self._cache_bytes -= old.nbytes
                self._evicted_bytes += old.nbytes

    def reset_cache(self) -> None:
        """Drop every cached page; `cache_bytes` goes to zero.  The
        monotone hit/miss/evicted counters are NOT reset (they mirror
        into the process registry, which scrapers expect monotone)."""
        with self._lock:
            self._cache.clear()
            self._cache_bytes = 0

    def stats(self) -> Dict[str, int]:
        """{hits, misses, evicted_bytes, cache_bytes, cached_pages} —
        the first three monotone, the rest gauges."""
        with self._lock:
            return {"hits": self._hits, "misses": self._misses,
                    "evicted_bytes": self._evicted_bytes,
                    "cache_bytes": self._cache_bytes,
                    "cached_pages": len(self._cache)}

    # -- whole-resident special case (Collection duck type) ------------

    def materialize(self) -> Collection:
        """The full Collection, built on first touch.

        Peak transient memory is the destination block itself: rows are
        copied extent-by-extent into ONE preallocated array (the old
        per-shard `np.asarray` + `np.concatenate` transiently held ~2x
        the payload), and a single-extent store hands its mmap straight
        to `Collection.from_array` with no host copy at all.
        """
        if self._coll is None:
            exts = self._extents()
            if len(exts) == 1:
                data = exts[0][1]
            else:
                data = np.empty((self.num_series, self._series_len),
                                np.float32)
                for start, arr in exts:
                    data[start:start + arr.shape[0]] = arr
            self._coll = Collection.from_array(data)
        return self._coll

    @property
    def data(self):
        return self.materialize().data

    @property
    def csum(self):
        return self.materialize().csum

    @property
    def csum2(self):
        return self.materialize().csum2

    @property
    def csum_lo(self):
        return self.materialize().csum_lo

    @property
    def csum2_lo(self):
        return self.materialize().csum2_lo

    @property
    def center(self):
        return self.materialize().center

    def window_stats(self, sid, off, length):
        return self.materialize().window_stats(sid, off, length)


# the pre-paging name: PayloadStore subsumed LazyCollection's lazy
# whole-resident behavior as its one-page special case
LazyCollection = PayloadStore


# --------------------------------------------------------------------------
# local indexes
# --------------------------------------------------------------------------

def _save_envelope_set(tmp: str, group: str, env: EnvelopeSet,
                       arrays: dict) -> None:
    for field in ENV_FIELDS:
        rel = f"{group}/{field}"
        arrays[rel] = fmt.save_array(tmp, rel, getattr(env, field))


def _load_envelope_set(path: str, group: str, arrays: dict) -> EnvelopeSet:
    # the symbols stay on the host, where a UlisseIndex keeps them
    return EnvelopeSet(*(
        (np.asarray if field in SYMBOL_FIELDS else jnp.asarray)(
            fmt.load_array(path, arrays[f"{group}/{field}"]))
        for field in ENV_FIELDS))


def save_index(path: str, index: UlisseIndex, shard_rows: int = 4096,
               page_rows: int = DEFAULT_PAGE_ROWS) -> str:
    """Serialize a local index to `path` (atomically). Returns `path`.

    An unmaterialized `PayloadStore` collection is streamed shard block
    by shard block through `read_rows` — saving a paged index never
    materializes the payload.  The manifest records the page table
    (`page_rows`; page boundaries are derived — page p is rows
    [p*page_rows, (p+1)*page_rows), an additive key older readers
    ignore and `open_index` defaults when absent).
    """
    p: EnvelopeParams = index.params
    tmp = fmt.stage_dir(path, "envelopes", "levels", "collection")
    arrays: dict = {}

    _save_envelope_set(tmp, "envelopes", index.envelopes, arrays)
    for k, lvl in enumerate(index.levels):
        for field in LEVEL_FIELDS:
            rel = f"levels/L{k}_{field}"
            arrays[rel] = fmt.save_array(tmp, rel, getattr(lvl, field))
    arrays["breakpoints"] = fmt.save_array(tmp, "breakpoints",
                                           index.breakpoints)
    if index.delta is not None:
        os.makedirs(os.path.join(tmp, "delta"), exist_ok=True)
        _save_envelope_set(tmp, "delta", index.delta, arrays)

    coll = index.collection
    if isinstance(coll, PayloadStore) and not coll.is_materialized:
        total, series_len = coll.num_series, coll.series_len
        blocks = (coll.read_rows(start, min(start + shard_rows, total))
                  for start in range(0, total, shard_rows))
    else:
        data = np.asarray(coll.data)
        total, series_len = data.shape
        blocks = (data[start:start + shard_rows]
                  for start in range(0, total, shard_rows))
    shards = []
    for block in blocks:
        rel = f"collection/shard_{len(shards):05d}"
        shards.append(fmt.save_array(tmp, rel, block))

    fmt.write_manifest(tmp, {
        "kind": fmt.KIND_LOCAL,
        "params": fmt.params_to_dict(p),
        "sort_order": SORT_ORDER,
        "block_size": index.block_size,
        "num_levels": index.num_levels,
        "num_envelopes": index.envelopes.size,
        "num_series": int(total),
        "series_len": int(series_len),
        "has_delta": index.delta is not None,
        "arrays": arrays,
        "collection_shards": shards,
        "page_table": {"page_rows": int(page_rows),
                       "num_pages": -(-int(total) // int(page_rows))},
    })
    return fmt.commit(path)


def open_index(path: str, params: Optional[EnvelopeParams] = None,
               mmap: bool = True) -> UlisseIndex:
    """Open a saved local index; raw series load lazily (see module doc).

    params: when given, validated against the stored EnvelopeParams —
    a mismatch raises IndexCompatibilityError instead of returning an
    engine that computes wrong distances.
    """
    fmt.gc_stale_tmp(path)
    manifest = fmt.read_manifest(path)
    if manifest["kind"] != fmt.KIND_LOCAL:
        raise fmt.IndexFormatError(
            f"{path!r} holds a {manifest['kind']!r} index; open it with "
            "UlisseEngine.open(path, mesh=...)")
    stored = fmt.params_from_dict(manifest["params"])
    fmt.validate_params(stored, params)
    arrays = manifest["arrays"]

    env = _load_envelope_set(path, "envelopes", arrays)
    if env.w != stored.w:
        raise fmt.IndexFormatError(
            f"envelope payload has {env.w} PAA segments, params imply "
            f"{stored.w} — index is corrupt")
    levels = [
        BlockLevel(*(jnp.asarray(
            fmt.load_array(path, arrays[f"levels/L{k}_{field}"]))
            for field in LEVEL_FIELDS))
        for k in range(manifest["num_levels"])
    ]
    delta = (_load_envelope_set(path, "delta", arrays)
             if manifest.get("has_delta") else None)
    page_rows = (manifest.get("page_table") or {}).get(
        "page_rows", DEFAULT_PAGE_ROWS)
    collection = PayloadStore(path, manifest["collection_shards"],
                              manifest["num_series"],
                              manifest["series_len"],
                              page_rows=page_rows)
    if not mmap:
        collection = collection.materialize()
    return UlisseIndex(
        envelopes=env, levels=levels, collection=collection,
        breakpoints=jnp.asarray(fmt.load_array(path, arrays["breakpoints"])),
        params=stored, delta=delta)


# --------------------------------------------------------------------------
# distributed indexes (per-shard raw payloads)
# --------------------------------------------------------------------------

def save_distributed(path: str, params: EnvelopeParams, breakpoints,
                     shard_arrays, axes=("data",),
                     max_batch: int = 8, *, delta_blocks=None,
                     delta_gmaps=None, sections=None) -> str:
    """Serialize a distributed engine's state as per-shard payloads.

    `shard_arrays`: per-shard (rows, n) MAIN host arrays in row order
    (see distributed.ulisse.shard_host_arrays) — one payload file each,
    so a multi-host deployment writes only its addressable shards.

    The ingestion/cold-start extensions (DESIGN.md §15), all additive
    to the PR-2 manifest (FORMAT_VERSION stays 1; old readers ignore
    the extra keys and still see the main payload shards):

      delta_blocks  per-shard (d, n) uncompacted delta rows;
      delta_gmaps   per-shard (d,) GLOBAL series ids of those rows
                    (append parts interleave shards, so the map is not
                    affine and must be recorded);
      sections      per-shard dicts of INDEX_SECTION_FIELDS covering
                    the shard's FULL [main; delta] block — envelope
                    rows AND prefix-sum planes, env series_id local.
                    With these, `load_distributed_sections` reopens
                    O(index): no summarization, payload bytes mmap'd
                    and only materialized at first search.

    One staged directory, one atomic commit — a crash between the
    per-shard writes and the manifest leaves only a staging dir for
    `gc_stale_tmp` to sweep; readers never see a half save.
    """
    shard_arrays = [np.asarray(s, np.float32) for s in shard_arrays]
    dirs = ["shards"]
    if delta_blocks is not None and any(
            b.shape[0] for b in delta_blocks):
        dirs.append("delta")
    if sections is not None:
        dirs.append("index")
    tmp = fmt.stage_dir(path, *dirs)
    arrays = {"breakpoints": fmt.save_array(tmp, "breakpoints", breakpoints)}
    shards = []
    for s, rows in enumerate(shard_arrays):
        rel = f"shards/shard_{s:05d}"
        shards.append(fmt.save_array(tmp, rel, rows))
    delta_rows = 0
    if "delta" in dirs:
        delta_rows = int(delta_blocks[0].shape[0])
        for s, (blk, gmap) in enumerate(zip(delta_blocks, delta_gmaps)):
            rel = f"delta/shard_{s:05d}"
            arrays[rel] = fmt.save_array(
                tmp, rel, np.asarray(blk, np.float32))
            rel = f"delta/shard_{s:05d}_gmap"
            arrays[rel] = fmt.save_array(
                tmp, rel, np.asarray(gmap, np.int64))
    if sections is not None:
        from repro.distributed.ulisse import INDEX_SECTION_FIELDS
        for s, sec in enumerate(sections):
            for field in INDEX_SECTION_FIELDS:
                rel = f"index/shard_{s:05d}_{field}"
                arrays[rel] = fmt.save_array(tmp, rel, sec[field])
    fmt.write_manifest(tmp, {
        "kind": fmt.KIND_DISTRIBUTED,
        "params": fmt.params_to_dict(params),
        "num_series": int(sum(s.shape[0] for s in shard_arrays)),
        "series_len": int(shard_arrays[0].shape[1]),
        "axes": list(axes),
        "max_batch": max_batch,
        "delta_rows_per_shard": delta_rows,
        "index_sections": sections is not None,
        "arrays": arrays,
        "collection_shards": shards,
    })
    return fmt.commit(path)


def load_distributed_sections(path: str,
                              params: Optional[EnvelopeParams] = None):
    """The O(index) cold-open payload of a distributed save, or None.

    Returns (params, breakpoints, manifest, mains, deltas, delta_gmaps,
    sections) — mains/deltas are per-shard mmap handles (no payload
    bytes read), sections per-shard dicts of mmap'd
    INDEX_SECTION_FIELDS arrays.  None when `path` holds a local index
    or a pre-section distributed save — callers fall back to
    `load_raw_data` + re-summarization then.
    """
    fmt.gc_stale_tmp(path)
    manifest = fmt.read_manifest(path)
    if (manifest["kind"] != fmt.KIND_DISTRIBUTED
            or not manifest.get("index_sections")):
        return None
    from repro.distributed.ulisse import INDEX_SECTION_FIELDS
    stored = fmt.params_from_dict(manifest["params"])
    fmt.validate_params(stored, params)
    arrays = manifest["arrays"]
    mains = [fmt.load_array(path, e, mmap=True)
             for e in manifest["collection_shards"]]
    n = int(manifest["series_len"])
    deltas, gmaps, sections = [], [], []
    for s in range(len(mains)):
        key = f"delta/shard_{s:05d}"
        if key in arrays:
            deltas.append(fmt.load_array(path, arrays[key], mmap=True))
            gmaps.append(np.asarray(fmt.load_array(
                path, arrays[f"{key}_gmap"])))
        else:
            deltas.append(np.zeros((0, n), np.float32))
            gmaps.append(np.zeros((0,), np.int64))
        sections.append({
            f: fmt.load_array(path, arrays[f"index/shard_{s:05d}_{f}"],
                              mmap=True)
            for f in INDEX_SECTION_FIELDS})
    bp = fmt.load_array(path, arrays["breakpoints"])
    return (stored, jnp.asarray(bp), manifest, mains, deltas, gmaps,
            sections)


def load_raw_data(path: str, params: Optional[EnvelopeParams] = None):
    """Raw series + params + breakpoints from an index of EITHER kind.

    The re-sharding entry point: a distributed engine can be restored on
    any mesh size from these (the shard table is a layout hint, not a
    constraint), and a local index can be promoted to a distributed one.
    Uncompacted delta rows of a distributed save fold back into the
    returned array at their recorded GLOBAL ids, so re-sharding keeps
    every appended series.  Returns (params, breakpoints, data,
    manifest).
    """
    fmt.gc_stale_tmp(path)
    manifest = fmt.read_manifest(path)
    stored = fmt.params_from_dict(manifest["params"])
    fmt.validate_params(stored, params)
    arrays = manifest["arrays"]
    parts = [fmt.load_array(path, e, mmap=True)
             for e in manifest["collection_shards"]]
    data = parts[0] if len(parts) == 1 else np.concatenate(parts)
    data = np.asarray(data)
    d = int(manifest.get("delta_rows_per_shard", 0))
    if d:
        shards = len(manifest["collection_shards"])
        total = data.shape[0] + d * shards
        out = np.empty((total, data.shape[1]), np.float32)
        out[:data.shape[0]] = data
        for s in range(shards):
            key = f"delta/shard_{s:05d}"
            blk = np.asarray(fmt.load_array(path, arrays[key]))
            gmap = np.asarray(fmt.load_array(path, arrays[f"{key}_gmap"]))
            out[gmap] = blk
        data = out
    bp = fmt.load_array(path, arrays["breakpoints"])
    return stored, jnp.asarray(bp), data, manifest
