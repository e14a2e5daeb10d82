"""Distributed streaming ingestion + O(index) cold start (PR 10,
DESIGN.md §15): per-shard delta buffers searched through the
delta-first shard pack, mesh-wide compact(), and section-carrying
persistence.

The equivalence matrix mirrors tests/test_distributed_scan.py and the
PR-4 brute-force matrix: a distributed engine that STREAMED part of
its data in via append() must answer exactly like a local engine fed
the same stream and like the brute-force oracle over the final
collection — across znorm/raw x ED/DTW x kNN/range and shard counts.
compact() must be bit-identical to a from-scratch sharded build of the
full collection, a cold open() must answer bit-equal to the warm
engine it was saved from while reading O(index) bytes (no
re-summarization, payload left as mmap handles), and a writer killed
inside the commit window must roll back to the previous committed
index on the next open.

Subprocess pattern as in test_distributed_scan.py: the sharded legs
need --xla_force_host_platform_device_count staged before jax init.
"""
import os
import subprocess
import sys
import textwrap

# virtual devices are a CPU-backend knob: the children never touch a chip
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=4",
           PYTHONPATH=os.pathsep.join([os.path.join(_ROOT, "src"), _ROOT]))


def run_sub(code: str):
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=ENV, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_append_equivalence_matrix():
    """distributed append -> search == local append -> search ==
    brute force, across znorm/raw x ed/dtw x kNN/range x shards
    {1, 2, 4}, with the stream split over TWO append batches so the
    per-shard delta holds non-contiguous global ids (the gmap case).
    Raw mode pins explicit breakpoints: default_breakpoints calibrates
    on the data it is handed, and the matrix needs every engine
    quantizing identically."""
    run_sub("""
        import jax, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        from repro.core.index import default_breakpoints
        from repro.core.search import brute_force_knn, brute_force_range

        rng = np.random.default_rng(7)
        base = np.cumsum(rng.normal(size=(16, 96)), -1).astype(np.float32)
        ex1 = np.cumsum(rng.normal(size=(8, 96)), -1).astype(np.float32)
        ex2 = np.cumsum(rng.normal(size=(4, 96)), -1).astype(np.float32)
        full = np.concatenate([base, ex1, ex2])
        coll = Collection.from_array(full)

        def codes(res):
            return set(zip(res.series.tolist(), res.offsets.tolist()))

        for znorm in (True, False):
            p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8,
                               card=64, znorm=znorm)
            bp = default_breakpoints(p, jax.numpy.asarray(base))
            local = UlisseEngine.from_collection(
                Collection.from_array(base), p, breakpoints=bp)
            local.append(ex1)
            local.append(ex2)
            qs = [full[1, 5:45] + rng.normal(size=40).astype(np.float32) * .02,
                  full[17, 11:51] + rng.normal(size=40).astype(np.float32) * .02,
                  full[25, 40:88] + rng.normal(size=48).astype(np.float32) * .02]
            for shards in (1, 2, 4):
                mesh = jax.make_mesh((shards,), ("data",))
                dist = UlisseEngine.distributed(mesh, p, base,
                                                breakpoints=bp,
                                                max_batch=4)
                dist.append(ex1)
                dist.append(ex2)
                for measure, r in (("ed", 0), ("dtw", 3)):
                    spec = QuerySpec(k=5, measure=measure, r=r,
                                     chunk_size=16)
                    rd = dist.search(qs, spec)
                    rl = local.search(qs, spec)
                    for q, a, b in zip(qs, rd, rl):
                        bf = brute_force_knn(coll, q, k=5, znorm=znorm,
                                             measure=measure, r=r)
                        assert codes(a) == codes(b) == codes(bf), \\
                            (shards, znorm, measure, codes(a),
                             codes(b), codes(bf))
                        assert np.allclose(a.dists, b.dists,
                                           atol=2e-3), \\
                            (shards, znorm, measure)
                        assert np.allclose(a.dists, bf.dists,
                                           atol=2e-2), \\
                            (shards, znorm, measure)
                    eps = float(rl[0].dists[2]) + 1e-3
                    rspec = QuerySpec(eps=eps, measure=measure, r=r,
                                      chunk_size=16)
                    ra = dist.search(qs[0], rspec)
                    rb = local.search(qs[0], rspec)
                    bf = brute_force_range(coll, qs[0], eps,
                                           znorm=znorm,
                                           measure=measure, r=r)
                    assert codes(ra) == codes(rb) == codes(bf), \\
                        (shards, znorm, measure,
                         codes(ra) ^ codes(bf))
                print(f"shards={shards} znorm={znorm} ok", flush=True)
        print("ok")
    """)


def test_compact_bit_identical_to_rebuild():
    """compact() folds the per-shard deltas into the main sorted
    envelope set; the result must be BIT-identical to a from-scratch
    sharded build of the final collection (same breakpoints) at shards
    {1, 2, 4} — every array of the served index tuple compares equal,
    not just the answers."""
    run_sub("""
        import jax, numpy as np
        from repro.core import EnvelopeParams, QuerySpec, UlisseEngine
        from repro.core.index import default_breakpoints

        rng = np.random.default_rng(3)
        base = np.cumsum(rng.normal(size=(16, 96)), -1).astype(np.float32)
        ex1 = np.cumsum(rng.normal(size=(8, 96)), -1).astype(np.float32)
        ex2 = np.cumsum(rng.normal(size=(4, 96)), -1).astype(np.float32)
        full = np.concatenate([base, ex1, ex2])
        q = full[20, 7:47].copy()
        for znorm in (True, False):
            p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8,
                               card=64, znorm=znorm)
            bp = default_breakpoints(p, jax.numpy.asarray(base))
            for shards in (1, 2, 4):
                mesh = jax.make_mesh((shards,), ("data",))
                eng = UlisseEngine.distributed(mesh, p, base,
                                               breakpoints=bp,
                                               max_batch=4)
                eng.append(ex1)
                eng.append(ex2)
                before = eng.search(q, QuerySpec(k=5, chunk_size=16))
                eng.compact()
                assert eng.delta_size == 0
                fresh = UlisseEngine.distributed(mesh, p, full,
                                                 breakpoints=bp,
                                                 max_batch=4)
                a = eng._ensure_sharded_index()
                b = fresh._ensure_sharded_index()
                assert len(a) == len(b)
                for x, y in zip(a, b):
                    np.testing.assert_array_equal(np.asarray(x),
                                                  np.asarray(y))
                after = eng.search(q, QuerySpec(k=5, chunk_size=16))
                assert np.array_equal(before.series, after.series)
                assert np.array_equal(before.offsets, after.offsets)
                print(f"shards={shards} znorm={znorm} bit-identical",
                      flush=True)
        print("ok")
    """)


def test_cold_open_bit_equal_and_o_index():
    """A cold open() of a delta-carrying distributed save must (a)
    answer bit-equal to the warm engine it was saved from, (b) never
    re-run summarization (build_envelope_set / host_prefix_stats are
    poisoned across the open), and (c) eagerly read only O(index)
    bytes — the raw payload stays behind mmap handles until first
    search.  The eager-read budget is asserted against the payload
    size recorded in the manifest shard table."""
    run_sub("""
        import os, tempfile
        import jax, numpy as np
        from repro.core import EnvelopeParams, QuerySpec, UlisseEngine
        from repro.storage import format as fmt

        rng = np.random.default_rng(5)
        base = np.cumsum(rng.normal(size=(16, 96)), -1).astype(np.float32)
        extra = np.cumsum(rng.normal(size=(8, 96)), -1).astype(np.float32)
        p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8,
                           card=64, znorm=True)
        mesh = jax.make_mesh((4,), ("data",))
        eng = UlisseEngine.distributed(mesh, p, base, max_batch=4)
        eng.append(extra)
        q = base[3, 5:45].copy()
        spec = QuerySpec(k=5, chunk_size=16)
        rspec = QuerySpec(eps=float(eng.search(q, spec).dists[3]),
                          chunk_size=16)
        warm = eng.search(q, spec)
        warmr = eng.search(q, rspec)
        path = os.path.join(tempfile.mkdtemp(), "idx")
        eng.save(path)

        # poison summarization + meter eager payload reads for the
        # whole open(): the O(index) contract is structural, so ANY
        # summarize call or eager payload materialization fails here
        import repro.core.envelope as envelope
        import repro.core.types as core_types
        import repro.distributed.ulisse as du

        def boom(*a, **k):
            raise AssertionError("cold open re-ran summarization")

        saved = (envelope.build_envelope_set,
                 core_types.host_prefix_stats, du.build_envelope_set)
        envelope.build_envelope_set = boom
        core_types.host_prefix_stats = boom
        du.build_envelope_set = boom

        eager = {"bytes": 0}
        orig_load = fmt.load_array

        def metered(directory, entry, mmap=False):
            arr = orig_load(directory, entry, mmap=mmap)
            if not mmap:
                eager["bytes"] += int(np.asarray(arr).nbytes)
            return arr

        fmt.load_array = metered
        try:
            cold = UlisseEngine.open(path, mesh=mesh)
        finally:
            fmt.load_array = orig_load
            (envelope.build_envelope_set,
             core_types.host_prefix_stats,
             du.build_envelope_set) = saved

        manifest = fmt.read_manifest(path)
        payload = sum(int(np.prod(e["shape"])) * 4
                      for e in manifest["collection_shards"])
        assert payload > 0
        # eager reads: breakpoints + per-shard gmaps — orders of
        # magnitude under the payload even at this tiny scale
        assert eager["bytes"] < payload // 4, (eager, payload)
        print(f"eager={eager['bytes']}B payload={payload}B", flush=True)

        coldk = cold.search(q, spec)
        assert np.array_equal(warm.series, coldk.series)
        assert np.array_equal(warm.offsets, coldk.offsets)
        assert np.array_equal(warm.dists, coldk.dists)
        coldr = cold.search(q, rspec)
        assert np.array_equal(warmr.series, coldr.series)
        assert np.array_equal(warmr.offsets, coldr.offsets)
        assert np.array_equal(warmr.dists, coldr.dists)

        # the reopened engine keeps full write capability: append and
        # compact on top of the restored sections
        more = np.cumsum(rng.normal(size=(4, 96)), -1).astype(np.float32)
        cold.append(more)
        cold.compact()
        assert cold.delta_size == 0
        assert cold.raw_data.shape[0] == 28
        print("ok")
    """)


def test_delta_stats_parity():
    """tests/test_stats_parity.py schema, delta present: for a
    pruning-free kNN (k >= every window, approx_first=False) the
    row-level work counters of a delta-carrying distributed engine
    must equal the host reference over the SAME final collection —
    envelopes_checked, true_dist_computations, envelopes_pruned == 0 —
    and the chunk funnel must stay self-consistent (sum(shard_chunks)
    == chunks_visited <= chunks_planned; per-shard ceil rounding may
    only ADD chunks vs the host's single stream)."""
    run_sub("""
        import jax, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        rng = np.random.default_rng(11)
        base = np.cumsum(rng.normal(size=(16, 256)), -1).astype(np.float32)
        extra = np.cumsum(rng.normal(size=(8, 256)), -1).astype(np.float32)
        full = np.concatenate([base, extra])
        p = EnvelopeParams(lmin=96, lmax=160, gamma=16, seg_len=16,
                           card=64, znorm=True)
        local = UlisseEngine.from_collection(
            Collection.from_array(full), p)
        q = full[3, 9:9 + 128] \\
            + rng.normal(size=128).astype(np.float32) * .05
        big_k = full.shape[0] * full.shape[1]
        spec = dict(k=big_k, approx_first=False, chunk_size=16)
        ref = local.search(q, QuerySpec(scan_backend="host",
                                        **spec)).stats
        assert ref.envelopes_checked > 0
        assert ref.true_dist_computations > 0
        for shards in (1, 2, 4):
            mesh = jax.make_mesh((shards,), ("data",))
            dist = UlisseEngine.distributed(mesh, p, base, max_batch=4)
            dist.append(extra)
            st = dist.search(q, QuerySpec(scan_backend="device",
                                          **spec)).stats
            line = (shards, st.envelopes_checked, st.envelopes_pruned,
                    st.true_dist_computations, st.chunks_visited,
                    st.chunks_planned)
            print(*line, flush=True)
            assert st.envelopes_checked == ref.envelopes_checked, line
            assert st.true_dist_computations == \\
                ref.true_dist_computations, line
            assert st.envelopes_pruned == 0, line
            assert st.chunks_visited >= ref.chunks_visited, line
            assert st.chunks_planned >= st.chunks_visited, line
            assert st.shard_chunks is not None
            assert len(st.shard_chunks) == shards
            assert sum(st.shard_chunks) == st.chunks_visited, line
        print("ok")
    """)


def test_crash_in_commit_window_rolls_back():
    """A writer killed between the commit protocol's two renames (old
    index moved aside, new one not yet in place) must leave the
    PREVIOUS committed index recoverable: the next open() runs
    gc_stale_tmp, rolls the old directory back, and answers from the
    pre-crash state."""
    run_sub("""
        import os, tempfile
        import jax, numpy as np
        from repro.core import EnvelopeParams, QuerySpec, UlisseEngine
        from repro.storage import format as fmt

        rng = np.random.default_rng(9)
        base = np.cumsum(rng.normal(size=(16, 96)), -1).astype(np.float32)
        extra = np.cumsum(rng.normal(size=(8, 96)), -1).astype(np.float32)
        p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8,
                           card=64, znorm=True)
        mesh = jax.make_mesh((4,), ("data",))
        q = base[3, 5:45].copy()
        spec = QuerySpec(k=5, chunk_size=16)

        eng = UlisseEngine.distributed(mesh, p, base, max_batch=4)
        path = os.path.join(tempfile.mkdtemp(), "idx")
        eng.save(path)                       # committed v1
        v1 = eng.search(q, spec)

        eng.append(extra)

        # crash INSIDE the commit window of the v2 save: the rename
        # that would promote <path>.tmp to <path> never happens, after
        # v1 was already moved aside to <path>.old
        orig_rename = os.rename
        def killed(src, dst):
            if src.endswith(".tmp"):
                raise OSError("simulated crash between commit renames")
            return orig_rename(src, dst)
        os.rename = killed
        try:
            try:
                eng.save(path)
                raise SystemExit("save unexpectedly committed")
            except OSError:
                pass
        finally:
            os.rename = orig_rename
        # the crash left no committed <path>, only <path>.old + .tmp
        assert not os.path.exists(path)
        assert os.path.exists(path + ".old")

        reopened = UlisseEngine.open(path, mesh=mesh)
        assert os.path.exists(path)          # rolled back by open()
        assert not os.path.exists(path + ".old")
        assert not os.path.exists(path + ".tmp")
        assert reopened.raw_data.shape[0] == 16   # v1, not v2
        r = reopened.search(q, spec)
        assert np.array_equal(v1.series, r.series)
        assert np.array_equal(v1.offsets, r.offsets)
        assert np.array_equal(v1.dists, r.dists)

        # and a clean retry of the v2 save commits normally
        eng.save(path)
        v2 = UlisseEngine.open(path, mesh=mesh)
        assert v2.raw_data.shape[0] == 24
        print("ok")
    """)
