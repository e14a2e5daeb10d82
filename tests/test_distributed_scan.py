"""The sharded pruned device scan (PR 5): distributed == local device
backend across shard counts, measures, normalizations, and query types,
plus the global-bsf pruning property.

Like tests/test_distributed.py these run in SUBPROCESSES because
--xla_force_host_platform_device_count must be set before jax
initializes; the sharded scan's own tests force 4 devices (the CI
multi-device job count) and build meshes of 1/2/4 shards from them.
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


def test_distributed_vs_local_equivalence_matrix():
    """Same top-k codes/distances and identical eps-range hit sets as
    the local device backend, across shard counts {1, 2, 4} x
    znorm/raw x ed/dtw x kNN/range — the sharded scan is a sharding
    layer over the same core, so answers must not depend on the mesh.
    The eps-range leg also exercises the per-shard overflow
    continuation (range_capacity=2 forces every shard's buffer to
    spill) and asserts the union stays exact."""
    run_sub("""
        import jax, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        rng = np.random.default_rng(7)
        data = np.cumsum(rng.normal(size=(16, 96)), -1).astype(np.float32)

        def codes(res):
            return set(zip(res.series.tolist(), res.offsets.tolist()))

        for znorm in (True, False):
            p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8,
                               card=64, znorm=znorm)
            local = UlisseEngine.from_collection(
                Collection.from_array(data), p)
            qs = [data[1, 5:45] + rng.normal(size=40).astype(np.float32) * .02,
                  data[9, 11:51] + rng.normal(size=40).astype(np.float32) * .02,
                  data[4, 40:88] + rng.normal(size=48).astype(np.float32) * .02]
            for shards in (1, 2, 4):
                mesh = jax.make_mesh((shards,), ("data",))
                dist = UlisseEngine.distributed(mesh, p, data, max_batch=4)
                for measure, r in (("ed", 0), ("dtw", 3)):
                    spec = QuerySpec(k=5, measure=measure, r=r,
                                     chunk_size=16)
                    rd = dist.search(qs, spec)
                    rl = local.search(qs, spec)
                    for a, b in zip(rd, rl):
                        assert codes(a) == codes(b), \\
                            (shards, znorm, measure, codes(a), codes(b))
                        assert np.allclose(a.dists, b.dists, atol=2e-3), \\
                            (shards, znorm, measure, a.dists, b.dists)
                    # eps around the 3rd NN so the hit set is
                    # non-trivial; capacity 2 exercises the per-shard
                    # continuation whenever any shard collects > 2 hits
                    eps = float(rl[0].dists[2]) + 1e-3
                    for cap in (2048, 2):
                        rspec = QuerySpec(eps=eps, measure=measure, r=r,
                                          chunk_size=16,
                                          range_capacity=cap)
                        ra = dist.search(qs[0], rspec)
                        rb = local.search(qs[0], rspec)
                        assert codes(ra) == codes(rb), \\
                            (shards, znorm, measure, cap,
                             codes(ra) ^ codes(rb))
                        assert np.allclose(
                            np.sort(ra.dists) ** 2,
                            np.sort(rb.dists) ** 2, atol=2e-2), \\
                            (shards, znorm, measure, cap)
                print(f"shards={shards} znorm={znorm} ok", flush=True)
            # guaranteed overflow: with eps covering EVERY subsequence,
            # each shard's 2-row buffer must spill and the per-shard
            # host continuation must reproduce the full hit set
            mesh = jax.make_mesh((4,), ("data",))
            dist = UlisseEngine.distributed(mesh, p, data, max_batch=4)
            ospec = QuerySpec(eps=1e4, chunk_size=16, range_capacity=2)
            ro = dist.search(qs[0], ospec)
            rb = local.search(qs[0], QuerySpec(eps=1e4, chunk_size=16))
            assert ro.stats.range_overflows == 4, \\
                ro.stats.range_overflows
            assert codes(ro) == codes(rb), (znorm, len(ro.series),
                                            len(rb.series))
            print(f"overflow znorm={znorm} ok", flush=True)
        print("ok")
    """)


def test_global_bsf_prunes_sharded_scan():
    """The broadcast global bsf is what makes the sharded scan prune:
    (a) with bsf sharing on (sync_every=1) no shard scans deeper down
    its LB order than the local single-device scan had to — the shared
    kth is at least as tight as the local scan's own; (b) turning
    sharing off (sync_every >= n_chunks, shards merged only at the
    end) can only increase the chunks visited, because each shard then
    prunes with its weaker local-pool kth."""
    run_sub("""
        import jax, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        rng = np.random.default_rng(3)
        # shard 0 (series 0-3 on the 4-way mesh) holds near-copies of
        # the query; every other shard holds structurally different
        # series, so only a SHARED bsf lets shards 1..3 prune early
        t = np.arange(128, dtype=np.float32)
        base = np.sin(t / 7).astype(np.float32)
        data = np.stack(
            [np.cumsum(rng.normal(size=128)).astype(np.float32) * 3
             for _ in range(16)])
        for s in range(4):
            data[s] = base + rng.normal(size=128).astype(np.float32) * .01
        p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8,
                           card=64, znorm=True)
        q = base[20:60] + rng.normal(size=40).astype(np.float32) * .005
        mesh = jax.make_mesh((4,), ("data",))
        dist = UlisseEngine.distributed(mesh, p, data, max_batch=4)
        local = UlisseEngine.from_collection(Collection.from_array(data), p)
        on = dist.search(q, QuerySpec(k=3, chunk_size=8, sync_every=1))
        off = dist.search(q, QuerySpec(k=3, chunk_size=8, sync_every=64))
        ref = local.search(q, QuerySpec(k=3, chunk_size=8,
                                        approx_first=False))
        assert on.stats.shard_chunks is not None
        print("shard_chunks on:", on.stats.shard_chunks,
              "off:", off.stats.shard_chunks,
              "local:", ref.stats.chunks_visited)
        # (a) the sharded scan visits no more chunks per shard than the
        # local device scan visits in total
        assert max(on.stats.shard_chunks) <= ref.stats.chunks_visited, \\
            (on.stats.shard_chunks, ref.stats.chunks_visited)
        # (b) sharing the bsf never increases work, and actually prunes
        # the far shards on this workload
        assert on.stats.chunks_visited <= off.stats.chunks_visited, \\
            (on.stats.chunks_visited, off.stats.chunks_visited)
        assert on.stats.envelopes_checked < on.stats.envelopes_total
        # answers agree regardless of cadence
        assert np.allclose(on.dists, off.dists, atol=1e-5)
        assert np.allclose(on.dists, ref.dists, atol=2e-3)
        print("ok")
    """)


def test_distributed_approx_mode_and_program_cache():
    """Approximate mode runs as a budget-capped sharded scan with an
    in-graph certificate; one compiled program object serves every
    query length (retraced per shape, not re-made per length)."""
    run_sub("""
        import jax, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        rng = np.random.default_rng(5)
        data = np.cumsum(rng.normal(size=(16, 96)), -1).astype(np.float32)
        p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8,
                           card=64, znorm=True)
        mesh = jax.make_mesh((4,), ("data",))
        dist = UlisseEngine.distributed(mesh, p, data, max_batch=4)
        local = UlisseEngine.from_collection(Collection.from_array(data), p)
        q40 = data[1, 5:45] + rng.normal(size=40).astype(np.float32) * .02
        q48 = data[4, 40:88] + rng.normal(size=48).astype(np.float32) * .02
        spec = QuerySpec(k=3, chunk_size=16)
        for q in (q40, q48):
            a = dist.search(q, spec)
            b = local.search(q, spec)
            assert np.allclose(a.dists, b.dists, atol=2e-3)
        # ONE knn program object across both lengths
        assert len(dist._programs) == 1, list(dist._programs)
        # a generous budget covers every chunk -> certificate proves
        # exactness; the same answer as the exact scan
        ra = dist.search(q40, QuerySpec(k=3, mode="approx",
                                        chunk_size=16, max_leaves=64))
        assert ra.stats.exact_from_approx
        assert np.allclose(ra.dists, dist.search(q40, spec).dists,
                           atol=1e-5)
        # a 1-chunk budget on a pool-priming workload may or may not
        # certify, but must never claim exactness falsely: re-check
        # against the exact answer whenever it does
        rb = dist.search(q40, QuerySpec(k=3, mode="approx",
                                        chunk_size=16, max_leaves=1))
        if rb.stats.exact_from_approx:
            assert np.allclose(rb.dists, dist.search(q40, spec).dists,
                               atol=1e-5)
        print("ok")
    """)
