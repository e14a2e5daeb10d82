"""One SearchStats schema, every backend: host, local device, and the
sharded device scan must report the SAME work counters for the same
pruning-free query (DESIGN.md §12).

Pruning-free because that is the configuration where the work is
backend-independent by construction: k at least the total window count
keeps the best-so-far at +inf (kNN) and a huge eps accepts everything
(range), so every backend must check every envelope, verify every
window, and visit every planned chunk — any counter drift is a
telemetry bug, not a scheduling difference.

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


def test_knn_stats_agree_across_backends():
    """envelopes_checked / true_dist_computations / chunk funnel match
    across host, device, and sharded (1/2 shards) kNN paths."""
    run_sub("""
        import jax, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        rng = np.random.default_rng(11)
        data = np.cumsum(rng.normal(size=(16, 256)), -1)\\
            .astype(np.float32)
        p = EnvelopeParams(lmin=96, lmax=160, gamma=16, seg_len=16,
                           card=64, znorm=True)
        local = UlisseEngine.from_collection(
            Collection.from_array(data), p)
        q = data[3, 9:9 + 128] \\
            + rng.normal(size=128).astype(np.float32) * .05
        # k >= every window in scope: the bsf stays +inf, nothing can
        # prune, so the per-backend work is identical by construction
        big_k = data.shape[0] * data.shape[1]
        spec = dict(k=big_k, approx_first=False, chunk_size=16)

        stats = {}
        for name, backend in (("host", "host"), ("device", "device")):
            res = local.search(q, QuerySpec(scan_backend=backend,
                                            **spec))
            stats[name] = res.stats
        for shards in (1, 2):
            mesh = jax.make_mesh((shards,), ("data",))
            dist = UlisseEngine.distributed(mesh, p, data, max_batch=4)
            res = dist.search(q, QuerySpec(scan_backend="device",
                                           **spec))
            stats[f"dist{shards}"] = res.stats

        ref = stats["host"]
        assert ref.envelopes_checked > 0
        assert ref.true_dist_computations > 0
        assert ref.chunks_visited > 0
        for name, st in stats.items():
            line = (name, st.envelopes_checked, st.envelopes_pruned,
                    st.true_dist_computations, st.chunks_visited,
                    st.chunks_planned)
            print(*line)
            assert st.envelopes_checked == ref.envelopes_checked, line
            assert st.true_dist_computations == \\
                ref.true_dist_computations, line
            assert st.envelopes_pruned == 0, line   # nothing CAN prune
            assert st.chunks_visited == ref.chunks_visited, line
            # planned >= visited always; host plans exactly what it
            # visits, device plans include pow2 padding chunks
            assert st.chunks_planned >= st.chunks_visited, line
        # a sharded scan must not invent or lose chunks vs its own
        # per-shard report
        for shards in (1, 2):
            st = stats[f"dist{shards}"]
            assert st.shard_chunks is not None
            assert len(st.shard_chunks) == shards
            assert sum(st.shard_chunks) == st.chunks_visited
        print("knn parity ok")
        """)


def test_range_stats_agree_across_backends():
    """Same matrix for an eps-range query whose eps accepts every
    window: the range scan funnel is backend-independent too."""
    run_sub("""
        import jax, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        rng = np.random.default_rng(5)
        data = np.cumsum(rng.normal(size=(12, 192)), -1)\\
            .astype(np.float32)
        p = EnvelopeParams(lmin=64, lmax=96, gamma=8, seg_len=16,
                           card=64, znorm=True)
        local = UlisseEngine.from_collection(
            Collection.from_array(data), p)
        q = data[1, 4:4 + 64] \\
            + rng.normal(size=64).astype(np.float32) * .05
        # every z-normed window sits within eps: nothing prunes, every
        # envelope is checked and every window verified on each backend
        spec = dict(eps=1e3, chunk_size=16, range_capacity=1 << 14)

        stats = {}
        for name, backend in (("host", "host"), ("device", "device")):
            res = local.search(q, QuerySpec(scan_backend=backend,
                                            **spec))
            stats[name] = res.stats
        for shards in (1, 2):
            mesh = jax.make_mesh((shards,), ("data",))
            dist = UlisseEngine.distributed(mesh, p, data, max_batch=4)
            res = dist.search(q, QuerySpec(scan_backend="device",
                                           **spec))
            stats[f"dist{shards}"] = res.stats

        ref = stats["host"]
        assert ref.envelopes_checked > 0
        assert ref.true_dist_computations > 0
        for name, st in stats.items():
            line = (name, st.envelopes_checked, st.envelopes_pruned,
                    st.true_dist_computations, st.chunks_visited,
                    st.chunks_planned)
            print(*line)
            assert st.envelopes_checked == ref.envelopes_checked, line
            assert st.true_dist_computations == \\
                ref.true_dist_computations, line
            assert st.envelopes_pruned == 0, line
            assert st.chunks_visited == ref.chunks_visited, line
            assert st.chunks_planned >= st.chunks_visited, line
        print("range parity ok")
        """)
