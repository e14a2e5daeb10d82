"""Distributed logic on 8 fake host devices.

These run in SUBPROCESSES because --xla_force_host_platform_device_count
must be set before jax initializes, and the main pytest process must
keep seeing the single real device (per the dry-run contract).
"""
import os
import subprocess
import sys
import textwrap

import pytest

# virtual devices are a CPU-backend knob: the children never touch a chip
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH=os.pathsep.join([os.path.join(_ROOT, "src"), _ROOT]))


def run_sub(code: str):
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=ENV, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_query_exactness():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.types import Collection, EnvelopeParams
        from repro.core import isax
        from repro.core.search import brute_force_knn
        from repro.distributed.ulisse import (make_distributed_query,
                                              shard_collection, decode_id)
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(7)
        data = np.cumsum(rng.normal(size=(64, 128)), -1).astype(np.float32)
        p = EnvelopeParams(lmin=48, lmax=96, gamma=8, seg_len=16,
                           card=64, znorm=True)
        bp = isax.gaussian_breakpoints(p.card)
        for qi in (3, 20, 41):
            q = data[qi, 9:73] + rng.normal(size=64).astype(np.float32)*.02
            qfn = make_distributed_query(mesh, p, bp, qlen=64, k=5,
                                         verify_top=256)
            d, codes, exact = qfn(shard_collection(mesh, jnp.asarray(data)),
                                  jnp.asarray(q))
            ref = brute_force_knn(Collection.from_array(data), q, k=5,
                                  znorm=True)
            assert bool(exact), "exactness certificate failed"
            # 5e-3: dot-identity ED (brute oracle) cancels near d=0
            assert np.allclose(np.asarray(d), ref.dists, atol=5e-3), \\
                (np.asarray(d), ref.dists)
        print("ok")
    """)


def test_distributed_engine_batched_mixed_lengths():
    """UlisseEngine distributed backend (sharded pruned scan): mixed
    query lengths through ONE compiled program object (retraced per
    (B, qlen) shape); every exact answer matches brute force, on both
    the device default and the legacy host reference backend."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (Collection, EnvelopeParams, QuerySpec,
                                UlisseEngine)
        from repro.core.search import brute_force_knn
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(7)
        data = np.cumsum(rng.normal(size=(64, 128)), -1).astype(np.float32)
        p = EnvelopeParams(lmin=48, lmax=96, gamma=8, seg_len=16,
                           card=64, znorm=True)
        eng = UlisseEngine.distributed(mesh, p, data, max_batch=4)
        qs = []
        for qi, ql in ((3, 64), (20, 96), (41, 64), (11, 80), (5, 96)):
            o = rng.integers(0, 128 - ql + 1)
            qs.append(data[qi, o:o + ql]
                      + rng.normal(size=ql).astype(np.float32) * .02)
        out = eng.search(qs, QuerySpec(k=5))
        coll = Collection.from_array(data)
        for q, r in zip(qs, out):
            ref = brute_force_knn(coll, q, k=5, znorm=True)
            # 5e-3: dot-identity ED (brute oracle) cancels near d=0
            assert np.allclose(r.dists, ref.dists, atol=5e-3), \\
                (r.dists, ref.dists)
        # one sharded-scan program serves all three lengths
        assert len(eng._programs) == 1, list(eng._programs)
        # legacy host reference (PR-1 unpruned verify + escalation)
        out_h = eng.search(qs, QuerySpec(k=5, verify_top=256,
                                         scan_backend="host"))
        for r, rh in zip(out, out_h):
            assert np.allclose(r.dists, rh.dists, atol=5e-3), \\
                (r.dists, rh.dists)
        # host path adds its ("legacy", k, verify_top, bucket)
        # programs (key shape declared in engine.PROGRAM_KEY_SPECS):
        # lengths {64, 80, 96} bucket to {64, 96}
        assert sorted(k[-1] for k in eng._programs
                      if k[0] == "legacy") == [64, 96], \\
            sorted(map(str, eng._programs))
        print("ok")
    """)


def test_distributed_engine_rejects_non_divisible_mesh():
    """num_series % shards != 0 used to silently truncate the
    rows-per-shard table, under-counting the escalation cap and letting
    a failed certificate read as 'fully verified' — the constructor
    must refuse loudly instead (PR 4 satellite)."""
    run_sub("""
        import jax, numpy as np
        from repro.core import EnvelopeParams, UlisseEngine
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        p = EnvelopeParams(lmin=48, lmax=96, gamma=8, seg_len=16,
                           card=64, znorm=True)
        data = np.cumsum(rng.normal(size=(65, 128)), -1)  # 65 % 8 != 0
        try:
            UlisseEngine.distributed(mesh, p, data)
        except ValueError as e:
            assert "not divisible" in str(e), e
        else:
            raise AssertionError("non-divisible mesh accepted silently")
        # the divisible case still constructs and answers
        eng = UlisseEngine.distributed(mesh, p, data[:64])
        res = eng.search(data[3, 9:73].astype(np.float32))
        assert res.dists.shape == (1,)
        print("ok")
    """)


def test_topk_merge_and_bsf():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import topk_merge, bsf_allreduce
        mesh = jax.make_mesh((8,), ("x",))
        def local(d, i):
            md, mi = topk_merge(d, i, 3, "x")
            return md, mi, bsf_allreduce(jnp.min(d), "x")
        d = jnp.arange(24, dtype=jnp.float32)[::-1].reshape(8, 3) / 10
        i = jnp.arange(24, dtype=jnp.int32).reshape(8, 3)
        f = jax.shard_map(local, mesh=mesh,
                          in_specs=(P("x"), P("x")),
                          out_specs=(P(), P(), P()), check_vma=False)
        md, mi, bsf = f(d.reshape(24), i.reshape(24))
        np.testing.assert_allclose(np.asarray(md), [0.0, 0.1, 0.2])
        assert float(bsf) == 0.0
        print("ok")
    """)


def test_ef_int8_allreduce_error_feedback():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import ef_int8_allreduce
        mesh = jax.make_mesh((8,), ("x",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
        def local(xs):
            red, err = ef_int8_allreduce(xs[0], jnp.zeros_like(xs[0]), "x")
            return red[None], err[None]
        f = jax.shard_map(local, mesh=mesh, in_specs=(P("x"),),
                          out_specs=(P("x"), P("x")), check_vma=False)
        red, err = f(x)
        exact = np.mean(np.asarray(x), axis=0)
        got = np.asarray(red)[0]
        # quantized mean within int8 tolerance; error feedback bounded
        assert np.max(np.abs(got - exact)) < 0.05
        assert np.max(np.abs(np.asarray(err))) < np.max(np.abs(x)) / 100
        print("ok")
    """)


def test_ring_allgather_matmul():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import ring_allgather_matmul
        mesh = jax.make_mesh((8,), ("x",))
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        def local(xs, w):
            return ring_allgather_matmul(xs, w, "x", 8)[None]
        f = jax.shard_map(local, mesh=mesh, in_specs=(P("x"), P()),
                          out_specs=P("x"), check_vma=False)
        y = np.asarray(f(x, w))[0]
        np.testing.assert_allclose(y, np.asarray(x) @ np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
        print("ok")
    """)


