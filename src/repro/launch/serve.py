"""ULISSE query service launcher (the paper's native serving workload).

    python -m repro.launch.serve --devices 8 --series 2048 --queries 60

Builds a sharded collection behind one `UlisseEngine`, wraps it in the
`repro.serve.UlisseServer` dynamic batcher, and drives it with a
closed-loop multi-client mixed-length workload: each client thread
submits a query, waits for its answer, submits the next.  Requests
coalesce into pow2 length buckets and dispatch as padded device
programs after --window-ms (or when a bucket fills to --batch); the
serial one-request-at-a-time loop is timed first as the baseline.
--sync-every still controls the sharded scan's global best-so-far
broadcast cadence inside each dispatched program.
"""
import argparse
import os
import sys
import time


def _ensure_device_count(n: int) -> None:
    """Serve on `n` virtual CPU devices, or refuse.

    `--xla_force_host_platform_device_count` is read when the backend
    initializes and only the CPU backend obeys it, so the flag is staged
    before that and the result verified after: on a TPU (or any other
    backend), or once an earlier import has started the backend with a
    different count, this raises instead of serving on the wrong mesh.
    """
    if not n:
        return
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        flag = f"--xla_force_host_platform_device_count={n}"
        prev = [f for f in os.environ.get("XLA_FLAGS", "").split()
                if not f.startswith(
                    "--xla_force_host_platform_device_count")]
        os.environ["XLA_FLAGS"] = " ".join(prev + [flag])
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"--devices {n} makes virtual CPU devices, but the backend "
            f"is {jax.default_backend()!r}; drop --devices to serve on "
            f"its {jax.device_count()} device(s)")
    if jax.device_count() != n:
        raise RuntimeError(
            f"--devices {n} requested but the jax backend is already "
            f"initialized with {jax.device_count()} device(s).  Set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "before the first jax import (or drop --devices).")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--series", type=int, default=1024)
    ap.add_argument("--series-len", type=int, default=256)
    ap.add_argument("--queries", type=int, default=48)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4,
                    help="max queries coalesced into one dispatch "
                         "(and fused into one device program)")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="chunks each shard scans between global "
                         "best-so-far broadcasts")
    ap.add_argument("--clients", type=int, default=8,
                    help="closed-loop client threads")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="bucket hold window before a non-full "
                         "dispatch")
    args = ap.parse_args(argv)

    _ensure_device_count(args.devices)
    import threading

    import numpy as np
    import jax

    from repro.core import EnvelopeParams, QuerySpec, UlisseEngine
    from repro.launch import configure_compile_cache
    from repro.serve import ServeConfig, UlisseServer
    from repro.train.data import series_batches

    configure_compile_cache()

    n_dev = jax.device_count()
    ns = (args.series // n_dev) * n_dev
    data = series_batches(ns, args.series_len, seed=11)
    p = EnvelopeParams(lmin=args.series_len // 2,
                       lmax=args.series_len, gamma=16, seg_len=16,
                       znorm=True)
    if n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("data",))
        engine = UlisseEngine.distributed(mesh, p, data,
                                          max_batch=args.batch)
        backend = f"sharded scan over {n_dev} devices"
    else:
        from repro.core import Collection
        engine = UlisseEngine.from_collection(
            Collection.from_array(data), p, max_batch=args.batch)
        backend = "local one-sync pipeline"
    spec = QuerySpec(k=args.k, sync_every=args.sync_every)
    lengths = sorted({p.lmin, (p.lmin + p.lmax) // 2 // 16 * 16, p.lmax})
    print(f"serving {ns} series x {args.series_len} ({backend}); "
          f"query lengths {lengths}")

    rng = np.random.default_rng(1)

    def make_query(i):
        qlen = lengths[i % len(lengths)]
        s = rng.integers(0, ns)
        o = rng.integers(0, args.series_len - qlen + 1)
        return (data[s, o:o + qlen]
                + rng.normal(size=qlen).astype(np.float32) * .02)

    queries = [make_query(i) for i in range(args.queries)]

    # baseline: the old serial one-request-at-a-time loop
    engine.warmup(lengths, [1], spec)
    t0 = time.perf_counter()
    for q in queries:
        engine.search(q, spec)
    dt_serial = time.perf_counter() - t0
    print(f"serial baseline: {len(queries) / dt_serial:.1f} qps "
          f"({dt_serial / len(queries) * 1e3:.1f} ms/query)")

    # the serving loop: closed-loop clients against the dynamic batcher
    server = UlisseServer(engine, spec,
                          ServeConfig(window_ms=args.window_ms,
                                      max_batch=args.batch))
    server.warmup(lengths)
    server.metrics.reset()
    results = [None] * len(queries)

    def client(cid):
        for i in range(cid, len(queries), args.clients):
            results[i] = server.search(queries[i], timeout=300)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    server.close()

    m = server.metrics.snapshot()
    print(f"served {m['total']['completed']} queries from "
          f"{args.clients} clients: {len(queries) / dt:.1f} qps "
          f"({dt_serial / dt:.2f}x serial)")
    for bucket, bm in m["buckets"].items():
        print(f"  bucket {bucket}: qps={bm['qps']} "
              f"dispatches={bm['dispatches']} "
              f"mean_fill={bm['mean_fill']} fill={bm['fill_hist']} "
              f"wait_p50={bm['queue_wait_ms']['p50']}ms "
              f"latency p50/p95/p99="
              f"{bm['latency_ms']['p50']}/{bm['latency_ms']['p95']}/"
              f"{bm['latency_ms']['p99']}ms")
    first = results[0]
    print(f"sample answer: nn=({first.series[0]},{first.offsets[0]}) "
          f"d={first.dists[0]:.4f} "
          f"pruning={first.stats.pruning_power:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
