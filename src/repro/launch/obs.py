"""Observability demo: trace a mixed workload end-to-end and dump the
artifacts a dashboard would scrape (DESIGN.md §12).

    python -m repro.launch.obs --devices 2 --out obs_artifacts

Runs kNN + eps-range + approximate queries two ways — directly against
the `UlisseEngine` (stats recorded by hand via
`obs.record_search_stats`) and through the `UlisseServer` dynamic
batcher (spans + stats recorded by the serving tier itself) — with the
process tracer enabled, then writes three artifacts into --out:

    trace.json     Chrome trace_event JSON (Perfetto / chrome://tracing)
    metrics.prom   Prometheus text exposition of the full registry
    metrics.json   the same registry as a JSON snapshot

CI uploads these from the tier-1 job so every commit has a browsable
trace of admission -> queue wait -> dispatch -> device scan -> merge.
"""
import argparse
import json
import os
import sys
import time

from repro.launch.serve import _ensure_device_count


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--series", type=int, default=128)
    ap.add_argument("--series-len", type=int, default=256)
    ap.add_argument("--queries", type=int, default=12)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--out", default="obs_artifacts")
    ap.add_argument("--sample-every", type=int, default=1,
                    help="trace every N-th root span (1 = all)")
    ap.add_argument("--jax-annotations", action="store_true",
                    help="also enter jax.profiler.TraceAnnotation "
                         "scopes so spans align with XLA profiles")
    args = ap.parse_args(argv)

    _ensure_device_count(args.devices)
    import numpy as np
    import jax

    from repro import obs
    from repro.core import EnvelopeParams, QuerySpec, UlisseEngine
    from repro.serve import ServeConfig, UlisseServer
    from repro.train.data import series_batches

    tracer = obs.get_tracer().configure(
        enabled=True, sample_every=args.sample_every,
        jax_annotations=args.jax_annotations)

    n_dev = jax.device_count()
    ns = max(args.series // n_dev, 1) * n_dev
    data = series_batches(ns, args.series_len, seed=7)
    p = EnvelopeParams(lmin=args.series_len // 2, lmax=args.series_len,
                       gamma=16, seg_len=16, znorm=True)
    if n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("data",))
        engine = UlisseEngine.distributed(mesh, p, data, max_batch=4)
        backend = f"distributed ({n_dev} devices)"
    else:
        from repro.core import Collection
        engine = UlisseEngine.from_collection(
            Collection.from_array(data), p, max_batch=4)
        backend = "local device pipeline"
    print(f"tracing {ns} series x {args.series_len} on {backend}; "
          f"artifacts -> {args.out}/")

    rng = np.random.default_rng(3)
    qlen = (p.lmin + p.lmax) // 2 // 16 * 16

    def make_query():
        s = rng.integers(0, ns)
        o = rng.integers(0, args.series_len - qlen + 1)
        return (data[s, o:o + qlen]
                + rng.normal(size=qlen).astype(np.float32) * .02)

    knn = QuerySpec(k=args.k)
    approx = QuerySpec(k=args.k, mode="approx")

    # direct engine queries: the caller owns stats recording
    probe = engine.search(make_query(), knn)       # warm the programs
    eps = float(np.sqrt(probe.dists[-1]) * 1.5) if len(probe.dists) \
        else 1.0
    rng_spec = QuerySpec(eps=eps)
    specs = [knn, approx, rng_spec]
    label = "distributed" if engine.is_distributed else "device"
    t0 = time.perf_counter()
    for i in range(args.queries):
        res = engine.search(make_query(), specs[i % len(specs)])
        obs.record_search_stats(res.stats, backend=label)
    dt = time.perf_counter() - t0
    print(f"engine: {args.queries} mixed queries "
          f"(knn/approx/range eps={eps:.3f}) in {dt:.2f}s")

    # served queries: the dispatcher records spans + stats itself
    server = UlisseServer(engine, knn, ServeConfig(max_batch=4))
    server.warmup([qlen])
    server.metrics.reset()
    for _ in range(args.queries):
        server.search(make_query(), timeout=300)
    m = server.metrics.snapshot()
    server.close()
    print(f"server: {m['total']['completed']} queries, "
          f"mean_fill={m['total']['mean_fill']}")

    os.makedirs(args.out, exist_ok=True)
    trace_path = tracer.export_chrome_trace(
        os.path.join(args.out, "trace.json"))
    n_events = len(json.load(open(trace_path))["traceEvents"])
    prom_path = os.path.join(args.out, "metrics.prom")
    with open(prom_path, "w") as f:
        f.write(server.metrics_text())
    json_path = os.path.join(args.out, "metrics.json")
    with open(json_path, "w") as f:
        f.write(obs.get_registry().json_text())
    print(f"wrote {trace_path} ({n_events} events), {prom_path}, "
          f"{json_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
