#!/usr/bin/env python3
"""Serve ULISSE queries on a TPU and check every answer against brute force.

    python chip_smoke.py              # one chip: 2^20 random-walk series x 256
    python chip_smoke.py --chips 4    # the sharded backend over four chips,
                                      # each holding what one chip holds above

The script drives the path a user calls: `UlisseEngine.from_collection`
(or `UlisseEngine.distributed` over a ("data",) mesh) behind one
`repro.serve.UlisseServer` per `QuerySpec`.  It serves exact ED k-NN
(k=5, four queries coalesced into each dispatch), one DTW k-NN
(r = 10% of |Q|) and one eps-range query at lengths 128, 192 and 256,
with the launcher's index shape (lmin=128, lmax=256, gamma=16,
seg_len=16, Z-normalized).  Every answer is compared with the
`core.search` brute force run on the chip (on four chips: shard by
shard, each on its own chip, merged on the host): the (series, offset)
sets must be equal and the distances agree to 5e-3.

Without a TPU, or when any phase fails, it exits non-zero and prints no
result line.  Otherwise the last line of stdout is the JSON result.
Times printed on the way are informative, not measurements.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

LENGTHS = (128, 192, 256)
K = 5
ED_BATCH = 4          # ED queries per length: one B=4 dispatch each
ATOL = 5e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def make_queries(data, lengths, per_length, rng):
    """Windows of the collection plus 2% noise (the launcher's mix)."""
    out = []
    for qlen in lengths:
        for _ in range(per_length):
            s = int(rng.integers(0, data.shape[0]))
            o = int(rng.integers(0, data.shape[1] - qlen + 1))
            noise = rng.normal(size=qlen).astype(data.dtype) * 0.02
            out.append(data[s, o:o + qlen] + noise)
    return out


def serve(engine, spec, queries, max_batch):
    """One server per spec: warm its programs, submit every query at
    once, and return (answers, warmup seconds, metrics snapshot).  A
    failed ticket re-raises its dispatch error here."""
    from repro.serve import ServeConfig, UlisseServer
    server = UlisseServer(engine, spec,
                          ServeConfig(window_ms=50.0, max_batch=max_batch))
    try:
        t0 = time.perf_counter()
        server.warmup(sorted({len(q) for q in queries}), [max_batch])
        warm_s = time.perf_counter() - t0
        server.metrics.reset()
        tickets = [server.submit(q) for q in queries]
        answers = [t.result(timeout=900) for t in tickets]
        snap = server.metrics.snapshot()
    finally:
        server.close()
    return answers, warm_s, snap


def check(name, got, ref):
    """Equal (series, offset) sets and distances within ATOL."""
    import numpy as np
    got_set = set(zip(got.series.tolist(), got.offsets.tolist()))
    ref_set = set(zip(ref.series.tolist(), ref.offsets.tolist()))
    if got_set != ref_set or len(got.dists) != len(ref.dists):
        raise AssertionError(
            f"{name}: answer set differs from brute force: only served "
            f"{sorted(got_set - ref_set)[:5]}, only reference "
            f"{sorted(ref_set - got_set)[:5]}")
    err = float(np.max(np.abs(np.sort(got.dists) - np.sort(ref.dists)),
                       initial=0.0))
    if not err <= ATOL:
        raise AssertionError(f"{name}: max |d - ref| = {err} > {ATOL}")
    log(f"  {name}: {len(ref.dists)} matches equal to brute force, "
        f"max |d - ref| = {err:.3g}")


def run(chips: int, series: int, seed: int) -> dict:
    import jax
    import numpy as np

    from repro.core import (Collection, EnvelopeParams, QuerySpec,
                            UlisseEngine)
    from repro.core.search import brute_force_d2, knn_from_d2, range_from_d2
    from repro.launch import configure_compile_cache
    from repro.train.data import series_batches

    cache = configure_compile_cache()
    devices = jax.devices()
    if len(devices) < chips:
        raise RuntimeError(f"--chips {chips} but JAX sees {len(devices)}")
    log(f"device_kind: {devices[0].device_kind} ({devices[0].platform}, "
        f"{len(devices)} visible, {chips} used); compile cache {cache}")

    total = series * chips
    p = EnvelopeParams(lmin=128, lmax=256, gamma=16, seg_len=16,
                       znorm=True)
    t0 = time.perf_counter()
    data = series_batches(total, 256, seed=seed)
    log(f"collection: {total} random-walk series x 256 (seed {seed}) "
        f"made in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    if chips == 1:
        engine = UlisseEngine.from_collection(Collection.from_array(data),
                                              p, max_batch=ED_BATCH)
    else:
        mesh = jax.make_mesh((chips,), ("data",),
                             devices=devices[:chips])
        engine = UlisseEngine.distributed(mesh, p, data,
                                          max_batch=ED_BATCH)
    arrays = engine.device_arrays()
    jax.block_until_ready(list(arrays.values()))
    log(f"index built in {time.perf_counter() - t0:.1f}s")

    held = {d: 0 for d in devices[:chips]}
    for name, a in arrays.items():
        shards = a.addressable_shards
        if chips > 1 and len({s.device for s in shards}) != chips:
            raise AssertionError(f"{name} is not spread over {chips} "
                                 f"chips: {[s.device for s in shards]}")
        for s in shards:
            held[s.device] += s.data.nbytes
    for d, nbytes in held.items():
        log(f"collection + index bytes on device {d.id}: {nbytes} "
            f"({nbytes / 2**30:.3f} GiB)")
    if chips > 1 and min(held.values()) == 0:
        raise AssertionError("a chip holds no part of the collection")

    # the brute-force oracle runs where the series live: one table per
    # shard on its own chip, stacked on the host in series order
    if chips == 1:
        blocks = [engine.index.collection.data]
    else:
        sharded = arrays["data"]
        blocks = [s.data for s in sorted(sharded.addressable_shards,
                                         key=lambda s: s.index[0].start)]

    def reference_d2(q, measure, r):
        # one distance table per block, each left on its own chip
        return [brute_force_d2(b, q, True, measure, r) for b in blocks]

    rng = np.random.default_rng(seed + 1)
    ed_q = make_queries(data, LENGTHS, ED_BATCH, rng)
    dtw_len = LENGTHS[0]
    dtw_r = max(1, round(dtw_len / 10))
    dtw_q = make_queries(data, (dtw_len,), 1, rng)
    rng_q = make_queries(data, (LENGTHS[1],), 1, rng)
    rng_d2 = reference_d2(rng_q[0], "ed", 0)
    eps = float(knn_from_d2(rng_d2, K).dists[-1]) * 1.05

    phases = [("ed-knn", QuerySpec(k=K), ed_q, ED_BATCH),
              ("dtw-knn", QuerySpec(k=K, measure="dtw", r=dtw_r), dtw_q, 1),
              ("eps-range", QuerySpec(eps=eps), rng_q, 1)]
    for name, spec, queries, batch in phases:
        t0 = time.perf_counter()
        answers, warm_s, snap = serve(engine, spec, queries, batch)
        served_s = time.perf_counter() - t0 - warm_s
        fills = {b: m["fill_hist"] for b, m in snap["buckets"].items()}
        log(f"{name}: warmup (compile + first run) {warm_s:.1f}s, "
            f"{len(queries)} queries served in {served_s:.2f}s, "
            f"dispatch fills per length bucket {fills}")
        if name == "ed-knn" and snap["total"]["mean_fill"] <= 1:
            raise AssertionError(f"ED k-NN was not batched: {fills}")
        for i, (q, got) in enumerate(zip(queries, answers)):
            if spec.is_range:
                ref = range_from_d2(rng_d2, eps)
            else:
                ref = knn_from_d2(reference_d2(q, spec.measure, spec.r), K)
            st = got.stats
            check(f"{name} |Q|={len(q)} #{i} (chunks visited "
                  f"{st.chunks_visited}/{st.chunks_planned})", got, ref)

    records = engine.audit_programs([QuerySpec(k=K)], batch=ED_BATCH,
                                    qlen=LENGTHS[0])
    for rec in records:
        if rec["family"] == "prepare":
            continue
        n_calls = rec["lower"]().compile().as_text().count(
            "tpu_custom_call")
        log(f"tpu_custom_call in compiled {rec['name']}: {n_calls}")
        if n_calls == 0:
            raise AssertionError(f"{rec['name']} runs no Pallas kernel")

    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        log(f"device {d.id} peak_bytes_in_use: "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return {"ok": True,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--series", type=int, default=1 << 20,
                    help="series per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform!r})",
              file=sys.stderr)
        return 2
    result = run(args.chips, args.series, args.seed)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
