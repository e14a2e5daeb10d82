#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from `BENCHMARK.json` at
the checkout's root: its configuration in `bench/configs/<config>.json`,
its traffic mix in `bench/traffic/<traffic>.json`, and each per-layer
metric's reader in `bench/metrics/<metric>.py`.

A run builds the collection (random walks, on the chip) and a
`repro.core.UlisseEngine` over it, puts a `repro.serve.UlisseServer`
in front, warms every (length, batch) program the mix uses, and then
drives `UlisseServer.submit` for `--seconds`: open loop (Poisson, timed
from each request's due time) or closed loop (clients that send their
next query when the last is answered).  After the window it waits for
the window's requests, reads the chip's peak memory, frees the engine,
and checks a seeded sample of the window's answers against the plain
reference in `bench/reference.py`.

With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from `repro.obs` spans, the
answers' `SearchStats` and a profiler trace of the window.  The last
line of stdout is the JSON result; the last lines of stderr are the
numbers compared, each beside its limit.  Without a TPU, or with fewer
chips than the cell asks for, it exits 3 and prints no result.

A configuration with `"shards": s` (1 when absent) row-shards the
collection over a ("data",) mesh of the cell's first s chips and serves
it from `UlisseEngine.distributed`; a cell whose `chips` is not its
configuration's `shards` exits 2 before any work.  Memory is read over
every chip of the cell: `hbm_bytes_per_data_byte` sums their growth,
`memory_peak_bytes` is the fullest chip's peak.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import statistics
import sys
import time
from pathlib import Path


T_START = time.perf_counter()

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

# the spans repro.obs opens, by which the trace's idle gaps are named
HOST_SPANS = ("serve.dispatch", "query.exact_device", "prepare",
              "approx_pass", "pack", "device_scan", "merge")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spec(workload: str, root: Path = ROOT):
    """(benchmark, cell, config, traffic) for a workload name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"])
                        .read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def metrics_for(bench: dict, cell: dict, kind: str) -> list:
    """The cell's end-to-end or per-layer metric entries."""
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- load -------------------------------------------------------------------

class Request:
    __slots__ = ("query", "due", "sent", "done", "ticket", "result",
                 "error")

    def __init__(self, query, due):
        self.query, self.due = query, due
        self.sent = self.done = None
        self.ticket = self.result = self.error = None


def _poll(outstanding, now):
    """Move finished requests out of `outstanding`; returns them."""
    done = [r for r in outstanding if r.ticket.done()]
    for r in done:
        r.done = now
        try:
            r.result = r.ticket.result(0)
        except Exception as e:   # noqa: BLE001 — a failed request is
            r.error = repr(e)    # counted, not raised
        outstanding.remove(r)
    return done


def _submit(server, r, now, refused):
    from repro.serve import AdmissionError
    r.sent = now
    try:
        r.ticket = server.submit(r.query.values)
    except AdmissionError as e:
        r.error = repr(e)
        refused.append(r)
        return False
    return True


def drive_open(server, queries, due_times, seconds, drain_s):
    """Open loop: request i is sent at its due time whatever the server
    does.  Returns (requests, t0)."""
    reqs, refused, outstanding = [], [], []
    t0 = time.perf_counter()
    nxt = 0
    end = t0 + seconds + drain_s
    while True:
        now = time.perf_counter()
        _poll(outstanding, now)
        if nxt < len(queries) and now >= t0 + due_times[nxt]:
            r = Request(queries[nxt], t0 + due_times[nxt])
            reqs.append(r)
            if _submit(server, r, now, refused):
                outstanding.append(r)
            nxt += 1
            continue
        if (nxt >= len(queries) and not outstanding) or now > end:
            break
        wait = 1e-3
        if nxt < len(queries):
            wait = min(wait, max(t0 + due_times[nxt] - now, 0.0))
        time.sleep(wait)
    return reqs, t0


def drive_closed(server, queries, clients, seconds, drain_s):
    """Closed loop: `clients` callers, each sending its next query (in
    pool order, cycling) the moment its last answer arrives, until the
    window closes.  Returns (requests, t0)."""
    reqs, refused, outstanding = [], [], []
    t0 = time.perf_counter()
    close = t0 + seconds
    nxt = 0

    def send(now):
        nonlocal nxt
        r = Request(queries[nxt % len(queries)], now)
        reqs.append(r)
        nxt += 1
        if _submit(server, r, now, refused):
            outstanding.append(r)

    for _ in range(clients):
        send(time.perf_counter())
    while True:
        now = time.perf_counter()
        for _ in _poll(outstanding, now):
            if now < close:
                send(now)
        if now >= close and not outstanding:
            break
        if now > close + drain_s:
            break
        time.sleep(1e-3)
    return reqs, t0


class Profile:
    """The profiler session of a `--trace 1` run.  Its window is marked
    in the trace (`bench.window`) and, on the `repro.obs` tracer's
    clock, by a `bench.trace_window` interval."""

    def __init__(self, tracer):
        import jax
        self.jax, self.tracer = jax, tracer
        self.opts = jax.profiler.ProfileOptions()
        self.opts.python_tracer_level = 0
        self.t0 = self.mark = None

    def start(self):
        TRACE_DIR.mkdir(exist_ok=True)
        for old in TRACE_DIR.rglob("*.xplane.pb"):
            old.unlink()
        self.jax.profiler.start_trace(str(TRACE_DIR),
                                      profiler_options=self.opts)
        from bench import trace as trace_mod
        self.mark = self.jax.profiler.TraceAnnotation(trace_mod.WINDOW_MARK)
        self.mark.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        t1 = time.perf_counter()
        self.mark.__exit__(None, None, None)
        self.tracer.record_interval("bench.trace_window", self.t0, t1)
        self.jax.profiler.stop_trace()


# -- correctness --------------------------------------------------------------

def pick_sample(reqs, t_close, n, seed):
    """A seeded sample of distinct queries answered among the window's
    requests."""
    from bench import gen
    seen, pool = set(), []
    for r in reqs:
        if (r.result is not None and r.sent is not None
                and r.sent <= t_close and r.query.values.tobytes()
                not in seen):
            seen.add(r.query.values.tobytes())
            pool.append(r)
    rng = gen.host_rng(seed, 4)
    pick = rng.choice(len(pool), size=min(n, len(pool)), replace=False)
    return [pool[i] for i in sorted(pick)]


# -- the run ----------------------------------------------------------------

def shards_of(config) -> int:
    return int(config.get("shards", 1))


def cell_mesh(config):
    """The ("data",) mesh of the first `shards` chips that the
    configuration shards its collection over; None for one chip."""
    shards = shards_of(config)
    if shards == 1:
        return None
    import jax
    return jax.make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])


def build_engine(config, data):
    from repro.core import Collection, EnvelopeParams, UlisseEngine
    p = EnvelopeParams(lmin=config["lmin"], lmax=config["lmax"],
                       gamma=config["gamma"], seg_len=config["seg_len"],
                       card=config["card"], znorm=config["znorm"])
    mesh = cell_mesh(config)
    if mesh is None:
        return UlisseEngine.from_collection(
            Collection.from_array(data), p,
            max_batch=config["engine_max_batch"])
    return UlisseEngine.distributed(mesh, p, data,
                                    max_batch=config["engine_max_batch"])


def chip_bytes(devices, key="bytes_in_use") -> list:
    """`key` of each chip's memory stats (0 where a chip keeps none)."""
    return [(d.memory_stats() or {}).get(key, 0) for d in devices]


def query_spec(config):
    from repro.core import QuerySpec
    return QuerySpec(k=config["k"], measure=config["measure"],
                     r=config.get("r", 0), mode=config["mode"])


def run_cell(bench, cell, config, traffic, seed, seconds, trace, devices):
    """Everything after the look for chips (`devices`: the cell's
    chips): returns the result dict."""
    import jax
    from bench import check, gen, reference
    from repro import obs
    from repro.serve import ServeConfig, UlisseServer

    device = devices[0]
    s_count, n = config["num_series"], config["series_len"]
    mem0 = chip_bytes(devices)
    data, queries, due = gen.make_work(config, traffic, seed, seconds,
                                       cell_mesh(config))
    data.block_until_ready()
    engine = build_engine(config, data)
    jax.block_until_ready(list(engine.device_arrays().values()))
    del data
    gc.collect()
    mem1 = chip_bytes(devices)
    held = sum(mem1) - sum(mem0)
    hbm_ratio = held / (s_count * n * 4)
    log(f"collection + index: {held} bytes on {len(devices)} chip(s) "
        f"for {s_count * n * 4} bytes of series")
    for d, b0, b1 in zip(devices, mem0, mem1):
        log(f"  chip {d.id}: {b1 - b0} bytes resident")

    srv = traffic["server"]
    server = UlisseServer(engine, query_spec(config),
                          ServeConfig(window_ms=srv["window_ms"],
                                      max_batch=srv["max_batch"],
                                      max_pending=srv["max_pending"]))
    shapes = server.warmup(sorted(set(traffic["lengths"])))
    tracer = obs.get_tracer()
    prof = None
    if trace:
        tracer.configure(enabled=True, sample_every=1, capacity=1 << 20,
                         jax_annotations=True)
        tracer.drain()
        prof = Profile(tracer)
        prof.start()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s ({shapes} warm shapes)")

    drain_s = traffic["drain_s"]
    if traffic["kind"] == "poisson":
        reqs, t0 = drive_open(server, queries, due, seconds, drain_s)
    else:
        reqs, t0 = drive_closed(server, queries, traffic["clients"],
                                seconds, drain_s)
    t_close = t0 + seconds
    if prof is not None:
        prof.stop()
    server.close(drain=False)
    peaks = chip_bytes(devices, "peak_bytes_in_use")
    for d, b in zip(devices, peaks):
        log(f"  chip {d.id}: peak_bytes_in_use {b}")
    peak = max(peaks)
    spans = [s.as_dict() for s in tracer.drain()] if trace else []
    tracer.configure(enabled=False)

    attempted = len(reqs)
    answered = [r for r in reqs if r.result is not None]
    refused = [r for r in reqs if r.ticket is None]
    lost = [r for r in reqs if r.ticket is not None and r.result is None]
    late = [r.sent - r.due for r in reqs if r.sent is not None]
    lat = [(r.done - r.due) * 1e3 for r in reqs
           if r.result is not None and r.due < t_close]
    in_window = [r for r in answered if r.done <= t_close]
    log(f"requests: {len(reqs)} sent, {len(answered)} answered, "
        f"{len(refused)} refused, {len(lost)} failed or unanswered; "
        f"{len(lat)} latency samples; generator lateness max "
        f"{max(late, default=0) * 1e3:.3f} ms, mean "
        f"{statistics.fmean(late) * 1e3 if late else 0:.3f} ms")

    # the program's state goes before the reference runs
    sample = pick_sample(reqs, t_close, config["check"]["sample"], seed)
    sample = [(r.query, r.result) for r in sample]
    stats = [(len(r.query.values), r.result.stats.as_dict())
             for r in answered]
    del server, engine, answered, reqs
    gc.collect()
    t_ref = time.perf_counter()
    data = gen.make_collection(traffic["work_seed"], s_count, n)
    checks = check.judge(check.readings(config, data, sample, reference),
                         config, len(lost))
    del data
    log(f"reference over {len(sample)} sampled answers: "
        f"{time.perf_counter() - t_ref:.3f} s")
    correct = check.is_correct(checks)

    n_failed = len(refused) + len(lost)
    e2e = end_to_end(traffic, lat, in_window, seconds, hbm_ratio, setup_s,
                     drain_s, lost, refused)
    per_layer, breakdown, busy = {}, None, None
    if trace:
        per_layer, breakdown, busy = read_per_layer(
            bench, cell, config, traffic, spans, stats, device)
    metrics = {}
    for m in metrics_for(bench, cell, "per_layer" if trace
                         else "end_to_end"):
        v = per_layer.get(m["name"]) if trace else e2e.get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    if busy is not None:
        dev["busy_s"], dev["window_s"] = busy
    result = {"correct": correct, "attempted": attempted,
              "failed": n_failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def percentile(values, q):
    """The q-th percentile (inclusive method) of a non-empty sample."""
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100,
                                      method="inclusive")[q - 1])


def end_to_end(traffic, lat, in_window, seconds, hbm_ratio, setup_s,
               drain_s, lost, refused):
    """The end-to-end metrics of a run.  A request that failed, was
    refused or was never answered counts with the latency of the whole
    wait the run allowed it."""
    out = {"setup_s": setup_s, "hbm_bytes_per_data_byte": hbm_ratio}
    if traffic["kind"] == "poisson":
        worst = (seconds + drain_s) * 1e3
        lat = list(lat) + [worst] * (len(lost) + len(refused))
        if lat:
            out["query_p50_ms"] = percentile(lat, 50)
            out["query_p90_ms"] = percentile(lat, 90)
    else:
        out["queries_per_s"] = len(in_window) / seconds
    return out


class Run:
    """What a per-layer metric's reader is given.

    spans:  the `repro.obs` spans of the window (dicts: name, t0, dur,
            tid, depth, attrs), on the tracer's clock
    stats:  [(query length, SearchStats dict)] of every answered request
    trace:  the reduced profiler trace (`bench.trace.Trace`)
    modules: device seconds per XLA module name in the traced window
    traced_queries: requests dispatched inside the traced window
    busy_s, window_s: the chip's busy time and the traced window
    config, traffic: the cell's configuration and traffic dicts
    peaks:  the chip's row of `bench/peaks.json`
    """

    def __init__(self, **kw):
        self.__dict__.update(kw)


def traced_queries(spans) -> int:
    """Requests of the dispatches that ran inside the traced window (by
    the midpoint of their `serve.dispatch` span)."""
    marks = [s for s in spans if s["name"] == "bench.trace_window"]
    if not marks:
        return 0
    lo, hi = marks[0]["t0"], marks[0]["t0"] + marks[0]["dur"]
    return sum(s["attrs"].get("fill", 0) for s in spans
               if s["name"] == "serve.dispatch"
               and lo <= s["t0"] + s["dur"] / 2 <= hi)


def read_per_layer(bench, cell, config, traffic, spans, stats, device):
    """(per-layer values, breakdown, (busy_s, window_s))."""
    from bench import trace as trace_mod, work
    paths = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    tr = trace_mod.load(str(paths[-1]))
    for p in paths:
        p.unlink()
    planes = sorted(tr.devices)[:cell["chips"]]
    busy_s, window_s = trace_mod.busy_window(tr, planes)
    run = Run(spans=spans, stats=stats, trace=tr,
              modules=trace_mod.module_seconds(tr, planes),
              busy_s=busy_s, window_s=window_s, config=config,
              traffic=traffic, peaks=work.peaks(device.device_kind),
              traced_queries=traced_queries(spans))
    out = {}
    for m in metrics_for(bench, cell, "per_layer"):
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = float(v)
    breakdown = {"device_ops": trace_mod.top_ops(tr, planes[0]),
                 "idle_gaps": trace_mod.idle_by_host(tr, planes[0],
                                                     HOST_SPANS)}
    return out, breakdown, (busy_s, window_s)


def print_checks(checks) -> None:
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_spec(args.workload)
    if cell["chips"] != shards_of(config):
        log(f"bench: cell {cell['name']} asks for {cell['chips']} chip(s) "
            f"but its config {cell['config']} shards over "
            f"{shards_of(config)}")
        return 2

    # one fixed cache inside the checkout, whatever the environment says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
            f"chip(s); JAX found {len(devices)} {devices[0].platform} "
            f"device(s)")
        return 3
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    result = run_cell(bench, cell, config, traffic, args.seed,
                      args.seconds, bool(args.trace),
                      devices[:cell["chips"]])
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
