"""Benchmark driver: ``python -m benchmarks.run [--only substr]``.

One function per paper table/figure (bench_paper) + kernel micros
(bench_kernels).  Prints ``name,us_per_call,derived`` CSV; per-program
HLO cost summaries come from ``benchmarks.hlo_cost``.

``--json`` maintains BENCH_kernels.json as the recorded perf artifact:
``results`` holds the latest value per section (merged, so a --only'd
run refreshes its own rows without wiping everyone else's) and
``trajectory`` appends one run record per invocation — git sha,
timestamp, backend/device count, and the sections this run produced —
so the artifact CI uploads preserves the perf history across PRs
instead of only the final overwrite.  Each write also stamps
``calibration.reference_us`` — the wall time of a fixed numpy-only
workload on the machine producing the artifact — which
benchmarks/check_regression.py re-measures at gate time to normalize
the committed qps by runner speed before gating the ``results``
sections.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def _git_sha() -> str:
    import subprocess
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def write_json(path: str) -> None:
    import datetime
    import json
    import os

    import jax

    from benchmarks.common import RESULTS
    from benchmarks.check_regression import reference_workload_us

    doc = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
    merged = doc.get("results", {})
    merged.update(RESULTS)
    # runner-speed stamp: check_regression re-measures this fixed
    # numpy workload at gate time and scales the committed qps by the
    # ratio, so the gate compares work, not machines.  Stamped into
    # BOTH the top-level calibration (gates the ``results`` overwrite)
    # and this run's trajectory record — a trajectory row without its
    # own stamp cannot be speed-normalized against any other row, so
    # the perf history would be machine noise; check_trajectory
    # rejects such records.
    calibration = {"reference_us": round(reference_workload_us(), 1)}
    trajectory = doc.get("trajectory", [])
    trajectory.append({
        "sha": _git_sha(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "reference_us": calibration["reference_us"],
        "results": dict(RESULTS),
    })
    with open(path, "w") as f:
        json.dump({"backend": jax.default_backend(),
                   "calibration": calibration,
                   "results": merged,
                   "trajectory": trajectory}, f, indent=2,
                  sort_keys=True)
    print(f"# wrote {len(RESULTS)} rows to {path} "
          f"({len(merged)} total, {len(trajectory)} trajectory runs)",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="merge this run's rows into the JSON artifact "
                         "and append a trajectory record; '' disables")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    from benchmarks import bench_kernels, bench_paper

    print("name,us_per_call,derived")
    failures = 0
    for fn in bench_paper.ALL + bench_kernels.ALL:
        if args.only and args.only not in fn.__name__:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# {fn.__name__} done in {time.time() - t0:.1f}s",
                  flush=True)
        except Exception:    # noqa: BLE001 — report and continue
            failures += 1
            print(f"# {fn.__name__} FAILED:", flush=True)
            traceback.print_exc()
    if args.json:
        write_json(args.json)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
