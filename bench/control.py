#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place, computed one precision below the configuration's
(bfloat16 for float32), read by the same numbers that decide `correct`.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed it makes the cell's collection and queries as a run with
that seed does, answers the run's sample of them with the bfloat16
reference, and prints each number compared beside the cell's
limit.  The control has to come out not correct: its smallest readings
are the upper readings that the limits in `bench/configs/` were set
below.  The benchmark's own runs never run this.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
from bench.run import CACHE_DIR, ROOT, load_spec, log  # noqa: E402


def control_answers(config, data, queries, dtype):
    from bench import reference
    out = []
    for q in queries:
        d, s, o = reference.knn(data, q.values, config["k"],
                                config["measure"], config.get("r", 0),
                                dtype=dtype)
        out.append((q, types.SimpleNamespace(dists=d, series=s,
                                             offsets=o)))
    return out


def sample_queries(config, traffic, seed, seconds):
    """The collection a run with this seed serves, and the sample of its
    queries the run checks when every request is answered (as
    `run.pick_sample` draws it)."""
    from bench import gen
    data, queries, _ = gen.make_work(config, traffic, seed, seconds)
    n = min(config["check"]["sample"], len(queries))
    pick = gen.host_rng(seed, 4).choice(len(queries), size=n,
                                        replace=False)
    return data, [queries[i] for i in sorted(pick)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length whose query mix to draw "
                         "(default: run_seconds)")
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_spec(args.workload)
    seconds = args.seconds or bench["run_seconds"]

    import jax
    import jax.numpy as jnp
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    if jax.devices()[0].platform != "tpu":
        log("control: no TPU")
        return 3
    from bench import check, reference

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        data, sample = sample_queries(config, traffic, seed, seconds)
        answers = control_answers(config, data, sample, jnp.bfloat16)
        values = check.readings(config, data, answers, reference)
        checks = check.judge(values, config, 0)
        rows.append({"seed": seed, "sample": len(sample),
                     "correct": check.is_correct(checks),
                     "readings": values})
        log(json.dumps(rows[-1]))
        del data
    print(json.dumps({"workload": cell["name"], "control": "bfloat16",
                      "runs": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
