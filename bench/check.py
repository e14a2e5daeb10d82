"""The comparison that decides `correct`.

For a seeded sample of the answers the timed window served, the plain
reference (`bench/reference.py`) recomputes each query's k nearest
windows over the whole collection, and the served distances of the
served windows.  The numbers compared:

* `dist_gap`: the largest gap, over the sample and the k ranks, between
  the served distances (sorted) and the reference's k best.  A missed
  neighbour, a wrong order or an approximate answer shows here.
* `claim_gap`: the largest gap between a served distance and the
  reference's distance of the very window served with it.  An answer
  routed to the wrong request, or altered where it is produced, shows
  here.
* `short_answers`: sampled answers with fewer than k distinct windows
  (limit 0).
* `unanswered`: admitted requests that failed or never came back
  (limit 0).

The limits of the first two are the configuration's (`check.limits`),
set from the readings of sound runs and of the control
(`bench/control.py`), as PERF.md records.
"""
from __future__ import annotations

import numpy as np


def readings(config, data, sample, reference):
    """{name: value} over `sample`, a list of (query, answer) where the
    answer has `dists`, `series` and `offsets`."""
    k, measure, r = config["k"], config["measure"], config.get("r", 0)
    dist_gap = claim_gap = 0.0
    short = 0
    for q, res in sample:
        ref_d, _, _ = reference.knn(data, q.values, k, measure, r)
        got = np.asarray(res.dists, np.float64)
        wins = set(zip(np.asarray(res.series).tolist(),
                       np.asarray(res.offsets).tolist()))
        if len(got) != k or len(wins) != k:
            short += 1
            continue
        dist_gap = max(dist_gap, float(np.max(np.abs(np.sort(got)
                                                     - ref_d))))
        claimed = reference.window_dists(data, q.values, res.series,
                                         res.offsets, measure, r)
        claim_gap = max(claim_gap, float(np.max(np.abs(got - claimed))))
    return {"dist_gap": dist_gap, "claim_gap": claim_gap,
            "short_answers": short}


def judge(values, config, unanswered):
    """Each number beside its limit: {name: {"value", "limit"}}."""
    limits = dict(config["check"]["limits"], short_answers=0,
                  unanswered=0)
    values = dict(values, unanswered=unanswered)
    return {name: {"value": values[name], "limit": limits[name]}
            for name in ("dist_gap", "claim_gap", "short_answers",
                         "unanswered")}


def is_correct(checks) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
