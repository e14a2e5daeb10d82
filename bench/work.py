"""The least work of the verification tier, counted from a run's own
counts and the query shapes, never from how a kernel fetches its data.

Per query (`SearchStats` of its answer, length l, gamma, band r):

* each verified row (an envelope that passed the lower-bound test and
  whose windows were computed, `envelopes_checked`) reads the
  (l + gamma) * 4 bytes of its gamma + 1 overlapping windows;
* ED: 2 * l * (gamma + 1) FLOPs per row, the query's dot products with
  its gamma + 1 windows;
* DTW: 4 * l * (gamma + 1) FLOPs per row for LB_Keogh (two differences
  against the query envelope, a square and a sum per point), and
  4 * l * (2r + 1) FLOPs per full banded DP (`dtw_full`: a difference,
  a square, a minimum and a sum per cell);
* each query's own values are read once per dispatch: l * 4 bytes,
  twice that for DTW's envelope pair.

The least time is the larger of bytes over the chip's HBM bandwidth
and FLOPs over its peak (the bf16 peak, which no f32 kernel beats), so
a share of it stays at or under 100% for any kernel that does the
work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def verify_work(stats: dict, qlen: int, gamma: int, measure: str,
                r: int) -> Tuple[float, float]:
    """(bytes, FLOPs) of one query's verification."""
    rows = stats["envelopes_checked"]
    g = gamma + 1
    nbytes = rows * (qlen + gamma) * 4 + qlen * 4 * (2 if measure == "dtw"
                                                    else 1)
    if measure == "ed":
        flops = rows * 2 * qlen * g
    else:
        flops = (rows * 4 * qlen * g
                 + stats["dtw_full"] * 4 * qlen * (2 * r + 1))
    return float(nbytes), float(flops)


def least_seconds(nbytes: float, flops: float, peak: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip needs for the work, and its bound."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
