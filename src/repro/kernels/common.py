"""Shared Pallas kernel utilities: lane-aligned scans, padding, tiling.

TPU geometry constants: the VPU operates on (8, 128) f32 tiles; matmuls
want every contraction/output dim in multiples of 128 for full MXU
occupancy.  All kernels here pad to these multiples in their ops.py
wrappers, and reason about VMEM budgets with `pick_block_rows`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128          # VPU lane width / MXU tile edge
SUBLANES = 8         # f32 sublane count
VMEM_BUDGET = 8 * 1024 * 1024   # conservative half of ~16MB VMEM


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_axis(x: jnp.ndarray, axis: int, multiple: int, value=0.0):
    """Pad `axis` of x up to a multiple; returns (padded, original_size)."""
    size = x.shape[axis]
    pad = round_up(size, multiple) - size
    if pad == 0:
        return x, size
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value), size


def pick_block_rows(row_bytes: int, max_rows: int = 1024,
                    budget: int = VMEM_BUDGET, min_rows: int = SUBLANES) -> int:
    """Rows per VMEM block so that block bytes stay under budget."""
    rows = max(budget // max(row_bytes, 1), min_rows)
    rows = min(rows, max_rows)
    # round down to sublane multiple
    return max((rows // SUBLANES) * SUBLANES, min_rows)


def cumsum_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum along the last (lane) axis via log-doubling shifts.

    Mosaic-friendly replacement for jnp.cumsum inside kernels: `steps`
    static shifted adds, exact for float32 accumulation order.
    """
    n = x.shape[-1]
    off = 1
    while off < n:
        shifted = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(off, 0)])[..., :n]
        x = x + shifted
        off *= 2
    return x


def cummin_lanes(x: jnp.ndarray, big: float = 1e30) -> jnp.ndarray:
    """Inclusive cummin along the last axis via log-doubling shifts."""
    n = x.shape[-1]
    off = 1
    while off < n:
        shifted = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(off, 0)],
                          constant_values=big)[..., :n]
        x = jnp.minimum(x, shifted)
        off *= 2
    return x


def default_interpret() -> bool:
    """Pallas interpret mode on the CPU, Mosaic on the TPU.

    Any other backend raises: interpreting there would hide that the
    kernels never ran as device code.
    """
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on 'tpu' and interpreted on "
            f"'cpu'; the default backend is {backend!r}")
    return backend == "cpu"
