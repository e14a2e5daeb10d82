"""Inputs of a run, all drawn from `--seed`: the collection, the query
mix and the arrival schedule.

* Collection: random walks, the cumulative sum of N(0, 1) steps (the
  ULISSE paper's synthetic data, as `repro.train.data.series_batches`
  makes it), generated on the device in one jitted call.
* Queries: a window of the collection at a uniform (series, offset)
  plus N(0, noise^2) noise per point (the recipe of `chip_smoke.py`).
* The work is fixed: the collection, the queries and the Poisson
  arrival times are drawn from the traffic file's own `work_seed`, and
  the run seed puts the queries in its own order over those arrivals
  (and draws the sample of answers that is checked).  Every seed thus
  offers the same set of queries, lengths and arrivals, in another
  order.  On the chip, runs whose seeds drew their own collection and
  queries differed by 30-60% in their latency percentiles while two
  runs of one seed agreed within a few percent: the seed was changing
  the work, through how many chunks each query's scan visits.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

SEED_BITS = 32


def split_seed(seed: int):
    """Two 32-bit words of a non-negative seed of up to 64 bits."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2^64)")
    return seed & 0xFFFFFFFF, seed >> SEED_BITS


def device_key(seed: int, stream: int):
    import jax
    lo, hi = split_seed(seed)
    key = jax.random.key(lo)
    key = jax.random.fold_in(key, hi)
    return jax.random.fold_in(key, stream)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def make_collection(seed: int, num_series: int, series_len: int,
                    mesh=None):
    """(S, n) float32 random walks from the seed: on the default device,
    or row-sharded over `mesh`'s "data" axis, each chip making only its
    own rows.  Both are the same walks, bit for bit: the random bits are
    partitionable (`jax_threefry_partitionable`) and the sum runs along
    each row."""
    import jax
    import jax.numpy as jnp

    kw = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        kw["out_shardings"] = NamedSharding(mesh, P("data", None))

    def walk(key):
        steps = jax.random.normal(key, (num_series, series_len),
                                  jnp.float32)
        return jnp.cumsum(steps, axis=1)

    return jax.jit(walk, **kw)(device_key(seed, 0))


@dataclasses.dataclass
class Query:
    series: int
    offset: int
    length: int
    values: np.ndarray


def make_queries(data, seed: int, lengths: Sequence[int], noise: float,
                 stream: int = 1) -> List[Query]:
    """One query per entry of `lengths`: a window of `data` at a uniform
    (series, offset) plus N(0, noise^2) per point.  The windows are cut
    on the device and fetched in one transfer."""
    import jax
    import jax.numpy as jnp

    rng = host_rng(seed, stream)
    s_count, n = int(data.shape[0]), int(data.shape[1])
    lengths = [int(x) for x in lengths]
    sids = np.array([rng.integers(0, s_count) for _ in lengths], np.int32)
    offs = np.array([rng.integers(0, n - ln + 1) for ln in lengths],
                    np.int32)
    take = lambda d, s: jnp.take(d, s, axis=0)
    sharding = data.sharding
    if isinstance(sharding, jax.sharding.NamedSharding):
        # rows of a collection sharded over a mesh of explicit axes: the
        # gather has to be told where its result goes (every chip)
        every_chip = jax.sharding.NamedSharding(sharding.mesh,
                                                jax.sharding.PartitionSpec())
        take = lambda d, s: d.at[s].get(out_sharding=every_chip)
    rows = np.asarray(jax.jit(take)(data, jnp.asarray(sids)))
    out = []
    for i, ln in enumerate(lengths):
        vals = rows[i, offs[i]:offs[i] + ln].astype(np.float32)
        vals = vals + (rng.normal(size=ln) * noise).astype(np.float32)
        out.append(Query(int(sids[i]), int(offs[i]), ln, vals))
    return out


def poisson_due_times(rate: float, seconds: float, work_seed: int
                      ) -> np.ndarray:
    """Due times (s, from the window's start) of the round(rate *
    seconds) requests of a window: the exponential gaps of a Poisson
    stream drawn from `work_seed`, scaled to fill the window exactly."""
    count = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(work_seed).exponential(1.0, count)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return starts * (seconds / gaps.sum())


def make_work(config, traffic, seed: int, seconds: float, mesh=None):
    """(collection, queries in arrival order, due times) of one run.

    The collection (row-sharded over `mesh` where one is given), the
    queries (each length of the traffic's list as
    often as the others, to within one) and the due times (None for a
    closed loop, which sends from a pool of `pool` queries) come from
    the traffic's `work_seed`; the run seed orders the queries."""
    work = traffic["work_seed"]
    data = make_collection(work, config["num_series"],
                           config["series_len"], mesh)
    if traffic["kind"] == "poisson":
        due = poisson_due_times(traffic["rate_per_s"], seconds, work)
        count = len(due)
    else:
        due, count = None, traffic["pool"]
    lengths = [int(traffic["lengths"][i % len(traffic["lengths"])])
               for i in range(count)]
    queries = make_queries(data, work, lengths, config["query_noise"])
    order = host_rng(seed, 5).permutation(count)
    return data, [queries[i] for i in order], due
