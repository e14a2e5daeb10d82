"""Compile the served path's Pallas kernels for a TPU v5e, without one.

The TPU compiler is installed even where no chip is attached: a
described `v5e:2x2` topology lets `lower(...).compile()` run Mosaic and
XLA:TPU on the real shapes, which finds what interpret mode cannot —
block shapes the (8, 128) tiling refuses, vector loads from SMEM,
kernels whose VMEM or HBM footprint does not fit.  Nothing runs, so
these tests say nothing about results or times.

Shapes are the one-chip deployment's: 2^20 series of 256 points,
512-row chunks, gamma=16 (g=17 offsets per envelope).  Every call
passes interpret=False explicitly: `default_interpret()` sees the CPU
here.  The topology is described inside a fixture (never at import),
so a worker that cannot load the TPU library skips instead of breaking
collection.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.core import executor
from repro.kernels.fused_verify import (fused_gather_ed,
                                        fused_gather_lb_keogh)

S, N, ROWS, G, K = 1 << 20, 256, 512, 17, 5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler log files
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        # a compile for a described chip can be written to the
        # persistent cache but never read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _collection(sharding):
    return ([_sds(sharding, (S, N))]
            + [_sds(sharding, (S, N + 1))] * 4
            + [_sds(sharding, (S,))])


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("qlen", [128, 256])
@pytest.mark.parametrize("kernel", ["ed", "lb_keogh"])
def test_fused_kernel_compiles_for_v5e(one_chip, kernel, qlen, b):
    plan = [_sds(one_chip, (b * ROWS,), jnp.int32)] * 2
    query = _sds(one_chip, (b, qlen))
    if kernel == "ed":
        lowered = fused_gather_ed.lower(
            *_collection(one_chip), *plan, query, g=G, rows=ROWS,
            znorm=True, interpret=False)
    else:
        lowered = fused_gather_lb_keogh.lower(
            *_collection(one_chip), *plan, query, query, g=G, rows=ROWS,
            znorm=True, interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_exact_scan_program_compiles_for_v5e(one_chip):
    """The whole jitted exact-scan program (the while_loop over chunk
    steps) at full size: the kernel is inside, and it fits the chip."""
    b, qlen, n_pad = 8, 128, 1 << 23      # 8 envelopes per series
    fn = executor._device_scan_program(K, G, ROWS, True, "ed", 0, 128,
                                       False)
    args = (_collection(one_chip)
            + [_sds(one_chip, (b, n_pad), jnp.int32)] * 3
            + [_sds(one_chip, (b, n_pad))]
            + [_sds(one_chip, (b, qlen))] * 3
            + [_sds(one_chip, (b, K)), _sds(one_chip, (b, K), jnp.int32),
               _sds(one_chip, (b, K), jnp.int32)])
    # the compiler itself refuses a program that does not fit the HBM
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
