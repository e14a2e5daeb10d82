"""fused verify kernels (`kernels/fused_verify.py`): device milliseconds
per answered query of the scan program that runs them (the approximate
pass's leaf scan and the exact scan compile to the same module name),
found in the trace by XLA module name."""
from bench import layers

# `executor._device_scan_program` jits a functools.partial of
# `_device_scan_core`, which XLA names `jit__unknown` (seen by hand in
# a chip trace): the approximate pass's leaf scan and the exact scan.
# No other program of a k-NN cell is jitted from a partial.
MODULES = ("jit__unknown",)


def read(run):
    return layers.module_ms_per_query(run, MODULES)
