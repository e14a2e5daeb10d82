"""fused verify kernels (`kernels/fused_verify.py`): the least time the
chip needs for the run's verification work (`bench/work.py`: bytes of
the verified rows' windows over HBM bandwidth, or FLOPs over peak,
whichever is larger; for these shapes it is the memory bound) over the
device time of the scan program, in percent."""
from bench import layers

# `executor._device_scan_program` jits a functools.partial of
# `_device_scan_core`, which XLA names `jit__unknown` (seen by hand in
# a chip trace): the approximate pass's leaf scan and the exact scan.
# No other program of a k-NN cell is jitted from a partial.
MODULES = ("jit__unknown",)


def read(run):
    return layers.roofline_share(run, MODULES)
