"""planner (`core/planner.py`): device milliseconds per answered query
of the programs that compute lower bounds and pack the scan plans: the
approximate pass's block bounds and leaf pack, and the exact scan's
envelope bounds and LB argsort pack.  Found in the trace by XLA module
name."""
from bench import layers

MODULES = ("jit_block_lower_bounds_batch", "jit_device_leaf_pack",
           "jit_env_lower_bounds_batch", "jit_device_scan_pack")


def read(run):
    return layers.module_ms_per_query(run, MODULES)
