"""scan core (`core/executor.py`, `device_exact_scan`): milliseconds per
traced query of the program's `device.scan` stage spans, the exact scan
alone (the approximate pass's leaf scan, which compiles to the same XLA
module, is not in them), from launch (or the end of the pack) to the
moment the scan's output is ready on the chip."""

STAGES = ("device.scan",)


def read(run):
    marks = [s for s in run.spans if s["name"] == "bench.trace_window"]
    if not marks or not run.traced_queries:
        return None
    lo = marks[0]["t0"]
    hi = lo + marks[0]["dur"]
    d = [s["dur"] for s in run.spans if s["name"] in STAGES
         and lo <= s["t0"] + s["dur"] / 2 <= hi]
    if not d:
        return None
    return sum(d) / run.traced_queries * 1e3
