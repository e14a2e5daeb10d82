"""engine approximate pass (`core/engine.py`, `_device_approx_stage`):
milliseconds per traced query of the program's `device.approx_scan`
stage spans: the seed pool, the leaf scan and the exactness
certificate, from launch (or the end of the leaf pack) to the moment
the certificate is ready on the chip."""

STAGES = ("device.approx_scan",)


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
