"""planner (`core/planner.py`): milliseconds per traced query of the
program's two planning stages, from its own device-stage spans:
`device.approx_plan` (block bounds and leaf pack of the approximate
pass) and `device.plan` (envelope bounds and LB argsort pack of the
exact scan).  A stage span runs from its launch, or the end of the
stage before it if that is later, to the moment its output is ready on
the chip."""

STAGES = ("device.approx_plan", "device.plan")


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
