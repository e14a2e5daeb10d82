"""device: the share of the traced window in which the chip was idle
while the server had work, in percent: the device idle share (1 - busy
/ window, from the profiler trace) less the share of the window under
the dispatcher's `serve.idle` spans (no request queued).  The
dispatcher opens `serve.idle` only after its last readback, and it
alone runs engine work, so the chip has nothing to run inside it."""
from bench import layers


def read(run):
    idle = layers.idle_share(run)
    marks = [s for s in run.spans if s["name"] == "bench.trace_window"]
    waits = [s for s in run.spans if s["name"] == "serve.idle"]
    if idle is None or not marks or not waits:
        return None
    lo = marks[0]["t0"]
    hi = lo + marks[0]["dur"]
    t = sum(max(0.0, min(s["t0"] + s["dur"], hi) - max(s["t0"], lo))
            for s in waits)
    return idle - t / (hi - lo) * 100.0
