"""engine host (`core/engine.py`): host milliseconds of the `merge`
spans (the float64 rescore and result assembly after the batch's one
readback) per answered query.  `merge` opens no child span, so its
duration is its self time."""
from bench import layers


def read(run):
    d = layers.span_durations(run, "merge")
    if not d or not run.stats:
        return None
    return sum(d) / len(run.stats) * 1e3
