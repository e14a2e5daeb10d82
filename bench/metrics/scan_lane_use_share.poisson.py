"""scan core (`core/executor.py`): the share of the batched exact scan's
lane-steps that did useful work, in percent.  The lanes of a batch run
in lockstep until the last one stops; a padding lane repeats the last
query.  Read from the counters on `query.exact_device`: the chunks the
real rows visited over loop trips (`steps`) times lanes (`lanes`)."""


def read(run):
    rows = [s["attrs"] for s in run.spans
            if s["name"] == "query.exact_device" and "steps" in s["attrs"]]
    slots = sum(a["steps"] * a["lanes"] for a in rows)
    if not slots:
        return None
    return sum(a["chunks"] for a in rows) / slots * 100.0
