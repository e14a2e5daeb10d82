"""compile: milliseconds of the traced window spent compiling, from the
program's `jax.compile` spans (jaxpr tracing, lowering and backend
compile of any function, recorded on the compiling thread): the union
of their intervals inside the window, so a trace nested in another
counts once.  0 when nothing compiled; nothing where the program
records no span ids, and so no compiles either."""


def read(run):
    marks = [s for s in run.spans if s["name"] == "bench.trace_window"]
    if not marks or not any(s.get("id") for s in run.spans):
        return None
    lo = marks[0]["t0"]
    hi = lo + marks[0]["dur"]
    ivs = sorted((max(s["t0"], lo), min(s["t0"] + s["dur"], hi))
                 for s in run.spans if s["name"] == "jax.compile")
    total, end = 0.0, lo
    for a, b in ivs:
        a = max(a, end)
        if b > a:
            total += b - a
            end = b
    return total * 1e3
