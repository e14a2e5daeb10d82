"""scan core (`core/executor.py`): chunks the exact scan visited over
the chunks its plans held, summed over the answered queries
(`SearchStats.chunks_visited / chunks_planned`), in percent."""


def read(run):
    planned = sum(st["chunks_planned"] for _, st in run.stats)
    if not planned:
        return None
    return sum(st["chunks_visited"] for _, st in run.stats) / planned * 100
