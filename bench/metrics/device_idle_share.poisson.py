"""device: share of the traced window in which no operation ran on the
chip (1 - busy / window), in percent."""
from bench import layers


def read(run):
    return layers.idle_share(run)
