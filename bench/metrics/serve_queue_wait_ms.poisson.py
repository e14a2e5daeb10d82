"""serve (`serve/server.py`): mean wait of a request in its length
bucket before dispatch, from the `serve.queue_wait` spans."""
from bench import layers


def read(run):
    return layers.mean_span_ms(run, "serve.queue_wait")
