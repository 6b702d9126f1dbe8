"""95th percentile of the time a request waits in the batcher before its
batch is dispatched (``queue@<node>`` spans), in ms."""
from bench import readers


def read(ctx):
    return readers.percentile_ms(
        [s.duration_s for s in readers.spans(ctx, "queue")], 95)
