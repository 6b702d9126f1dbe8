"""Median time the accelerator executor spent running a request's batch
(``exec_s`` of the ``exec@<node>`` spans), in ms."""
from bench import readers


def read(ctx):
    return readers.percentile_ms(
        [s.attrs["exec_s"] for s in readers.spans(ctx, "exec")
         if s.attrs.get("exec_s") is not None], 50)
