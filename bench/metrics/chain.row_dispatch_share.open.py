"""Share of the lowered chain's dispatches that ran one row each (the
router's per-row path: singletons and probes) over the whole window."""
from bench import readers


def read(ctx):
    c = ctx["window_counters"]
    return readers.share(c["row_dispatches"],
                         c["row_dispatches"] + c["batch_dispatches"])
