"""The model step's share of its roofline: the least time of the chain's
dispatches (``costs/``, per stage the larger of the compute and the
memory bound) over the device time of the chain's programs."""
from bench import readers


def read(ctx):
    return readers.roofline(ctx)
