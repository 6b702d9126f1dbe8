"""The whole step's share of the chip's peak bf16 rate: operations the
served requests needed over the device time of the chain's programs."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx)
