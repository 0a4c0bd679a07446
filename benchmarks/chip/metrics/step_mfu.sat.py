"""Model operations of the served frames per second over the chip's peaks, in %."""

from chipbench import readers


def read(ctx):
    return readers.step_mfu(ctx)
