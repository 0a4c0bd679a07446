"""Ragged projection kernel: least time from shapes over its trace time, in %."""

from chipbench import readers


def read(ctx):
    return readers.ragged_proj_roofline(ctx)
