"""Delta-attention kernel: least time from shapes over its trace time, in %."""

from chipbench import readers


def read(ctx):
    return readers.delta_attn_roofline(ctx)
