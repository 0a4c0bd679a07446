"""w8a8 embed kernel: least time from shapes over its trace time, in %."""

from chipbench import readers


def read(ctx):
    return readers.w8a8_embed_roofline(ctx)
