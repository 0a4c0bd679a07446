"""Process start to the first timed tick."""

from chipbench import readers


def read(ctx):
    return ctx.setup_s
