"""Share of the tick intervals (dispatch to results) with no device operation."""

from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
