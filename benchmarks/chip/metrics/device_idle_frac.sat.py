"""1 - union of device operation intervals over the traced window."""

from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
