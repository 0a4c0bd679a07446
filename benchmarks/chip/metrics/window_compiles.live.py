"""Programs compiled or loaded from the cache inside the window."""

from chipbench import readers


def read(ctx):
    return ctx.window_compiles
