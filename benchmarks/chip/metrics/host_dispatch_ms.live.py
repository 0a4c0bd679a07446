"""Mean host time in step(block=False) per tick, open loop."""

from chipbench import readers


def read(ctx):
    return readers.host_dispatch_ms(ctx) if ctx.loop == "open" else None
