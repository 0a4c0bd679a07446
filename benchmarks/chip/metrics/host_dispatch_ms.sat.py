"""Mean host time in step(block=False) per tick, closed loop."""

from chipbench import readers


def read(ctx):
    return readers.host_dispatch_ms(ctx) if ctx.loop == "closed" else None
