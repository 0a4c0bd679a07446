"""95th percentile latency of all frames due in the window, from due to logits on the host."""

from chipbench import readers


def read(ctx):
    return readers.frame_pct_ms(ctx, 95)
