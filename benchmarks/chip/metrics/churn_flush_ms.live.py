"""Mean host time of one coalesced churn flush (``engine.churn_flush``)
in the untraced window."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.mean_span_ms(ctx, "engine.churn_flush")
