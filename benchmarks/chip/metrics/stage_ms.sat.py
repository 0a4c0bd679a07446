"""Mean host time of the engine's copy of the fed frames into its
staging buffer (``engine.stage``) per untraced tick."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.mean_span_ms(ctx, "engine.stage")
