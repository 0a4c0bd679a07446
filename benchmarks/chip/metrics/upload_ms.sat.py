"""Mean host time of the engine's H2D upload of the staged rows and
their slot ids (``engine.upload``) per untraced tick."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.mean_span_ms(ctx, "engine.upload")
