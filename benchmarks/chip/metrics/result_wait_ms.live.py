"""Mean host time the engine blocks fetching a tick's logits
(``engine.result``) per untraced tick."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.mean_span_ms(ctx, "engine.result")
