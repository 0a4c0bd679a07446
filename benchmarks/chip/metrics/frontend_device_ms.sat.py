"""Device self time of the step's ``frontend`` scope per step
execution in the traced window."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.scope_device_ms(ctx, ("frontend",))
