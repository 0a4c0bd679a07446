"""Bytes the engine uploads per frame fed, from its own counts
(``h2d_bytes`` over ``frames_fed``) over the untraced window."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.h2d_bytes_per_frame(ctx)
