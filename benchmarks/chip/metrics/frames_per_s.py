"""Frames completed over the whole window."""

from chipbench import readers


def read(ctx):
    return readers.frames_per_s(ctx)
