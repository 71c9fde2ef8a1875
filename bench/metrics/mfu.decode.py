"""The whole decode step's share of the chip's peak bf16 FLOP/s, in %:
model FLOPs of the traced batches' decode steps over the engine's host-clock
decode seconds times the peak."""

from bench.metrics._step import mfu


def read(ctx):
    return mfu(ctx, "decode")
