"""The whole prefill step's share of the chip's peak bf16 FLOP/s, in %:
model FLOPs of the traced batches' prefill steps over the engine's host-clock
prefill seconds times the peak."""

from bench.metrics._step import mfu


def read(ctx):
    return mfu(ctx, "prefill")
