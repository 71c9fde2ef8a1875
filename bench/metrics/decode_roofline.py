"""The decode program's share of its roofline, in %: the least time of its
steps (larger of FLOPs over peak FLOP/s and bytes over peak bandwidth,
from bench/cost/<family>.py) over the device time of its module events in
the trace."""

from bench.metrics._step import roofline


def read(ctx):
    return roofline(ctx, "decode")
