"""Share of the engine's decode loop in which no op ran on the chip, in %:
chip-idle time (the union of op intervals) inside the `engine.decode` spans
of the traced window, over those spans' summed duration."""

from bench.metrics._spans import DECODE, idle_share


def read(ctx):
    return idle_share(ctx, DECODE)
