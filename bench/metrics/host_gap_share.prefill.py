"""Share of the engine's prefill phase (everything before the first decode
step) in which no op ran on the chip, in %: chip-idle time inside the
`engine.prefill` spans of the traced window, over their summed duration."""

from bench.metrics._spans import PREFILL, idle_share


def read(ctx):
    return idle_share(ctx, PREFILL)
