"""Times the host blocks on the chip per decode position: `engine.fetch`
(device-to-host copy) and `engine.wait` (the meter's block) spans inside
the `engine.decode` spans, over the summed `max_new` of the traced batches.
A counter: it reads on any trace, the CPU's too."""

from bench.metrics._spans import syncs_per_token


def read(ctx):
    return syncs_per_token(ctx)
