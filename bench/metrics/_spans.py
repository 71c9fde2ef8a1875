"""Shared arithmetic of the engine host loop's metrics, read from the
engine's host spans (`repro.serving.engine`) in the traced window: the
spans of a phase, the chip's idle time inside them, and the sync spans
nested in them.

A program that opens no engine spans (any from before they were added) has
its phases placed by the host clock instead: each batch's decode as the
last `decode_s` of its `bench.batch` span, its prefill as the rest. Such a
program has no sync spans to count."""

from bisect import bisect_right

from bench.trace import Event, union

GENERATE, PREFILL, DECODE = "engine.generate", "engine.prefill", "engine.decode"
SYNCS = ("engine.fetch", "engine.wait")


def named(ctx, name):
    """The host spans `name` inside the traced window, by start."""
    return [e for e in ctx.trace.host
            if e.name == name and ctx.t0 <= e.start and e.end <= ctx.t1]


def phase(ctx, name):
    """The window's `engine.prefill` or `engine.decode` spans."""
    if named(ctx, GENERATE):
        return named(ctx, name)
    out = []
    for b, r in zip(named(ctx, "bench.batch"), ctx.records):
        cut = b.end - r.decode_s * 1e9
        out.append(Event(name, b.start, cut) if name == PREFILL
                   else Event(name, cut, b.end))
    return out


def _overlap(busy, spans):
    """Nanoseconds of the merged, sorted intervals `busy` inside `spans`."""
    starts = [s for s, _ in busy]
    tot = 0.0
    for e in spans:
        i = max(0, bisect_right(starts, e.start) - 1)
        while i < len(busy) and busy[i][0] < e.end:
            tot += max(0.0, min(busy[i][1], e.end) - max(busy[i][0], e.start))
            i += 1
    return tot


def idle_share(ctx, name):
    """Chip-idle time inside the phase's spans over their summed duration,
    in %, averaged over the chips; None without a TPU op line."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    spans = phase(ctx, name)
    total = sum(e.end - e.start for e in spans)
    if total <= 0:
        return None
    busy = [_overlap(union(ops, ctx.t0, ctx.t1), spans) for ops in ctx.trace.ops]
    return 100.0 * (1.0 - sum(busy) / len(busy) / total)


def syncs_per_token(ctx):
    """`engine.fetch` and `engine.wait` spans inside the decode spans, over
    the decode positions (the batches' `max_new`) of the traced records."""
    if ctx.trace is None:
        return None
    spans = phase(ctx, DECODE)
    n = sum(1 for e in ctx.trace.host if e.name in SYNCS
            and any(p.start <= e.start and e.end <= p.end for p in spans))
    tokens = sum(r.batch.max_new for r in ctx.records)
    return n / tokens if tokens else None
