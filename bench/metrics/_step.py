"""Shared arithmetic of the step metrics: the least time of each prefill
and decode step the traced batches ran, from `bench/cost/<family>.py` and
the peaks, and their model FLOPs."""


def steps(ctx, phase):
    """[(flops, bytes)] of every `phase` step the traced batches ran."""
    out = []
    for r in ctx.records:
        B, s0 = len(r.batch.out_lens), r.batch.prompt_len
        if phase == "prefill":
            out.append(ctx.cost.prefill(ctx.m, B, s0))
        else:
            out.extend(ctx.cost.decode_step(ctx.m, B, s0 + t)
                       for t in range(r.batch.max_new))
    return out


def least_s(ctx, phase):
    """Least time of the phase's steps: each the larger of FLOPs over the
    peak FLOP/s and bytes over the peak bandwidth."""
    pf, pb = ctx.peak["flops_bf16"], ctx.peak["hbm_bytes_per_s"]
    return sum(max(f / pf, b / pb) for f, b in steps(ctx, phase))


def roofline(ctx, phase):
    """Least time over the device time of the phase's program, in %."""
    if ctx.trace is None:
        return None
    from bench.trace import program_s

    device = program_s(ctx.trace, ctx.programs[phase], ctx.t0, ctx.t1)
    if device <= 0:
        return None
    return 100.0 * least_s(ctx, phase) / device


def mfu(ctx, phase):
    """Model FLOPs of the phase's steps over (host-clock seconds the engine
    spent in the phase x peak bf16 FLOP/s), in %."""
    secs = sum(r.prefill_s if phase == "prefill" else r.decode_s
               for r in ctx.records)
    flops = sum(f for f, _ in steps(ctx, phase))
    return 100.0 * flops / (secs * ctx.peak["flops_bf16"]) if secs > 0 else None
