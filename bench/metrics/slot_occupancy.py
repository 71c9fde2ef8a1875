"""Requested output tokens over the decode slots the static batches ran
(batch x decode steps), in %: the share of decode work that a request
asked for. A counter: it needs no trace."""


def read(ctx):
    asked = sum(sum(r.batch.out_lens) for r in ctx.records)
    ran = sum(len(r.batch.out_lens) * r.batch.max_new for r in ctx.records)
    return 100.0 * asked / ran if ran else None
