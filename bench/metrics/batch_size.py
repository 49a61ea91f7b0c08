"""Batcher: mean requests per micro-batch in the window, from the
program's ``serve_batch_size`` histogram."""


def read(ctx):
    count = sum(c for c, _ in ctx.batch.values())
    total = sum(s for _, s in ctx.batch.values())
    return total / count if count else None
