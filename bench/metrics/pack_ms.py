"""Host packing: ms per micro-batch building the groups' padded
``[Q, T, L]`` blocks on the host (``kernel_phase_ms{bm25_topk,gather}``,
all groups of a batch together)."""


def read(ctx):
    total, n = ctx.phase_ms("gather"), ctx.n_batches
    return total / n if total is not None and n else None
