"""Merge: ms per micro-batch in the global k-way merge of the groups'
top-k lists (``RetrievalServer.timings`` merge)."""


def read(ctx):
    t0, t1 = ctx.timings
    n = ctx.n_batches
    return 1e3 * (t1["merge_s"] - t0["merge_s"]) / n if n else None
