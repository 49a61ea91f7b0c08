"""Scatter: ms per micro-batch spent in the fan-out read of every group
(``RetrievalServer.timings`` scatter; per-group stats and term lists)."""


def read(ctx):
    t0, t1 = ctx.timings
    n = ctx.n_batches
    return 1e3 * (t1["scatter_s"] - t0["scatter_s"]) / n if n else None
