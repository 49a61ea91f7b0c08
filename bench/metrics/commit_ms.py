"""Commit path: median ms of ``ShardedWarren.commit()`` alone (both
phases of the quorum commit, fsyncs included) over the window's writes,
by the benchmark's own clock; waiting to start the transaction is not in
it."""

import numpy as np


def read(ctx):
    ack = np.array(ctx.writes.ack[ctx.w_lo:], dtype=np.float64)
    start = np.array(ctx.writes.commit_start[ctx.w_lo:], dtype=np.float64)
    ok = ~np.isnan(ack)
    return float(np.median(ack[ok] - start[ok])) * 1e3 if ok.any() else None
