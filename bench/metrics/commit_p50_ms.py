"""Commit path: median ms from when an update was due to its
acknowledgement, over the window's acknowledged writes, by the client's
clock: the wait for a writer, the transaction and the quorum commit.
About 80 commits a window set its median, too few to hold it to an
end-to-end bound."""

import numpy as np


def read(ctx):
    ack = np.array(ctx.writes.ack[ctx.w_lo:], dtype=np.float64)
    due = np.array(ctx.writes.due[ctx.w_lo:], dtype=np.float64)
    ok = ~np.isnan(ack)
    return float(np.median(ack[ok] - due[ok])) * 1e3 if ok.any() else None
