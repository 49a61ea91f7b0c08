"""Device aggregate: % of the HBM roofline.  The work is what a scan must
read for the window's answered requests, counted from the benchmark's own
table: every row, 4 bytes a column, of each column the query reads (Q1 7,
Q6 4).  The time is the device's busy time in the traced window, all
operations together, so the number stays the same whatever implements
the scan."""

import numpy as np


def read(ctx):
    busy = ctx.trace["busy_s"]
    cols = getattr(ctx, "columns_read", None)
    if busy <= 0 or cols is None:
        return None
    done = ~np.isnan(ctx.reads.done)
    per_read = [cols[ctx.pool[int(q)].params[0]]
                for q in ctx.reads.query[done]]
    nbytes = 4.0 * ctx.n_rows * sum(per_read)
    return 100.0 * nbytes / ctx.peaks["hbm_bw"] / busy
