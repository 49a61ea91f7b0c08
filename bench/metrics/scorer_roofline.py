"""Device scorer: % of the HBM roofline.  The work is what an exact
exhaustive scorer must read for the window's answered queries: for each,
8 bytes (document index and impact) per posting of each distinct term,
with document frequencies from the benchmark's own corpus.  The time is
the device's busy time in the traced window, all operations together, so
the number stays the same whatever implements scoring."""

import numpy as np


def read(ctx):
    busy = ctx.trace["busy_s"]
    if busy <= 0:
        return None
    df = ctx.corpus_df
    done = ~np.isnan(ctx.reads.done)
    per_query = np.array([sum(int(df[r]) if r < len(df) else 0
                              for r in set(q)) for q in ctx.pool])
    nbytes = 8.0 * per_query[ctx.reads.query[done]].sum()
    return 100.0 * nbytes / ctx.peaks["hbm_bw"] / busy
