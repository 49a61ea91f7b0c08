"""Host impacts: ms per micro-batch computing, on the host, the global
df/idf and avgdl, every group's BM25 impacts and the posting cap
(``kernel_phase_ms{bm25_topk,impacts}``), between the scatter and the
packing.  None where the program has no such phase."""


def read(ctx):
    total, n = ctx.phase_ms("impacts"), ctx.n_batches
    return total / n if total is not None and n else None
