"""Device scorer: ms per micro-batch the host waits for the groups'
``bm25_topk`` results and copies them back
(``kernel_phase_ms{bm25_topk,compute}``).  Host time, not kernel time."""


def read(ctx):
    total, n = ctx.phase_ms("compute"), ctx.n_batches
    return total / n if total is not None and n else None
