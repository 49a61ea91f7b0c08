"""Device scorer: ms per micro-batch copying every group's packed blocks
to the device and launching its ``bm25_topk``
(``kernel_phase_ms{bm25_topk,dispatch}``).  Host time; the wait for the
results is ``device_wait_ms``.  None where the program has no such
phase."""


def read(ctx):
    total, n = ctx.phase_ms("dispatch"), ctx.n_batches
    return total / n if total is not None and n else None
