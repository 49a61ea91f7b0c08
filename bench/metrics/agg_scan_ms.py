"""Device aggregate: ms per micro-batch copying the batch's parameters to
the device, launching every group's ``agg_scan`` and waiting for their
partial sums (``kernel_phase_ms{agg_scan,dispatch}`` plus ``{compute}``).
Host time.  None where the program has no such phases."""


def read(ctx):
    dispatch, compute = ctx.phase_ms("dispatch"), ctx.phase_ms("compute")
    n = ctx.n_batches
    if dispatch is None or compute is None or not n:
        return None
    return (dispatch + compute) / n
