"""Resident columns: column tables the groups built during the window
(``serve_column_builds_total``, through the snapshots of the server's
timings).  0 while no snapshot key moves; a build in the window means
something invalidated the resident columns.  None where the program does
not count them."""


def read(ctx):
    t0, t1 = ctx.timings
    if "column_builds" not in t0:
        return None
    return float(t1["column_builds"] - t0["column_builds"])
