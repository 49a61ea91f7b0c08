"""The control of the ``correct`` check: the served path with its scores
carried in bfloat16, the nearest precision below the float32 the
configurations state, must come out as not correct.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]

One set-up, then one window per seed at the cell's own load, each judged
by the same check as a benchmark run.  Prints one JSON line per seed with
the checks; a seed whose ``correct`` is true is a control that failed to
fail.  Not part of a benchmark run.
"""

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
# libtpu logs to /tmp unless told otherwise; a run writes only in its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH.parent / "src"))


class Bf16Server:
    """``server`` with every served score rounded to bfloat16."""

    def __init__(self, server):
        self._server = server
        self.batcher = _Batcher(server.batcher)

    def __getattr__(self, name):
        return getattr(self._server, name)


class _Batcher:
    def __init__(self, batcher):
        self._batcher = batcher

    def __getattr__(self, name):
        return getattr(self._batcher, name)

    def submit(self, request):
        return _Handle(self._batcher.submit(request))


class _Handle:
    def __init__(self, handle):
        self._handle = handle

    def get(self, block=True, timeout=None):
        import numpy as np

        from kinds.passages import reference
        ans = self._handle.get(block, timeout)
        if not ans:
            return ans
        scores = reference.bf16(np.array([s for _, s in ans]))
        return [(a, float(s)) for (a, _), s in zip(ans, scores)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import harness
    seeds = [int(x) for x in args.seeds.split(",")]
    s = harness.Session(args.workload, hooks={"server": Bf16Server})
    try:
        warm = s.plan(seeds[0], s.cell.mix["warmup_s"], harness.WARM_STREAM)
        plans = [s.plan(x, args.seconds, harness.WINDOW_STREAM)
                 for x in seeds]
        s.warm(warm, sum(map(harness.n_updates, plans + [warm])))
        for seed, plan in zip(seeds, plans):
            win = s.window(plan, args.seconds)
            checks = s.check(win, seed)
            print(json.dumps({"seed": seed, "correct": all(
                c["value"] <= c["limit"] for c in checks.values()),
                "checks": checks}), flush=True)
    finally:
        s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
