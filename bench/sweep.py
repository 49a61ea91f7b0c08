"""Find a cell's knee: one set-up, then open-loop windows at rising rates.

    python bench/sweep.py --workload <cell> --rates 50,100,200 [--seconds 10]

Prints one JSON line per rate (latency percentiles, completed queries per
second, failures, mean batch size, and the check of that window's answers).
The rate a cell's mix fixes comes from such a sweep on the chip; this is
not part of a benchmark run.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import harness
    rates = [float(r) for r in args.rates.split(",")]
    s = harness.Session(args.workload, traced=True)
    try:
        warm = s.plan(args.seed, s.cell.mix["warmup_s"], harness.WARM_STREAM)
        plans = [s.plan(args.seed + i, args.seconds, harness.WINDOW_STREAM,
                        dict(s.cell.mix, rate_per_s=r))
                 for i, r in enumerate(rates)]
        s.warm(warm, sum(map(harness.n_updates, plans + [warm])))
        for i, (rate, plan) in enumerate(zip(rates, plans)):
            win = s.window(plan, args.seconds)
            batches = sum(c for c, _ in win.batch.values())
            size = sum(v for _, v in win.batch.values())
            checks = s.check(win, args.seed + i)
            print(json.dumps({"rate_per_s": rate, **win.e2e,
                              "attempted": win.attempted,
                              "failed": win.failed,
                              "late": win.late,
                              "mean_batch": size / batches if batches
                              else None,
                              "checks": checks}), flush=True)
    finally:
        s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
