"""Autopilot demo: a warren that reshapes itself under drifting load.

Builds a small ShardedWarren, then lets the closed-loop control plane
(``repro.dist.autopilot``) do everything an operator would otherwise do
by hand, on a fake clock so the whole "day" runs in seconds:

  * serve traffic -> the controller notices the hot group and splits it;
  * kill a replica -> anti-entropy re-syncs it back into lockstep;
  * stop traffic  -> the idle collection demotes to the static tier;

printing every structured Decision as it lands.  This is the same
Controller that ``repro.dist.elastic.autopilot(warren)`` runs on a real
interval timer in production — only the clock differs.

Run:  PYTHONPATH=src python examples/autopilot_demo.py
"""

import tempfile

from repro.core import ingest_documents, score_bm25
from repro.data.synth import doc_generator
from repro.dist.autopilot import (AntiEntropyPolicy, AutopilotConfig,
                                  ColdPolicy, Controller, Hysteresis,
                                  HotSplitPolicy)
from repro.dist.shard_router import ShardedWarren
from repro.dist.simharness import SimClock

QUERIES = ["school education student", "government law state",
           "stock money business", "vibration conductor wind"]


def main() -> None:
    static_root = tempfile.mkdtemp(prefix="autopilot-demo-")
    warren = ShardedWarren(n_shards=2, replicas=2, static_dir=static_root)
    ingest_documents(warren, doc_generator(7, 200, mean_len=30), batch=8)

    clock = SimClock()
    ctl = Controller.for_warren(warren, clock=clock, config=AutopilotConfig(
        split=HotSplitPolicy(p95_hot_ms=0.0, sustain_ticks=2, min_docs=1,
                             max_groups=3),
        cold=ColdPolicy(demote_after_ticks=2, merge_after_ticks=10 ** 6,
                        min_groups=1),
        anti_entropy=AntiEntropyPolicy(sustain_ticks=2),
        hysteresis=Hysteresis(cooldown_ticks=1, min_dwell_ticks=0),
        pool=None))

    def tick(serve: bool) -> None:
        if serve:
            with warren:
                for q in QUERIES:
                    score_bm25(warren, q, k=10)
        for d in ctl.tick():
            print(f"  {d.summary()}")
        clock.advance()

    print(f"day 1 — morning rush ({warren.n_shards} groups):")
    for _ in range(3):
        tick(serve=True)
    print(f"  -> {warren.n_shards} groups, routing epoch "
          f"{warren.routing.epoch}")

    print("day 1 — afternoon: replica (0, 1) dies:")
    warren.groups[0].mark_failed(1)
    for _ in range(4):
        tick(serve=True)
    print(f"  -> health {warren.health()}")

    print("day 1 — night: traffic stops:")
    for _ in range(4):
        tick(serve=False)
    print(f"  -> demoted: {[d is not None for d in warren.demoted()]}")

    print(f"\n{len(ctl.decisions)} decisions, "
          f"{sum(1 for d in ctl.decisions if d.outcome == 'applied')} "
          f"applied, 0 operator interventions")
    warren.close()


if __name__ == "__main__":
    main()
