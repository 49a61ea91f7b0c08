"""A day in the life of an autopiloted warren: closed loop vs no policy.

Three passes, one report:

1. **Simulated day** (deterministic, seeded).  A ``DriftingWorkload``
   (Zipf-over-topics traffic whose hot spot migrates each phase) drives a
   ``SimCluster`` for N ticks, twice: once with the autopilot
   ``Controller`` closing the loop, once with no policy.  The headline
   figure is worst-group p95 over time: the controller must keep it
   within ``--flatness`` (default 1.5x) of its starting value while the
   no-policy baseline degrades more — the run FAILS (non-zero exit) if
   either half of that claim breaks.  Fully reproducible per seed.

1b. **Burn-driven day**.  The same drifting traffic, but the raw p95
   split trigger is disabled and the controller acts only on the serving
   SLO's *sustained burn rate*: the sim cluster feeds its modeled
   latencies into the real ``scatter_latency_ms{group}`` histograms, an
   ``obs.SLOMonitor`` (on the sim clock, tick-denominated windows)
   computes multi-window ``slo_burn_rate``, and
   ``HotSplitPolicy.burn_hot`` fires the splits.  The run FAILS unless
   at least one burn-attributed split is applied.

2. **Real-warren pass**.  A live ``ShardedWarren`` under the controller
   (real ``WarrenSignals``/``WarrenActuator``, fake clock): traffic heats
   the groups, the controller splits, a replica is killed and
   anti-entropy resurrects it, traffic stops and the collection demotes —
   with served rankings checked bit-identical to a single-index oracle
   after every action.

``--smoke`` shrinks all passes to CI size.
"""

import math
import time

from repro import obs
from repro.dist.autopilot import (AntiEntropyPolicy, AutopilotConfig,
                                  ColdPolicy, Controller, Hysteresis,
                                  HotSplitPolicy)
from repro.dist.simharness import DriftingWorkload, SimClock, SimCluster

QUERIES = ["school education student", "government law state",
           "stock money business", "vibration conductor wind"]


# ------------------------------------------------------------------ #
# pass 1: the simulated day
# ------------------------------------------------------------------ #
def _sim_config(max_groups: int) -> AutopilotConfig:
    return AutopilotConfig(
        split=HotSplitPolicy(p95_hot_ms=40.0, sustain_ticks=3, min_docs=64,
                             max_groups=max_groups),
        cold=ColdPolicy(demote_after_ticks=15, merge_after_ticks=40,
                        min_groups=2),
        hysteresis=Hysteresis(cooldown_ticks=4, min_dwell_ticks=1,
                              window_ticks=30, max_actions_per_window=6),
        pool=None)


def _run_sim_day(seed: int, ticks: int, controlled: bool,
                 max_groups: int = 8):
    clock = SimClock()
    cluster = SimCluster(docs=1200, base_ms=2.0, ms_per_doc=0.05)
    wl = DriftingWorkload(seed=seed, topics=48, reads_per_tick=120,
                          writes_per_tick=8, phase_ticks=max(ticks // 3, 10))
    ctl = Controller(cluster, cluster, config=_sim_config(max_groups),
                     clock=clock)
    worst = []
    for _ in range(ticks):
        reads, writes = wl.tick_keys()
        cluster.route(reads)
        cluster.ingest(writes)
        if controlled:
            ctl.tick()
        else:
            cluster.collect()            # same signal drain, no policy
        clock.advance()
        worst.append(max(cluster.base_ms + cluster.ms_per_doc * g.docs
                         for g in cluster.active()))
    return ctl, cluster, worst


def sim_day(seed: int, ticks: int, flatness: float) -> dict:
    t0 = time.time()
    ctl, cluster, worst_ctl = _run_sim_day(seed, ticks, controlled=True)
    _, _, worst_base = _run_sim_day(seed, ticks, controlled=False)
    wall = time.time() - t0

    settle = max(ticks // 8, 5)          # the loop needs a few sustains
    start = worst_ctl[0]
    peak_ctl = max(worst_ctl[settle:])
    peak_base = max(worst_base)
    by_outcome: dict = {}
    for d in ctl.decisions:
        key = f"{d.kind}/{d.outcome}"
        by_outcome[key] = by_outcome.get(key, 0) + 1

    print(f"# simulated day: seed {seed}, {ticks} ticks, "
          f"{len(cluster.active())} active groups at close ({wall:.2f}s)")
    print(f"  decisions: {by_outcome or 'none'}")
    print(f"  worst-group p95 ms: start {start:.1f} -> controller peak "
          f"{peak_ctl:.1f} ({peak_ctl / start:.2f}x), no-policy peak "
          f"{peak_base:.1f} ({peak_base / start:.2f}x)")
    ok_flat = peak_ctl <= flatness * start
    ok_beats = peak_base > peak_ctl
    print(f"  flatness (controller <= {flatness:.2f}x start): "
          f"{'PASS' if ok_flat else 'FAIL'}; controller beats baseline: "
          f"{'PASS' if ok_beats else 'FAIL'}")
    if not (ok_flat and ok_beats):
        raise SystemExit("day-in-the-life flatness check failed")
    return {"seed": seed, "ticks": ticks, "p95_start_ms": start,
            "p95_peak_controller_ms": peak_ctl,
            "p95_peak_baseline_ms": peak_base,
            "flatness_bound": flatness,
            "decisions": by_outcome,
            "p95_trajectory_controller_ms": [round(x, 3) for x in worst_ctl],
            "p95_trajectory_baseline_ms": [round(x, 3) for x in worst_base]}


# ------------------------------------------------------------------ #
# pass 1b: the burn-driven day — autopilot acting on slo_burn_rate
# ------------------------------------------------------------------ #
def burn_day(seed: int, ticks: int) -> dict:
    clock = SimClock()
    cluster = SimCluster(docs=1200, base_ms=2.0, ms_per_doc=0.05,
                         observe_latency=True)
    wl = DriftingWorkload(seed=seed, topics=48, reads_per_tick=120,
                          writes_per_tick=8,
                          phase_ticks=max(ticks // 3, 10))
    monitor = obs.SLOMonitor(
        slos=[obs.SLO(name="serving_p95", kind="latency", objective=0.95,
                      metric="scatter_latency_ms", threshold_ms=40.0)],
        windows=(("short", 5.0), ("long", 20.0)), clock=clock)
    cfg = AutopilotConfig(
        # raw p95 and skew triggers OFF: only sustained burn splits
        split=HotSplitPolicy(p95_hot_ms=math.inf, skew_ratio=math.inf,
                             min_docs=64, sustain_ticks=3, max_groups=8,
                             burn_hot=1.0),
        cold=ColdPolicy(demote_after_ticks=15, merge_after_ticks=40,
                        min_groups=2),
        hysteresis=Hysteresis(cooldown_ticks=4, min_dwell_ticks=1,
                              window_ticks=30, max_actions_per_window=6),
        pool=None)
    ctl = Controller(obs.SLOSignalSource(cluster, monitor), cluster,
                     config=cfg, clock=clock)
    t0 = time.time()
    for _ in range(ticks):
        reads, writes = wl.tick_keys()
        cluster.route(reads)
        cluster.ingest(writes)
        ctl.tick()
        clock.advance()
    wall = time.time() - t0

    burn_splits = [d for d in ctl.decisions
                   if d.kind == "split" and d.outcome == "applied"
                   and "burn" in d.reason]
    print(f"# burn-driven day: seed {seed}, {ticks} ticks, "
          f"{len(cluster.active())} active groups at close, "
          f"{len(burn_splits)} burn-driven splits ({wall:.2f}s)")
    if burn_splits:
        print(f"  first: {burn_splits[0].summary()}")
    print(f"  sustained serving burn at close: "
          f"{monitor.burn('serving_p95'):.2f}")
    ok = len(burn_splits) > 0
    print(f"  autopilot acted on slo_burn_rate: "
          f"{'PASS' if ok else 'FAIL'}")
    if not ok:
        raise SystemExit("burn-driven day produced no burn-driven split")
    return {"seed": seed, "ticks": ticks,
            "burn_splits": len(burn_splits),
            "first_burn_split": burn_splits[0].to_record(),
            "closing_burn": monitor.burn("serving_p95"),
            "groups_at_close": len(cluster.active())}


# ------------------------------------------------------------------ #
# pass 2: the real warren under the controller, parity-checked
# ------------------------------------------------------------------ #
def real_warren_pass(smoke: bool, static_dir: str) -> dict:
    import numpy as np

    from repro.core import DynamicIndex, Warren, score_bm25
    from repro.data.synth import doc_generator
    from repro.core import ingest_documents
    from repro.dist.shard_router import ShardedWarren

    n_docs = 200 if smoke else 1500
    warren = ShardedWarren(n_shards=2, replicas=2, static_dir=static_dir)
    single = Warren(DynamicIndex())
    corpus = list(doc_generator(7, n_docs, mean_len=30))
    ingest_documents(warren, corpus, batch=8)
    ingest_documents(single, corpus, batch=128)

    clock = SimClock()
    cfg = AutopilotConfig(
        split=HotSplitPolicy(p95_hot_ms=0.0, sustain_ticks=2, min_docs=1,
                             max_groups=3),
        cold=ColdPolicy(demote_after_ticks=2, merge_after_ticks=10 ** 6,
                        min_groups=1),
        anti_entropy=AntiEntropyPolicy(max_seq_lag=0, sustain_ticks=2),
        hysteresis=Hysteresis(cooldown_ticks=1, min_dwell_ticks=0,
                              window_ticks=50, max_actions_per_window=50),
        pool=None)
    ctl = Controller.for_warren(warren, config=cfg, clock=clock)

    parity_checks = [0]

    def assert_parity():
        with warren, single:
            for q in QUERIES:
                got = [s for _, s in score_bm25(warren, q, k=10)]
                ref = [s for _, s in score_bm25(single, q, k=10)]
                np.testing.assert_allclose(got, ref, rtol=1e-9)
        parity_checks[0] += 1

    def serve(rounds=1):
        with warren:
            for _ in range(rounds):
                for q in QUERIES:
                    score_bm25(warren, q, k=10)

    t0 = time.time()
    # hot traffic -> controller split (capped at max_groups)
    for _ in range(3):
        serve()
        ctl.tick()
        clock.advance()
        assert_parity()
    # replica loss -> anti-entropy resurrection
    warren.groups[0].mark_failed(1)
    for _ in range(4):
        serve()
        ctl.tick()
        clock.advance()
    assert_parity()
    # traffic stops -> demotion to the static tier
    for _ in range(4):
        ctl.tick()
        clock.advance()
    assert_parity()
    wall = time.time() - t0

    kinds = sorted({(d.kind, d.outcome) for d in ctl.decisions})
    n_demoted = sum(1 for d in warren.demoted() if d is not None)
    all_alive = all(all(a) for a in warren.health())
    print(f"# real warren under the controller: {n_docs} docs, "
          f"{warren.n_shards} groups after split, {n_demoted} demoted, "
          f"{parity_checks[0]} oracle parity checks ({wall:.2f}s)")
    print(f"  decision kinds: {kinds}")
    ok = (warren.n_shards == 3 and all_alive and n_demoted > 0
          and any(d.kind == "split" and d.outcome == "applied"
                  for d in ctl.decisions)
          and any(d.kind == "resync" and d.outcome == "applied"
                  for d in ctl.decisions))
    print(f"  split + resync + demote all applied, every replica live: "
          f"{'PASS' if ok else 'FAIL'}")
    warren.close()
    if not ok:
        raise SystemExit("real-warren controller pass failed")
    return {"docs": n_docs, "groups_after": 3, "demoted": n_demoted,
            "parity_checks": parity_checks[0], "wall_s": wall,
            "decisions": [d.to_record() for d in ctl.decisions]}


def witness_pass(smoke: bool, baseline_wall: float) -> dict:
    """Re-run the real-warren pass with the LockWitness installed:
    proves the whole day's acquisition orders against
    analysis/lock_hierarchy.toml and reports the witness overhead vs the
    un-witnessed pass that just ran."""
    import os
    import tempfile

    from repro import obs

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hierarchy = os.path.join(root, "analysis", "lock_hierarchy.toml")
    w = obs.install_witness(obs.LockWitness.from_hierarchy(hierarchy))
    try:
        with tempfile.TemporaryDirectory(prefix="ditl-witness-") as d:
            real = real_warren_pass(smoke, d)
        w.check()          # any observed inversion fails the bench
        edges = w.edges()
    finally:
        obs.uninstall_witness()
    overhead = ((real["wall_s"] - baseline_wall) / baseline_wall * 100
                if baseline_wall else 0.0)
    print(f"# lock witness: {len(edges)} acquisition edges observed, "
          f"0 violations, overhead {overhead:+.1f}% vs un-witnessed pass")
    return {"edges": len(edges), "violations": 0,
            "wall_s": real["wall_s"], "overhead_pct": overhead}


def run(seed: int = 11, ticks: int = 400, flatness: float = 1.5,
        smoke: bool = False, lock_witness: bool = False):
    if smoke:
        ticks = min(ticks, 150)
    sim_day(seed, ticks, flatness)
    burn_day(seed, ticks)
    import tempfile

    with tempfile.TemporaryDirectory(prefix="ditl-static-") as d:
        real = real_warren_pass(smoke, d)
    if lock_witness:
        witness_pass(smoke, real["wall_s"])

if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--ticks", type=int, default=400,
                    help="length of the simulated day")
    ap.add_argument("--flatness", type=float, default=1.5,
                    help="controller p95 must stay within this factor of "
                         "its starting value")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: short sim day + tiny real corpus "
                         "(same checks, same determinism)")
    ap.add_argument("--lock-witness", action="store_true",
                    help="re-run the real-warren pass with the runtime "
                         "LockWitness installed (analysis/lock_hierarchy"
                         ".toml); fails on any observed lock-order "
                         "violation and reports the witness overhead")
    args = ap.parse_args()
    run(seed=args.seed, ticks=args.ticks, flatness=args.flatness,
        smoke=args.smoke, lock_witness=args.lock_witness)
