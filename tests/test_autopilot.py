"""Autopilot control plane: exact decision sequences on a fake clock.

Tier-1 here covers the acceptance criteria of the autopilot issue, fully
deterministically — seeded workloads, SimClock, zero wall-clock sleeps:

  * canned scenarios resolve to EXACT decision sequences: a sustained-hot
    group splits, a cold group demotes then merges away, a diverged (or
    dead) replica re-syncs, and repair outranks reshaping;
  * hysteresis provably prevents flapping: a split is never reverted by
    a merge of the same group inside the cooldown window, any two
    actions are separated by the min-dwell, and attempted actions per
    sliding window are bounded — asserted on canned data AND as a
    property over arbitrary signal streams (hypothesis);
  * an aborted migration triggers capped exponential backoff — the
    controller keeps deciding (never wedges) and recovers when the
    mechanism heals;
  * the simulated day-in-the-life is bit-reproducible per seed, and the
    controller keeps sim p95 flat while a no-policy baseline degrades;
  * ``ScatterGather.resize`` swaps worker width under an in-flight
    fan-out without dropping results (the PR-4 static-sizing fix);
  * the real-warren closed loop: live signals + live actuator split a
    hot group, resurrect a dead replica, and demote an idle group, with
    served rankings bit-identical to a single index throughout.

Chaos variants (replica kills mid-controller-initiated split) live
behind the ``stress`` marker.
"""

import math
import threading

import pytest
from hypothesis import given, settings, strategies as st

from _sim import (RecordingActuator, decision_seq, run_scripted, sig,
                  tight_config)
from repro.core import score_bm25
from repro.dist.autopilot import (AntiEntropyPolicy, AutopilotConfig,
                                  ColdPolicy, Controller, Decision,
                                  GroupSignal, Hysteresis, HotSplitPolicy,
                                  RetryPolicy, ScriptedSignals,
                                  WarrenActuator, WarrenSignals)
from repro.dist.parallel import ScatterGather
from repro.dist.simharness import (DriftingWorkload, SimClock, SimCluster)


# ------------------------------------------------------------------ #
# exact decision sequences (canned scenarios, scripted signals)
# ------------------------------------------------------------------ #
def test_hot_split_exact_sequence():
    """p95 above threshold for sustain_ticks -> split; the streak resets
    and the cooldown holds, so the second split lands exactly when both
    have re-elapsed."""
    hot = [sig(0, docs=500, p95=80.0, reads=50), sig(1, docs=400, reads=40)]
    ctl, act = run_scripted([hot] * 10)
    assert decision_seq(ctl) == [
        (2, "split", 0, 2, "applied"),
        (7, "split", 0, 3, "applied"),
    ]
    assert act.calls == [("split", 0), ("split", 0)]


def test_skew_split_without_latency_signal():
    """Doc-count skew alone (p95 NaN, e.g. registry disabled) still
    triggers the split."""
    skew = [sig(0, docs=1500, reads=10), sig(1, docs=100, reads=10),
            sig(2, docs=110, reads=10)]
    ctl, _ = run_scripted([skew] * 4)
    assert decision_seq(ctl)[0] == (2, "split", 0, 3, "applied")


def test_cold_demote_then_merge_exact_sequence():
    """An idle group demotes at demote_after_ticks, then (still idle)
    merges into the smallest other active group at merge_after_ticks."""
    busy = [sig(0, docs=500, reads=30), sig(1, docs=300, reads=20)]
    before = [busy + [sig(2, docs=80, reads=0)]] * 3
    after = [busy + [sig(2, docs=80, reads=0, demoted=True)]] * 7
    ctl, act = run_scripted(before + after)
    assert decision_seq(ctl) == [
        (2, "demote", 2, None, "applied"),
        (8, "merge", 2, 1, "applied"),     # dest = smallest survivor
    ]
    assert act.calls == [("demote", 2), ("merge", 1, 2)]


def test_merge_respects_min_groups():
    """Two active groups with min_groups=2: the idle one demotes but is
    never merged away."""
    ticks = [[sig(0, docs=150, reads=30),
              sig(1, docs=80, reads=0, demoted=(t >= 3))]
             for t in range(12)]
    ctl, act = run_scripted(ticks)
    assert [d.kind for d in ctl.decisions] == ["demote"]


def test_resync_diverged_replica_exact_sequence():
    """A live replica whose seqnum trails the group max beyond the lag
    budget for sustain_ticks gets exactly one re-sync."""
    diverged = [sig(0, reads=10), sig(1, reads=10, seqs=(9, 5))]
    healed = [sig(0, reads=10), sig(1, reads=10, seqs=(9, 9))]
    ctl, act = run_scripted([diverged] * 2 + [healed] * 6)
    assert decision_seq(ctl) == [(1, "resync", 1, 1, "applied")]
    assert act.calls == [("resync", 1, 1)]


def test_resync_dead_replica():
    dead = [sig(0, reads=10, seqs=(9, 3), alive=(True, False))]
    ok = [sig(0, reads=10, seqs=(9, 9))]
    ctl, act = run_scripted([dead] * 2 + [ok] * 4)
    assert decision_seq(ctl) == [(1, "resync", 0, 1, "applied")]
    assert "dead" in ctl.decisions[0].reason


def test_repair_outranks_reshaping():
    """When a re-sync and a split are eligible on the same tick, the
    re-sync goes first (repair before reshaping)."""
    cfg = tight_config(anti_entropy=AntiEntropyPolicy(max_seq_lag=0,
                                                      sustain_ticks=3))
    both = [sig(0, docs=500, p95=80.0, reads=50),
            sig(1, reads=10, seqs=(9, 5))]
    ctl, _ = run_scripted([both] * 6, config=cfg)
    kinds = [(d.tick, d.kind) for d in ctl.decisions]
    assert kinds[0] == (2, "resync")
    assert kinds[1][1] == "split" and kinds[1][0] > 2


def test_decision_records_are_structured(tmp_path):
    """Decisions carry the full audit record and stream to the JSONL log."""
    import json

    log = tmp_path / "decisions.jsonl"
    hot = [sig(0, docs=500, p95=80.0, reads=50), sig(1, docs=400, reads=40)]
    clock = SimClock(start=100.0)
    ctl = Controller(ScriptedSignals([hot] * 3), RecordingActuator(next_gid=2),
                     config=tight_config(), clock=clock,
                     decision_log=str(log))
    for _ in range(3):
        ctl.tick()
        clock.advance()
    recs = [json.loads(line) for line in log.read_text().splitlines()]
    assert len(recs) == 1 and recs[0]["kind"] == "split"
    assert recs[0]["outcome"] == "applied" and recs[0]["t"] == 102.0
    assert "hot for 3 ticks" in recs[0]["reason"]
    assert ctl.decisions[0].to_record() == recs[0]


# ------------------------------------------------------------------ #
# hysteresis: the controller provably cannot flap
# ------------------------------------------------------------------ #
def test_split_never_reverted_by_merge_within_cooldown():
    """The canned flap bait: a group splits, then instantly goes idle
    with an aggressive merge policy.  The cooldown must hold the line."""
    cfg = tight_config(
        cold=ColdPolicy(demote_after_ticks=2, merge_after_ticks=3,
                        min_groups=1),
        hysteresis=Hysteresis(cooldown_ticks=6, min_dwell_ticks=1,
                              window_ticks=20, max_actions_per_window=8))
    hot = [sig(0, docs=500, p95=80.0, reads=50), sig(1, docs=400, reads=40)]
    idle = [sig(0, docs=250, reads=0), sig(1, docs=400, reads=40),
            sig(2, docs=250, reads=0)]
    ctl, _ = run_scripted([hot] * 3 + [idle] * 12, config=cfg)
    split = ctl.decisions[0]
    assert (split.tick, split.kind, split.outcome) == (2, "split", "applied")
    for d in ctl.decisions[1:]:
        if d.group in (0, split.target) or d.target in (0, split.target):
            assert d.tick > split.tick + cfg.hysteresis.cooldown_ticks, \
                f"{d.summary()} inside the cooldown window"


def test_min_dwell_separates_all_actions():
    """Even with every group permanently eligible, consecutive attempts
    are separated by more than min_dwell_ticks."""
    cfg = tight_config(
        anti_entropy=AntiEntropyPolicy(max_seq_lag=0, sustain_ticks=1),
        hysteresis=Hysteresis(cooldown_ticks=0, min_dwell_ticks=2,
                              window_ticks=50, max_actions_per_window=50))
    lag = [sig(g, reads=10, seqs=(9, 5)) for g in range(4)]
    ctl, _ = run_scripted([lag] * 12, config=cfg)
    ticks = [d.tick for d in ctl.decisions]
    assert ticks, "expected at least one action"
    assert all(b - a > 2 for a, b in zip(ticks, ticks[1:]))


def test_window_budget_bounds_total_actions():
    cfg = tight_config(
        anti_entropy=AntiEntropyPolicy(max_seq_lag=0, sustain_ticks=1),
        hysteresis=Hysteresis(cooldown_ticks=0, min_dwell_ticks=0,
                              window_ticks=10, max_actions_per_window=2))
    lag = [sig(g, reads=10, seqs=(9, 5)) for g in range(4)]
    ctl, _ = run_scripted([lag] * 40, config=cfg)
    ticks = [d.tick for d in ctl.decisions]
    assert len(ticks) >= 4                     # budget refills across windows
    for i, t in enumerate(ticks):
        inside = [u for u in ticks if t - 10 < u <= t]
        assert len(inside) <= 2, f"window ending at {t}: {inside}"


def _stream_strategy():
    """Arbitrary 3-group signal streams: any docs/latency/read pattern,
    replicas diverging and dying at random."""
    group = st.tuples(st.integers(0, 2000),            # docs
                      st.sampled_from([float("nan"), 5.0, 40.0, 80.0, 200.0]),
                      st.integers(0, 50),              # reads
                      st.integers(0, 9),               # trailing replica seq
                      st.booleans())                   # replica 1 alive
    return st.lists(st.tuples(group, group, group), min_size=10, max_size=40)


@given(_stream_strategy())
@settings(max_examples=30, deadline=None)
def test_property_hysteresis_invariants_hold_for_any_stream(stream):
    """For ARBITRARY signal sequences: the action budget per sliding
    window holds, min-dwell separates attempts, and no group is touched
    again within cooldown of an applied action on it."""
    cfg = tight_config(
        split=HotSplitPolicy(p95_hot_ms=50.0, skew_ratio=3.0, min_docs=10,
                             sustain_ticks=2, max_groups=16),
        cold=ColdPolicy(demote_after_ticks=2, merge_after_ticks=4,
                        min_groups=1),
        anti_entropy=AntiEntropyPolicy(max_seq_lag=0, sustain_ticks=2),
        hysteresis=Hysteresis(cooldown_ticks=5, min_dwell_ticks=1,
                              window_ticks=12, max_actions_per_window=3))
    ticks = [[GroupSignal(group=g, docs=docs, p95_ms=p95, reads=reads,
                          replica_seqs=(9, seq), alive=(True, alive))
              for g, (docs, p95, reads, seq, alive) in enumerate(tick)]
             for tick in stream]
    ctl, _ = run_scripted(ticks, config=cfg)

    attempts = [d.tick for d in ctl.decisions]
    hys = cfg.hysteresis
    for i, t in enumerate(attempts):
        inside = [u for u in attempts if t - hys.window_ticks < u <= t]
        assert len(inside) <= hys.max_actions_per_window
    assert all(b - a > hys.min_dwell_ticks
               for a, b in zip(attempts, attempts[1:]))

    def touched(d):
        out = {d.group}
        if d.kind in ("split", "merge") and d.target is not None:
            out.add(d.target)
        return out

    applied = [d for d in ctl.decisions if d.outcome == "applied"]
    for d in applied:
        for later in ctl.decisions:
            if d.tick < later.tick <= d.tick + hys.cooldown_ticks:
                assert not (touched(d) & touched(later)), \
                    f"{later.summary()} within cooldown of {d.summary()}"
                assert not (later.kind == "merge" and d.kind == "split"
                            and later.group in touched(d))


# ------------------------------------------------------------------ #
# aborted migrations: capped exponential backoff, never wedged
# ------------------------------------------------------------------ #
def test_backoff_on_aborted_split_is_capped_exponential():
    cfg = tight_config(
        hysteresis=Hysteresis(cooldown_ticks=1, min_dwell_ticks=0,
                              window_ticks=100, max_actions_per_window=100),
        retry=RetryPolicy(base_ticks=1, cap_ticks=8))
    hot = [sig(0, docs=500, p95=80.0, reads=50), sig(1, docs=400, reads=40)]
    act = RecordingActuator(next_gid=2, fail_kinds={"split"})
    ctl, _ = run_scripted([hot] * 40, config=cfg, actuator=act)
    assert all(d.outcome == "aborted" for d in ctl.decisions)
    assert len(ctl.decisions) >= 5             # kept retrying: never wedged
    gaps = [b.tick - a.tick for a, b in zip(ctl.decisions,
                                            ctl.decisions[1:])]
    assert gaps == sorted(gaps)                # monotone backoff
    assert gaps[0] <= 2 and max(gaps) <= cfg.retry.cap_ticks + 1
    assert gaps[-1] == cfg.retry.cap_ticks + 1  # capped, not unbounded


def test_backoff_recovers_when_mechanism_heals():
    cfg = tight_config(
        hysteresis=Hysteresis(cooldown_ticks=1, min_dwell_ticks=0,
                              window_ticks=100, max_actions_per_window=100))
    hot = [sig(0, docs=500, p95=80.0, reads=50), sig(1, docs=400, reads=40)]
    act = RecordingActuator(next_gid=2, fail_kinds={"split"}, fail_budget=2)
    ctl, _ = run_scripted([hot] * 20, config=cfg, actuator=act)
    outcomes = [d.outcome for d in ctl.decisions]
    assert outcomes[:3] == ["aborted", "aborted", "applied"]
    assert ctl.decisions[2].detail == ""


def test_unexpected_actuator_error_is_contained():
    """A non-Rebalance exception from the actuator becomes outcome
    'failed' with backoff — the control loop itself never raises."""

    class Exploding(RecordingActuator):
        def split(self, group):
            super().split(group)
            raise RuntimeError("boom")

    hot = [sig(0, docs=500, p95=80.0, reads=50), sig(1, docs=400, reads=40)]
    ctl, _ = run_scripted([hot] * 8, actuator=Exploding(next_gid=2))
    assert ctl.decisions and ctl.decisions[0].outcome == "failed"
    assert "RuntimeError: boom" in ctl.decisions[0].detail


# ------------------------------------------------------------------ #
# the simulated day in the life
# ------------------------------------------------------------------ #
def _run_day(seed, controlled=True, ticks=150):
    clock = SimClock()
    cluster = SimCluster(docs=1200, base_ms=2.0, ms_per_doc=0.05)
    wl = DriftingWorkload(seed=seed, topics=48, reads_per_tick=120,
                          writes_per_tick=8, phase_ticks=50)
    cfg = AutopilotConfig(
        split=HotSplitPolicy(p95_hot_ms=40.0, sustain_ticks=3, min_docs=64,
                             max_groups=8),
        cold=ColdPolicy(demote_after_ticks=15, merge_after_ticks=40,
                        min_groups=2),
        hysteresis=Hysteresis(cooldown_ticks=4, min_dwell_ticks=1,
                              window_ticks=30, max_actions_per_window=6),
        pool=None)
    ctl = Controller(cluster, cluster, config=cfg, clock=clock)
    worst = []
    for _ in range(ticks):
        reads, writes = wl.tick_keys()
        cluster.route(reads)
        cluster.ingest(writes)
        if controlled:
            ctl.tick()
        else:
            cluster.collect()               # same signal drain, no policy
        clock.advance()
        worst.append(max(cluster.base_ms + cluster.ms_per_doc * g.docs
                         for g in cluster.active()))
    return ctl, cluster, worst


def test_sim_day_is_bit_reproducible_per_seed():
    ctl_a, cluster_a, worst_a = _run_day(seed=11)
    ctl_b, cluster_b, worst_b = _run_day(seed=11)
    assert decision_seq(ctl_a) == decision_seq(ctl_b)
    assert cluster_a.actions == cluster_b.actions
    assert worst_a == worst_b
    ctl_c, _, _ = _run_day(seed=12)
    assert decision_seq(ctl_c) != decision_seq(ctl_a)


def test_sim_day_controller_flattens_p95_vs_no_policy_baseline():
    """The headline closed-loop claim, in miniature: under the same
    drifting workload the controlled cluster's worst-group p95 stays
    near its starting value while the uncontrolled one degrades."""
    ctl, cluster, worst_ctl = _run_day(seed=11, controlled=True)
    _, _, worst_base = _run_day(seed=11, controlled=False)
    assert any(d.outcome == "applied" for d in ctl.decisions)
    start = worst_ctl[0]
    assert max(worst_ctl[20:]) <= 1.5 * start
    assert max(worst_base) > max(worst_ctl[20:])


def test_sim_cluster_conserves_docs_across_actions():
    _, cluster, _ = _run_day(seed=11, controlled=True)
    # every ingested doc is owned by exactly one active group
    assert cluster.total_docs() == 1200 + 8 * 150
    for k in [i / 97 for i in range(97)]:
        cluster.owner(k)                    # no key orphaned by split/merge


# ------------------------------------------------------------------ #
# ScatterGather.resize: elastic pool width (PR-4 static sizing fix)
# ------------------------------------------------------------------ #
def test_scatter_resize_completes_inflight_fanout():
    """Resize the pool while a fan-out is blocked mid-flight: the old
    executor's work completes, results stay ordered, and later fan-outs
    use the new width."""
    pool = ScatterGather(workers=2)
    started, release = threading.Event(), threading.Event()

    def thunk(i):
        def run():
            started.set()
            assert release.wait(timeout=30)
            return i
        return run

    out = {}
    t = threading.Thread(
        target=lambda: out.setdefault("r", pool.run([thunk(i)
                                                     for i in range(4)])))
    t.start()
    assert started.wait(timeout=30)
    pool.resize(6)                          # swap width mid-flight
    assert pool.workers == 6
    release.set()
    t.join(timeout=30)
    assert not t.is_alive() and out["r"] == [0, 1, 2, 3]
    assert pool.run([lambda i=i: i * i for i in range(8)]) == \
        [i * i for i in range(8)]
    pool.close()


def test_scatter_resize_validation_and_noops():
    pool = ScatterGather(workers=3)
    with pytest.raises(ValueError):
        pool.resize(0)
    inner = pool._pool
    pool.resize(3)                          # same width: executor untouched
    assert pool._pool is inner
    pool.close()
    pool.resize(8)                          # closed: no-op, stays degraded
    assert pool.workers == 3
    assert pool.run([lambda: 1, lambda: 2]) == [1, 2]


def test_controller_autoscales_pool_to_group_count():
    from repro.dist.autopilot import PoolPolicy

    cfg = tight_config(pool=PoolPolicy(min_workers=2, max_workers=4))
    ticks = [[sig(g, reads=10) for g in range(n)]
             for n in (1, 1, 3, 3, 6, 6)]
    pool = ScatterGather(workers=8)
    clock = SimClock()
    ctl = Controller(ScriptedSignals(ticks), RecordingActuator(),
                     config=cfg, clock=clock, pool=pool)
    widths = []
    for _ in range(len(ticks)):
        ctl.tick()
        widths.append(pool.workers)
        clock.advance()
    assert widths == [2, 2, 3, 3, 4, 4]     # clamped to [min, max]
    pool.close()


# ------------------------------------------------------------------ #
# the real-warren closed loop (live signals + live actuator)
# ------------------------------------------------------------------ #
def test_closed_loop_on_real_warren_split_resync_demote(tmp_path):
    """End to end on a live ShardedWarren: the controller (real
    WarrenSignals + WarrenActuator, fake clock) splits a hot group,
    resurrects a killed replica via anti-entropy, and demotes the
    collection once traffic stops — with served rankings bit-identical
    to a single index after every action."""
    from test_rebalance import QUERIES, _assert_search_parity, _ingest

    from repro.core import DynamicIndex, Warren
    from repro.dist.shard_router import ShardedWarren

    sharded = ShardedWarren(n_shards=2, replicas=2,
                            static_dir=str(tmp_path))
    single = Warren(DynamicIndex())
    _ingest(sharded, range(80))
    _ingest(single, range(80))

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
    ctl = Controller.for_warren(sharded, config=cfg, clock=clock)

    def serve():
        with sharded:
            for q in QUERIES:
                score_bm25(sharded, q, k=10)

    # phase 1 — traffic makes every group "hot" (p95 threshold 0); after
    # sustain_ticks the controller splits the largest group, then
    # max_groups caps further growth
    for _ in range(3):
        serve()
        ctl.tick()
        clock.advance()
    splits = [d for d in ctl.decisions if d.kind == "split"]
    assert [d.outcome for d in splits] == ["applied"]
    assert sharded.n_shards == 3 and sharded.routing.epoch == 1
    with sharded, single:
        _assert_search_parity(sharded, single)

    # phase 2 — kill a replica; anti-entropy re-syncs it (dead streak
    # reaches sustain_ticks) and the pair ends in address lockstep
    sharded.groups[0].mark_failed(1)
    for _ in range(4):
        serve()
        ctl.tick()
        clock.advance()
    resyncs = [d for d in ctl.decisions if d.kind == "resync"]
    assert resyncs and resyncs[0].outcome == "applied"
    assert (resyncs[0].group, resyncs[0].target) == (0, 1)
    grp = sharded.groups[0]
    assert all(grp.alive)
    assert grp.replicas[0]._next_addr == grp.replicas[1]._next_addr
    with sharded, single:
        _assert_search_parity(sharded, single)

    # phase 3 — traffic stops; the idle streak demotes a group to its
    # static run set and reads still serve, bit-identical
    for _ in range(4):
        ctl.tick()
        clock.advance()
    demotes = [d for d in ctl.decisions if d.kind == "demote"]
    assert demotes and demotes[0].outcome == "applied"
    assert any(d is not None for d in sharded.demoted())
    with sharded, single:
        _assert_search_parity(sharded, single)
    assert not any(d.outcome == "failed" for d in ctl.decisions)


def test_warren_signals_are_windowed(tmp_path):
    """WarrenSignals reports per-window deltas: reads/latency observed
    between two collects show up once, then reset."""
    from test_rebalance import QUERIES, _ingest

    from repro.dist.shard_router import ShardedWarren

    sharded = ShardedWarren(n_shards=2, replicas=1)
    _ingest(sharded, range(40))
    src = WarrenSignals(sharded)
    src.collect()                            # baseline snapshot
    with sharded:
        for q in QUERIES:
            score_bm25(sharded, q, k=5)
        total = len(sharded.annotations(":"))   # counts as reads too
    sigs = {s.group: s for s in src.collect()}
    assert sum(s.reads for s in sigs.values()) >= len(QUERIES)
    assert all(s.p95_ms == s.p95_ms for s in sigs.values())  # not NaN
    assert sum(s.docs for s in sigs.values()) == total == 40
    quiet = {s.group: s for s in src.collect()}   # nothing in this window
    assert all(s.reads == 0 for s in quiet.values())
    assert all(s.p95_ms != s.p95_ms for s in quiet.values())  # NaN again


# ------------------------------------------------------------------ #
# chaos: replica kills mid-controller-initiated split (stress marker)
# ------------------------------------------------------------------ #
@pytest.mark.stress
def test_chaos_kill_replicas_mid_controller_split_backoff_reconverge():
    """Kill every source replica mid-copy of a CONTROLLER-initiated
    split: the controller observes RebalanceAborted (table untouched),
    backs off, re-syncs the dead replica through anti-entropy once ops
    re-join the intact one, retries the split after the backoff expires,
    and converges — without ever wedging the rebalance lock."""
    from test_rebalance import _assert_search_parity, _ingest

    from repro.core import DynamicIndex, Warren
    from repro.dist.shard_router import ShardedWarren

    sharded = ShardedWarren(n_shards=2, replicas=2)
    single = Warren(DynamicIndex())
    _ingest(sharded, range(60))
    _ingest(single, range(60))
    table_before = sharded.routing.to_record()

    killed = []

    def kill_all(warren, stage, gid):
        if stage == "after_copy" and not killed:
            for r in range(warren.groups[gid].n_replicas):
                warren.groups[gid].mark_failed(r)
            killed.append(gid)

    sharded.hooks["mid_migration"] = kill_all

    clock = SimClock()
    cfg = AutopilotConfig(
        split=HotSplitPolicy(p95_hot_ms=0.0, sustain_ticks=1, min_docs=1,
                             max_groups=4),
        cold=ColdPolicy(demote_after_ticks=10 ** 6,
                        merge_after_ticks=10 ** 6),
        anti_entropy=AntiEntropyPolicy(max_seq_lag=0, sustain_ticks=1),
        hysteresis=Hysteresis(cooldown_ticks=0, min_dwell_ticks=0,
                              window_ticks=50, max_actions_per_window=50),
        retry=RetryPolicy(base_ticks=1, cap_ticks=4),
        pool=None)
    ctl = Controller.for_warren(sharded, config=cfg, clock=clock)

    def serve():
        from test_rebalance import QUERIES
        with sharded:
            for q in QUERIES:
                score_bm25(sharded, q, k=10)

    # tick 0: the controller's split hits the kill — aborted, no torn table
    serve()
    ctl.tick()
    clock.advance()
    assert killed, "hook never fired"
    g_src = killed[0]
    d0 = ctl.decisions[0]
    assert (d0.kind, d0.group, d0.outcome) == ("split", g_src, "aborted")
    assert sharded.routing.to_record() == table_before
    sharded.hooks.clear()
    # ops re-join the intact first replica (its index survived the kill);
    # the controller's anti-entropy handles the truly-dead sibling
    sharded.groups[g_src].alive[0] = True

    for _ in range(6):
        serve()
        ctl.tick()
        clock.advance()

    resyncs = [d for d in ctl.decisions
               if d.kind == "resync" and d.group == g_src]
    assert resyncs and resyncs[0].outcome == "applied"
    retried = [d for d in ctl.decisions
               if d.kind == "split" and d.group == g_src
               and d.outcome == "applied"]
    assert retried and retried[0].tick > d0.tick + 1   # after the backoff
    assert all(all(a) for a in sharded.health())
    # the rebalance lock is free — a manual operation acquires it cleanly
    lock = sharded._ctx["rebalance_lock"]
    assert lock.acquire(blocking=False)
    lock.release()
    with sharded, single:
        _assert_search_parity(sharded, single)
