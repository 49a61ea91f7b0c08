"""The live introspection plane: admin server, profiling, SLO burn.

Tier-1 here covers the introspection issue's acceptance criteria: every
admin endpoint answers against a *live* ShardedWarren while a rebalance
is in flight and writers keep committing (the admin plane never takes a
write lock), the sampling profiler returns non-empty collapsed stacks,
ProfiledLock records contention without changing lock semantics (RLock
reentrancy included), RotatingJsonl bounds its disk use, and the SLO
monitor's multi-window burn rates — computed on a fake clock,
deterministically — drive the autopilot's hot-split policy through
``HotSplitPolicy.burn_hot``.
"""

import json
import math
import threading
import time
import urllib.request

import pytest

from repro import obs
from repro.core import score_bm25
from repro.obs import (SLO, AdminServer, MetricsRegistry, ProfiledLock,
                       RotatingJsonl, SamplingProfiler, SLOMonitor,
                       SLOSignalSource)
from repro.dist.autopilot import (AutopilotConfig, ColdPolicy, Controller,
                                  HotSplitPolicy, Hysteresis)
from repro.dist.simharness import SimClock, SimCluster

from tests.test_rebalance import QUERIES, _ingest, _pair


@pytest.fixture(autouse=True)
def _clean_global_obs():
    obs.enable()
    obs.registry().reset()
    obs.tracer().reset()
    obs.tracer().set_slow_dump(None, None)
    yield
    obs.enable()
    obs.tracer().set_slow_dump(None, None)


# --------------------------------------------------------------------- #
# RotatingJsonl                                                         #
# --------------------------------------------------------------------- #

def test_rotating_jsonl_caps_disk_use(tmp_path):
    p = tmp_path / "log.jsonl"
    sink = RotatingJsonl(str(p), max_bytes=300, backups=2)
    for i in range(100):
        sink.write({"i": i, "pad": "x" * 40})
    files = sink.files()
    assert str(p) in files and len(files) == 3        # live + 2 backups
    import os
    total = sum(os.path.getsize(f) for f in files)
    assert total <= 3 * 300 + 100                      # bounded disk use
    # live file holds whole lines, newest records last
    last = [json.loads(line) for line in p.read_text().splitlines()]
    assert last[-1]["i"] == 99
    # an oversized single record still lands rather than being dropped
    sink.write({"huge": "y" * 1000})
    assert json.loads(p.read_text().splitlines()[-1])["huge"] == "y" * 1000


def test_rotating_jsonl_zero_backups(tmp_path):
    p = tmp_path / "log.jsonl"
    sink = RotatingJsonl(str(p), max_bytes=200, backups=0)
    for i in range(50):
        sink.write({"i": i})
    assert sink.files() == [str(p)]
    import os
    assert os.path.getsize(str(p)) <= 250


def test_controller_decision_log_rotates(tmp_path):
    clock = SimClock()
    cluster = SimCluster(docs=500)
    log = tmp_path / "decisions.jsonl"
    cfg = AutopilotConfig(
        split=HotSplitPolicy(p95_hot_ms=0.0, sustain_ticks=1, min_docs=1,
                             max_groups=64),
        cold=ColdPolicy(demote_after_ticks=10 ** 6,
                        merge_after_ticks=10 ** 6),
        hysteresis=Hysteresis(cooldown_ticks=0, min_dwell_ticks=0,
                              window_ticks=1, max_actions_per_window=10),
        pool=None)
    ctl = Controller(cluster, cluster, config=cfg, clock=clock,
                     decision_log=str(log))
    ctl._log_sink = RotatingJsonl(str(log), max_bytes=400, backups=1)
    for _ in range(60):
        cluster.route([0.01, 0.51])
        ctl.tick()
        clock.advance()
    assert ctl.decisions, "controller made no decisions"
    import os
    assert os.path.getsize(str(log)) <= 500
    # every line in the live log is a valid Decision record
    for line in log.read_text().splitlines():
        rec = json.loads(line)
        assert {"tick", "kind", "group", "outcome"} <= set(rec)


# --------------------------------------------------------------------- #
# ProfiledLock                                                          #
# --------------------------------------------------------------------- #

def test_profiled_lock_records_contention_only():
    lk = ProfiledLock("t_uncontended")
    with lk:
        pass
    h = obs.registry().histogram("lock_wait_ms", lock="t_uncontended")
    assert h.count == 0                     # fast path: no observation

    lk2 = ProfiledLock("t_contended")
    lk2.acquire()
    waited = threading.Event()

    def taker():
        with lk2:
            waited.set()

    t = threading.Thread(target=taker)
    t.start()
    time.sleep(0.02)
    lk2.release()
    t.join(timeout=5.0)
    assert waited.is_set()
    h2 = obs.registry().histogram("lock_wait_ms", lock="t_contended")
    assert h2.count == 1
    assert h2.percentile(0.5) >= 1.0        # waited >= the sleep, roughly
    c = obs.registry().counter("lock_contended_total", lock="t_contended")
    assert c.value == 1


def test_profiled_lock_rlock_reentrancy_and_protocol():
    lk = ProfiledLock("t_rlock", threading.RLock())
    with lk:
        with lk:                            # reentrant: must not deadlock
            assert lk.acquire(blocking=False)
            lk.release()
    assert lk.acquire(blocking=True, timeout=1.0)
    lk.release()
    # non-blocking failure path returns False without metrics explosions
    plain = ProfiledLock("t_plain")
    plain.acquire()
    hold = threading.Event()
    done = threading.Event()

    def other():
        assert not plain.acquire(blocking=False)
        done.set()

    threading.Thread(target=other).start()
    assert done.wait(timeout=5.0)
    plain.release()
    hold.set()


# --------------------------------------------------------------------- #
# SamplingProfiler                                                      #
# --------------------------------------------------------------------- #

def _spin(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        x += 1


def test_sampling_profiler_collapsed_stacks():
    stop = threading.Event()
    t = threading.Thread(target=_spin, args=(stop,), name="spinner")
    t.start()
    try:
        prof = SamplingProfiler(interval_s=0.002)
        prof.start()
        time.sleep(0.15)
        prof.stop()
    finally:
        stop.set()
        t.join()
    assert prof.samples > 0
    text = prof.collapsed()
    assert text, "no collapsed stacks collected"
    for line in text.splitlines():
        stack, _, count = line.rpartition(" ")
        assert stack and int(count) >= 1
    assert "_spin" in text                  # the busy thread is visible
    assert "spinner" in text                # tagged with its thread name


def test_profile_for_one_shot():
    out = obs.profile_for(0.05, interval_s=0.002)
    assert isinstance(out, str)


# --------------------------------------------------------------------- #
# SLO burn rates on a fake clock                                        #
# --------------------------------------------------------------------- #

def test_latency_slo_burn_multiwindow():
    reg = MetricsRegistry()
    clk = SimClock(step=1.0)
    slo = SLO(name="p95", kind="latency", objective=0.9,
              metric="lat_ms", threshold_ms=10.0)
    mon = SLOMonitor(slos=[slo], windows=(("short", 2.0), ("long", 6.0)),
                     reg=reg, clock=clk)
    h = reg.histogram("lat_ms", group=0)
    # healthy traffic: all good, burn 0 in every window
    for _ in range(4):
        for _ in range(10):
            h.observe(1.0)
        mon.tick()
        clk.advance()
    assert mon.burn("p95") == 0.0
    # sustained badness: every observation over threshold -> bad
    # fraction 1.0, burn = 1.0 / 0.1 = 10 in both windows
    for _ in range(8):
        for _ in range(10):
            h.observe(100.0)
        mon.tick()
        clk.advance()
    assert mon.burn("p95", "short") == pytest.approx(10.0)
    assert mon.burn("p95") == pytest.approx(10.0, rel=0.35)
    assert mon.group_burns("p95")["0"] > 1.0
    # the gauges were exported
    snap = reg.snapshot()["slo_burn_rate"]
    labels = {tuple(sorted(s["labels"].items())) for s in snap["series"]}
    assert (("slo", "p95"), ("window", "short")) in labels
    assert (("slo", "p95"), ("window", "long")) in labels


def test_latency_slo_short_window_recovers_first():
    reg = MetricsRegistry()
    clk = SimClock(step=1.0)
    slo = SLO(name="p95", kind="latency", objective=0.9,
              metric="lat_ms", threshold_ms=10.0)
    mon = SLOMonitor(slos=[slo], windows=(("short", 2.0), ("long", 8.0)),
                     reg=reg, clock=clk)
    h = reg.histogram("lat_ms")
    for _ in range(6):                       # bad spell
        h.observe(100.0)
        mon.tick()
        clk.advance()
    for _ in range(3):                       # recovery
        for _ in range(20):
            h.observe(1.0)
        mon.tick()
        clk.advance()
    short, long_ = mon.burn("p95", "short"), mon.burn("p95", "long")
    assert short < long_                     # short window forgets first
    assert mon.burn("p95") == short          # sustained = min across windows


def test_ratio_slo_burn():
    reg = MetricsRegistry()
    clk = SimClock(step=1.0)
    slo = SLO(name="commit", kind="ratio", objective=0.9,
              good_metric="ok_total", bad_metric="fail_total")
    mon = SLOMonitor(slos=[slo], windows=(("w", 4.0),), reg=reg, clock=clk)
    ok, fail = reg.counter("ok_total"), reg.counter("fail_total")
    mon.tick()
    clk.advance()
    ok.inc(90)
    fail.inc(10)                             # 10% bad = exactly at budget
    mon.tick()
    assert mon.burn("commit") == pytest.approx(1.0)
    ok.inc(100)                              # dilute: 10/200 bad
    mon.tick()
    assert mon.burn("commit") == pytest.approx(0.5)


def test_empty_window_burns_zero_and_nan_before_first_tick():
    reg = MetricsRegistry()
    mon = SLOMonitor(slos=[SLO(name="p", kind="latency", objective=0.99,
                               metric="nothing_ms", threshold_ms=1.0)],
                     reg=reg, clock=SimClock())
    assert math.isnan(mon.burn("p"))
    mon.tick()
    assert mon.burn("p") == 0.0              # no traffic is not an outage


def test_slo_signal_source_drives_burn_hot_split():
    clk = SimClock(step=1.0)
    cluster = SimCluster(docs=64, ms_per_doc=1.0, observe_latency=True)
    mon = SLOMonitor(
        slos=[SLO(name="serving_p95", kind="latency", objective=0.95,
                  metric="scatter_latency_ms", threshold_ms=20.0)],
        windows=(("short", 3.0), ("long", 9.0)), clock=clk)
    cfg = AutopilotConfig(
        # p95/skew triggers disabled: only burn can split
        split=HotSplitPolicy(p95_hot_ms=math.inf, skew_ratio=math.inf,
                             min_docs=8, sustain_ticks=2, max_groups=4,
                             burn_hot=1.0),
        pool=None)
    ctl = Controller(SLOSignalSource(cluster, mon), cluster,
                     config=cfg, clock=clk)
    for _ in range(10):
        cluster.route([0.1] * 20)
        ctl.tick()
        clk.advance()
    splits = [d for d in ctl.decisions
              if d.kind == "split" and d.outcome == "applied"]
    assert splits, "sustained burn did not trigger a split"
    assert "burn" in splits[0].reason
    assert len(cluster.active()) > 1


def test_slo_signal_source_rejects_unknown_slo():
    mon = SLOMonitor(reg=MetricsRegistry())
    with pytest.raises(ValueError, match="no SLO named"):
        SLOSignalSource(SimCluster(), mon, slo_name="nonsense")


# --------------------------------------------------------------------- #
# AdminServer                                                           #
# --------------------------------------------------------------------- #

def _get(url, timeout=10.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_admin_endpoints_and_scrapes_mid_rebalance(tmp_path):
    from repro.dist.rebalance import Rebalancer

    sharded, _ = _pair(n_docs=140)
    clock = SimClock()
    ctl = Controller.for_warren(
        sharded, config=AutopilotConfig(pool=None), clock=clock)
    mon = SLOMonitor()
    with sharded:
        score_bm25(sharded, QUERIES[0], k=5)  # seed a trace + latency metrics
    ctl.tick()
    mon.tick()

    with AdminServer(warren=sharded, controller=ctl, slo=mon) as srv:
        # -- every endpoint answers -------------------------------------- #
        code, body = _get(srv.url("/healthz"))
        assert code == 200 and json.loads(body)["ok"] is True
        code, body = _get(srv.url("/readyz"))
        assert code == 200 and json.loads(body)["ready"] is True
        code, body = _get(srv.url("/metrics"))
        assert code == 200 and "# TYPE" in body
        assert "scatter_latency_ms_bucket" in body
        code, body = _get(srv.url("/metrics.json"))
        assert code == 200 and "scatter_latency_ms" in json.loads(
            body)["metrics"]
        code, body = _get(srv.url("/routing"))
        routing = json.loads(body)
        assert code == 200 and routing["n_groups"] == sharded.n_shards
        for g in routing["groups"].values():
            assert g["alive"] and g["ranges"]
        code, body = _get(srv.url("/autopilot/decisions?n=5"))
        assert code == 200 and "decisions" in json.loads(body)
        code, body = _get(srv.url("/slo"))
        assert code == 200
        names = [s["name"] for s in json.loads(body)["slos"]]
        assert "serving_p95" in names
        code, body = _get(srv.url("/tiered/runs"))
        assert code == 200 and "demoted_groups" in json.loads(body)
        code, body = _get(srv.url("/traces"))
        traces = json.loads(body)["traces"]
        assert code == 200 and traces
        tid = traces[-1]["trace_id"]
        code, body = _get(srv.url(f"/traces/{tid}"))
        assert code == 200 and json.loads(body)["tree"]["name"]
        # error paths stay JSON
        assert _get(srv.url("/traces/notanid"))[0] == 400
        assert _get(srv.url("/traces/999999999"))[0] == 404
        assert _get(srv.url("/nonsense"))[0] == 404
        code, body = _get(srv.url("/profile/cpu?seconds=0.05"))
        assert code == 200

        # -- scrape storm while a split runs and writers commit ----------- #
        errors = []
        stop = threading.Event()

        def scraper():
            paths = ["/metrics", "/routing", "/traces", "/healthz",
                     "/autopilot/decisions", "/slo"]
            i = 0
            while not stop.is_set():
                c, _ = _get(srv.url(paths[i % len(paths)]))
                if c != 200:
                    errors.append((paths[i % len(paths)], c))
                i += 1

        def writer():
            try:
                _ingest(sharded, range(1000, 1040), batch=8)
            except Exception as e:          # pragma: no cover
                errors.append(("writer", repr(e)))

        threads = [threading.Thread(target=scraper) for _ in range(3)]
        wt = threading.Thread(target=writer)
        for t in threads:
            t.start()
        wt.start()
        new_gid = Rebalancer(sharded).split_group(0)
        wt.join(timeout=60.0)
        assert not wt.is_alive(), "writer blocked during scraped rebalance"
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        assert not errors, f"admin-plane failures: {errors[:5]}"

        # post-split routing reflects the new epoch and group
        code, body = _get(srv.url("/routing"))
        routing = json.loads(body)
        assert str(new_gid) in routing["groups"]
        assert routing["epoch"] >= 1
    sharded.close()


def test_admin_server_without_attachments():
    with AdminServer() as srv:
        assert _get(srv.url("/healthz"))[0] == 200
        code, body = _get(srv.url("/readyz"))
        assert code == 200 and json.loads(body)["warren"] is None
        assert _get(srv.url("/routing"))[0] == 404
        assert _get(srv.url("/autopilot/decisions"))[0] == 404
        assert _get(srv.url("/tiered/runs"))[0] == 404
        assert _get(srv.url("/slo"))[0] == 404


def test_admin_tiered_runs_with_store(tmp_path):
    from repro.core import index_document
    from repro.tiered.store import TieredStore

    store = TieredStore(str(tmp_path))
    with store.warren() as w:
        w.transaction()
        index_document(w, "school education student", docid="t0")
        w.commit()
    info = store.freeze()
    assert info is not None
    with AdminServer(tiered=store) as srv:
        code, body = _get(srv.url("/tiered/runs"))
        doc = json.loads(body)
        assert code == 200
        assert doc["n_runs"] == 1
        assert doc["runs"][0]["n_records"] > 0
        assert doc["manifest"]["frozen_upto"] >= 0
    store.close()


def test_registry_series_view_concurrent_with_scrape():
    reg = obs.registry()
    stop = threading.Event()
    errs = []

    def churn():
        i = 0
        while not stop.is_set():
            reg.histogram("churn_ms", group=i % 50).observe(float(i % 90))
            i += 1

    def scrape():
        try:
            while not stop.is_set():
                text = reg.to_prometheus()
                assert "churn_ms" in text or text is not None
                reg.series("churn_ms")
        except Exception as e:              # pragma: no cover
            errs.append(repr(e))

    ts = [threading.Thread(target=churn) for _ in range(4)] + \
         [threading.Thread(target=scrape) for _ in range(2)]
    for t in ts:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in ts:
        t.join(timeout=10.0)
    assert not errs
