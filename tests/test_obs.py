"""repro.obs: metric primitives, registry, tracing.

Covers the concurrency contract (16-thread hammers with exact totals),
trace-context propagation across the ScatterGather pool, the span trees
of a search through the native sharded server and of a quorum commit,
the per-stage metrics they feed (``span_ms``, the batcher's queue wait,
host-to-device bytes), disabled-mode no-ops, and ScatterTimings
windowing."""

import json
import math
import threading
from collections import Counter as Tally

import pytest

from repro import obs
from repro.obs import (Counter, Gauge, Histogram, JsonlSink, MetricsRegistry,
                       Tracer, sanitize)


@pytest.fixture(autouse=True)
def _clean_global_obs():
    """Tests share the process-global registry/tracer: start clean,
    leave enabled for whoever runs next."""
    obs.enable()
    obs.registry().reset()
    obs.tracer().reset()
    obs.tracer().set_slow_dump(None, None)
    yield
    obs.enable()
    obs.tracer().set_slow_dump(None, None)


# --------------------------------------------------------------------- #
# primitives                                                            #
# --------------------------------------------------------------------- #

def test_histogram_percentiles_uniform():
    h = Histogram()
    for v in range(1, 1001):
        h.observe(float(v))
    # log buckets at 20/decade => ~12% relative resolution
    assert h.percentile(0.5) == pytest.approx(500, rel=0.15)
    assert h.percentile(0.95) == pytest.approx(950, rel=0.15)
    assert h.percentile(0.99) == pytest.approx(990, rel=0.15)
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["min"] == 1.0 and snap["max"] == 1000.0
    assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]


def test_histogram_empty_and_clamping():
    h = Histogram()
    assert math.isnan(h.percentile(0.5))
    h.observe(7.0)
    # single sample: every percentile must clamp to the one observation
    assert h.percentile(0.5) == 7.0
    assert h.percentile(0.99) == 7.0
    h.observe(0.0)       # underflow bucket (v <= lo)
    assert h.count == 2
    h.reset()
    assert h.count == 0 and math.isnan(h.percentile(0.5))


def test_counter_hammer_16_threads():
    c = Counter()
    n, per = 16, 5000

    def worker():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n * per


def test_histogram_hammer_16_threads():
    h = Histogram()
    n, per = 16, 2000

    def worker(tid):
        for i in range(per):
            h.observe(1.0 + (tid * per + i) % 100)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = h.snapshot()
    assert snap["count"] == n * per
    assert snap["min"] >= 1.0 and snap["max"] <= 100.0


def test_registry_get_or_create_hammer():
    reg = MetricsRegistry()
    n, per = 16, 1000

    def worker(tid):
        for _ in range(per):
            # same (name, labels) from every thread -> one series
            reg.counter("hammer_total", group=tid % 4).inc()

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = reg.snapshot()["hammer_total"]
    assert len(snap["series"]) == 4
    assert sum(s["value"] for s in snap["series"]) == n * per


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x_total").inc()
    with pytest.raises(ValueError, match="counter"):
        reg.gauge("x_total")


def test_disabled_mode_is_a_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")
    h = reg.histogram("h")
    g = reg.gauge("g")
    c.inc(10)
    h.observe(5.0)
    g.set(3.0)
    assert c.value == 0 and h.count == 0 and g.value == 0.0
    reg.enable()
    c.inc()
    assert c.value == 1


def test_prometheus_and_jsonl_exports(tmp_path):
    reg = MetricsRegistry()
    reg.counter("reads_total", "reads", group=0).inc(3)
    reg.histogram("lat_ms", "latency", site="s").observe(2.5)
    reg.gauge("depth").set(float("nan"))     # must not break JSON export
    text = reg.to_prometheus()
    assert 'reads_total{group="0"} 3' in text
    assert 'lat_ms_bucket{le="+Inf",site="s"} 1' in text
    assert 'lat_ms_sum{site="s"} 2.5' in text
    assert 'lat_ms_count{site="s"} 1' in text
    p = tmp_path / "m.jsonl"
    rec = JsonlSink(str(p)).write(reg)
    parsed = json.loads(p.read_text())       # strictly valid JSON
    assert parsed["metrics"]["reads_total"]["series"][0]["value"] == 3
    assert parsed["metrics"]["depth"]["series"][0]["value"] is None
    assert rec["metrics"]["lat_ms"]["series"][0]["count"] == 1


def test_sanitize_nonfinite():
    assert sanitize({"a": float("inf"), "b": [float("nan"), 1.5]}) == \
        {"a": None, "b": [None, 1.5]}


# --------------------------------------------------------------------- #
# tracing                                                               #
# --------------------------------------------------------------------- #

def test_span_nesting_and_tree():
    tr = Tracer()
    with tr.span("root", req=1):
        with tr.span("child_a"):
            with tr.span("leaf"):
                pass
        with tr.span("child_b"):
            pass
    t = tr.last_trace("root")
    assert t is not None
    tree = t.tree()
    assert tree["name"] == "root" and tree["labels"] == {"req": 1}
    assert [c["name"] for c in tree["children"]] == ["child_a", "child_b"]
    assert tree["children"][0]["children"][0]["name"] == "leaf"
    assert tree["duration_ms"] is not None and tree["duration_ms"] >= 0


def test_span_error_flag_propagates_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("root"):
            with tr.span("boom"):
                raise RuntimeError("x")
    tree = tr.last_trace("root").tree()
    assert tree["error"] is True
    assert tree["children"][0]["error"] is True


def test_disabled_tracer_returns_shared_null():
    tr = Tracer(enabled=False)
    a, b = tr.span("x"), tr.span("y", k=1)
    assert a is b                      # shared no-op, no allocation
    with a:
        pass
    assert tr.traces() == []


def test_trace_propagation_across_scattergather():
    from repro.dist.parallel import ScatterGather
    tr = obs.tracer()
    with ScatterGather(workers=4) as sg:
        with obs.span("fanout.root"):
            sg.map(_traced_work, list(range(6)))
    t = tr.last_trace("fanout.root")
    assert t is not None
    tree = t.tree()
    kids = [c for c in tree["children"] if c["name"] == "work"]
    # every worker-side span parented under the submitting context's root
    assert sorted(c["labels"]["group"] for c in kids) == list(range(6))


def _traced_work(g):
    with obs.span("work", group=g):
        return g


def test_slow_trace_dump(tmp_path):
    tr = Tracer()
    p = tmp_path / "slow.jsonl"
    tr.set_slow_dump(0.0, str(p))          # everything is "slow"
    with tr.span("req"):
        with tr.span("inner"):
            pass
    assert tr.n_slow_dumped == 1
    rec = json.loads(p.read_text())
    assert rec["root"] == "req"
    assert [s["name"] for s in rec["spans"]] == ["req", "inner"]


# --------------------------------------------------------------------- #
# ScatterTimings windowing (the lifetime-average fix)                   #
# --------------------------------------------------------------------- #

def test_scatter_timings_window_and_epoch():
    from repro.dist.parallel import ScatterTimings
    st = ScatterTimings()
    st.add(scatter=0.010, score=0.020, merge=0.001)
    st.add(scatter=0.030, score=0.040, merge=0.002, queries=2)
    w = st.window()
    assert w["epoch"] == 0
    assert w["queries"] == 3
    assert w["scatter_s"] == pytest.approx(0.040)
    # window() reset the sums: a fresh window sees only new samples
    st.add(scatter=0.005)
    s = st.snapshot()
    assert s["epoch"] == 1
    assert s["queries"] == 1 and s["scatter_s"] == pytest.approx(0.005)
    # ...and the summary reads the current window alone
    assert st.summary().startswith("1 queries — scatter 5.00 ")


# --------------------------------------------------------------------- #
# instrumented subsystems                                               #
# --------------------------------------------------------------------- #

def test_txn_commit_metrics():
    from repro.core import DynamicIndex, Warren, index_document
    reg = obs.registry()
    with Warren(DynamicIndex()) as w:
        for i in range(3):
            w.transaction()
            index_document(w, f"doc number {i} words here", docid=f"d{i}")
            w.commit()
    h = reg.histogram("txn_commit_latency_ms")
    assert h.count >= 3
    assert h.sum > 0


def test_sharded_span_tree_and_metrics(tmp_path):
    """Acceptance: one search through the native sharded server yields the
    complete span tree and populates the serving metric families."""
    from repro.core import index_document
    from repro.dist.shard_router import ShardedWarren
    from repro.train.serve import RetrievalServer

    reg, tr = obs.registry(), obs.tracer()
    warren = ShardedWarren(n_shards=3, replicas=1,
                           static_dir=str(tmp_path), async_scatter=True)
    try:
        with warren:
            warren.transaction()
            for i in range(40):
                index_document(
                    warren,
                    f"school education student wind conductor item{i}",
                    docid=f"d{i}")
            warren.commit()
        server = RetrievalServer(warren, k=5)
        try:
            out = server.batcher.submit("school education").get(timeout=60)
        finally:
            server.close()
        assert len(out) > 0
    finally:
        warren.close()

    t = tr.last_trace("serve.batch")
    assert t is not None, "no serve.batch trace captured"
    names = set(t.names())
    assert {"serve.batch", "scatter", "replica_read",
            "device_score", "merge"} <= names
    tree = t.tree()
    scatters = [c for c in tree["children"] if c["name"] == "scatter"]
    assert sorted(s["labels"]["group"] for s in scatters) == [0, 1, 2]
    for s in scatters:
        assert any(k["name"] == "replica_read" for k in s["children"])

    # metric families the sweep must have fed
    snap = reg.snapshot()
    for fam in ("span_ms", "serve_queue_wait_ms", "serve_h2d_bytes",
                "kernel_phase_ms", "scatter_latency_ms",
                "shard_read_total", "shard_write_total",
                "serve_batch_size", "serve_jit_recompile_total"):
        assert fam in snap, f"missing family {fam}"
        assert snap[fam]["series"], f"empty family {fam}"
    assert reg.histogram("span_ms", span="serve.batch").count >= 1
    assert reg.histogram("span_ms", span="txn.ready").count >= 1


def test_obs_disable_silences_instrumentation(tmp_path):
    from repro.core import DynamicIndex, Warren, index_document
    obs.disable()
    before = obs.registry().histogram("txn_commit_latency_ms").count
    with Warren(DynamicIndex()) as w:
        w.transaction()
        index_document(w, "quiet doc", docid="q0")
        w.commit()
    assert obs.registry().histogram("txn_commit_latency_ms").count == before
    assert obs.tracer().span("x") is obs.tracer().span("y")


# --------------------------------------------------------------------- #
# per-stage spans and metrics: span_ms, queue wait, h2d bytes, commits  #
# --------------------------------------------------------------------- #

STAGE_DOCS = 24


def _sharded_warren(tmp_path, replicas=1):
    """Three groups, every one holding documents: one transaction per
    document, so the append routing spreads them."""
    from repro.core import index_document
    from repro.dist.shard_router import ShardedWarren
    warren = ShardedWarren(n_shards=3, replicas=replicas,
                           static_dir=str(tmp_path), async_scatter=True)
    for i in range(STAGE_DOCS):
        with warren:
            warren.transaction()
            index_document(warren, f"school education wind item{i} w{i % 5}",
                           docid=f"d{i}")
            warren.commit()
    return warren


def _search(warren, text="school education"):
    from repro.train.serve import RetrievalServer
    server = RetrievalServer(warren, k=5)
    try:
        return server.query(text, timeout=60)
    finally:
        server.close()


def _one_doc_per_group(warren, groups):
    from repro.dist.shard_router import shard_of
    with warren:
        docs = warren.annotations(":")
    picks = {}
    for i in range(len(docs)):
        g = shard_of(int(docs.starts[i]))
        if g in groups:
            picks.setdefault(g, (int(docs.starts[i]), int(docs.ends[i])))
    assert sorted(picks) == sorted(groups)
    return [picks[g] for g in sorted(picks)]


def _tag(warren, picks):
    with warren:
        warren.transaction()
        for p, q in picks:
            warren.annotate("tag:", p, q, 1.0)
        warren.commit()


def _span_counts():
    return {labels["span"]: m.count
            for labels, m in obs.registry().series("span_ms") if m.count}


def test_span_ms_observes_every_closed_span():
    tr = Tracer()
    with pytest.raises(KeyError):
        with tr.span("root"):
            for _ in range(2):
                with tr.span("child"):
                    pass
            with tr.span("boom"):
                raise KeyError("x")
    assert _span_counts() == {"root": 1, "child": 2, "boom": 1}
    root = tr.last_trace("root").root
    assert obs.registry().histogram("span_ms", span="root").sum == \
        pytest.approx(root.duration_ms)
    obs.registry().disable()             # tracer on, registry off
    with tr.span("root"):
        pass
    assert obs.registry().histogram("span_ms", span="root").count == 1


def test_sharded_search_stage_spans_and_phases(tmp_path):
    warren = _sharded_warren(tmp_path)
    try:
        obs.registry().reset()
        obs.tracer().reset()
        assert _search(warren)
    finally:
        warren.close()
    t = obs.tracer().last_trace("serve.batch")
    tree = t.tree()
    kids = [c["name"] for c in tree["children"]]
    assert {"device_score", "merge"} <= set(kids)
    scatters = [c for c in tree["children"] if c["name"] == "scatter"]
    assert sorted(c["labels"]["group"] for c in scatters) == [0, 1, 2]
    for c in scatters:           # the stats read runs on the replica
        (read,) = c["children"]
        assert read["name"] == "replica_read"
        assert [k["name"] for k in read["children"]] == ["scatter.stats"]
    # one span_ms observation per span of the trace, and no other
    assert _span_counts() == dict(Tally(t.names()))
    phases = {labels["phase"]: m.count
              for labels, m in obs.registry().series("kernel_phase_ms")
              if labels["kernel"] == "bm25_topk"}
    assert phases["impacts"] == 1 and phases["compute"] == 1
    assert phases["gather"] == 3 and phases["dispatch"] == 3


def test_queue_wait_of_a_lone_request_is_the_coalescing_wait():
    from repro.train.serve import BatcherConfig, MicroBatcher
    b = MicroBatcher(lambda reqs: reqs,
                     BatcherConfig(max_batch=8, max_wait_ms=50))
    try:
        assert b.submit("a").get(timeout=5) == "a"
    finally:
        b.close()
    h = obs.registry().histogram("serve_queue_wait_ms")
    assert h.count == 1 and h.sum >= 45.0


def test_queue_wait_of_a_full_batch_is_near_zero():
    from repro.train.serve import BatcherConfig, MicroBatcher
    b = MicroBatcher(lambda reqs: reqs,
                     BatcherConfig(max_batch=4, max_wait_ms=2000))
    try:
        handles = [b.submit(i) for i in range(4)]
        assert [h.get(timeout=5) for h in handles] == [0, 1, 2, 3]
    finally:
        b.close()
    h = obs.registry().histogram("serve_queue_wait_ms")
    # launched as soon as it was full, long before the 2 s deadline
    assert h.count == 4 and h.snapshot()["max"] < 100.0


def test_h2d_bytes_are_the_packed_arrays(tmp_path, monkeypatch):
    from repro.train import serve
    sent = []
    scorer = serve.bm25_topk

    def spy(doc_idx, impacts, qmask, **kw):
        sent.append(doc_idx.nbytes + impacts.nbytes + qmask.nbytes)
        return scorer(doc_idx, impacts, qmask, **kw)

    monkeypatch.setattr(serve, "bm25_topk", spy)
    warren = _sharded_warren(tmp_path)
    try:
        obs.registry().reset()
        assert _search(warren)
    finally:
        warren.close()
    h = obs.registry().histogram("serve_h2d_bytes")
    assert len(sent) == 3
    assert h.count == 1 and h.sum == sum(sent)


def test_two_group_commit_span_tree(tmp_path):
    warren = _sharded_warren(tmp_path, replicas=2)
    try:
        picks = _one_doc_per_group(warren, (0, 1))
        obs.registry().reset()
        obs.tracer().reset()
        _tag(warren, picks)
        with warren:
            assert len(warren.annotations("tag:")) == 2
    finally:
        warren.close()
    tree = obs.tracer().last_trace("txn.commit").tree()
    assert [c["name"] for c in tree["children"]] == ["txn.ready",
                                                     "txn.publish"]
    assert not tree["error"]
    counts = _span_counts()
    assert [counts.get(n) for n in ("txn.commit", "txn.ready",
                                    "txn.publish")] == [1, 1, 1]


def test_quorum_abort_closes_ready_with_error_and_no_publish(tmp_path):
    from repro.dist.shard_router import QuorumError
    warren = _sharded_warren(tmp_path, replicas=2)
    try:
        picks = _one_doc_per_group(warren, (0, 1))
        warren.mark_failed(0, 0)             # group 0: 1/2 < quorum
        obs.registry().reset()
        obs.tracer().reset()
        with pytest.raises(QuorumError):
            _tag(warren, picks)
    finally:
        warren.close()
    tree = obs.tracer().last_trace("txn.commit").tree()
    assert tree["error"] is True
    assert [c["name"] for c in tree["children"]] == ["txn.ready"]
    assert tree["children"][0]["error"] is True
    assert tree["children"][0]["labels"]["error"] == "QuorumError"
    counts = _span_counts()
    assert counts.get("txn.ready") == 1 and "txn.publish" not in counts


def test_obs_disable_records_no_stage_metrics(tmp_path):
    warren = _sharded_warren(tmp_path, replicas=2)
    try:
        picks = _one_doc_per_group(warren, (0, 1))
        obs.registry().reset()
        obs.tracer().reset()
        obs.disable()
        assert _search(warren)
        _tag(warren, picks)
    finally:
        warren.close()
    reg = obs.registry()
    for fam in ("span_ms", "serve_queue_wait_ms", "serve_h2d_bytes",
                "kernel_phase_ms"):
        assert all(m.count == 0 for _, m in reg.series(fam)), fam
    assert obs.tracer().traces() == []
    assert obs.span("txn.commit") is obs.span("scatter.stats", group=1)


# --------------------------------------------------------------------- #
# Prometheus exposition conformance (format 0.0.4)                      #
# --------------------------------------------------------------------- #

def _parse_prometheus(text):
    """Minimal 0.0.4 parser: {(name, frozenset(labels)): value}."""
    out = {}
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            continue
        metric, value = line.rsplit(" ", 1)
        if "{" in metric:
            name, rest = metric.split("{", 1)
            body = rest[:-1]
            labels = {}
            for part in body.split('",'):
                k, v = part.split("=", 1)
                labels[k] = v.strip('"')
            key = (name, frozenset(labels.items()))
        else:
            key = (metric, frozenset())
        out[key] = float(value) if value != "NaN" else math.nan
    return out


def test_prometheus_histogram_conformance():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", "latency", site="a")
    for v in (0.5, 2.0, 2.0, 40.0, 1e9):     # includes an overflow sample
        h.observe(v)
    reg.histogram("lat_ms", "latency", site="b").observe(1.0)
    text = reg.to_prometheus()
    assert "# TYPE lat_ms histogram" in text

    # per-series: ascending le, non-decreasing cumulative counts,
    # terminal +Inf bucket equal to _count
    for site, count in (("a", 5), ("b", 1)):
        bounds, cums = [], []
        for line in text.split("\n"):
            if line.startswith("lat_ms_bucket") and f'site="{site}"' in line:
                metric, value = line.rsplit(" ", 1)
                le = metric.split('le="')[1].split('"')[0]
                bounds.append(math.inf if le == "+Inf" else float(le))
                cums.append(int(value))
        assert bounds == sorted(bounds)
        assert cums == sorted(cums)
        assert bounds[-1] == math.inf
        assert cums[-1] == count
        parsed = _parse_prometheus(text)
        assert parsed[("lat_ms_count",
                       frozenset({("site", site)}))] == count
    a_sum = _parse_prometheus(text)[("lat_ms_sum",
                                     frozenset({("site", "a")}))]
    assert a_sum == pytest.approx(0.5 + 2.0 + 2.0 + 40.0 + 1e9)


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("odd_total", "odd", path='a"b\\c\nd').inc()
    text = reg.to_prometheus()
    assert 'path="a\\"b\\\\c\\nd"' in text


# --------------------------------------------------------------------- #
# tracer hygiene: exception exits                                       #
# --------------------------------------------------------------------- #

def test_span_exception_sets_error_label_and_dumps(tmp_path):
    tr = Tracer()
    p = tmp_path / "slow.jsonl"
    tr.set_slow_dump(1e9, str(p))       # nothing is slow ...
    with pytest.raises(KeyError):
        with tr.span("req"):
            with tr.span("inner"):
                raise KeyError("boom")
    t = tr.last_trace("req")
    spans = {s.name: s for s in t.spans}
    assert spans["inner"].error and spans["req"].error
    assert spans["inner"].labels["error"] == "KeyError"
    assert t.duration_ms is not None    # trace still finished
    # ... but an errored trace is always dump-eligible
    assert tr.n_slow_dumped == 1
    rec = json.loads(p.read_text())
    assert rec["root"] == "req"


def test_span_error_label_does_not_clobber_user_label():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("req", error="custom"):
            raise ValueError("x")
    s = tr.last_trace("req").root
    assert s.error is True
    assert s.labels["error"] == "custom"     # setdefault semantics
