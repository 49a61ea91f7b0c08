"""Tiered storage engine: equivalence with a single DynamicIndex, manifest
crash recovery, non-blocking compaction, auto-merge policy, cold-shard
demotion, and merged hot+cold serving.

The property test drives identical random interleaved append / annotate /
erase / commit / abort sequences into a ``TieredWarren`` (with forced
mid-sequence freezes and run compactions) and a plain single-index
``Warren``; because both sides allocate addresses from one sequential hot
index, every feature's annotation list, every ``translate``, and the BM25
top-10 must be *bit-identical*.
"""

import os
import tempfile
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DynamicIndex, Warren, index_document, score_bm25,
                        write_static)
from repro.tiered import (Compactor, Manifest, ManifestStore, TieredStore,
                          demote_index, resurrect_index)

VOCAB = ["school", "education", "student", "government", "law", "state",
         "stock", "money", "business", "vibration", "conductor", "wind"]


def _doc_text(n: int) -> str:
    words = [VOCAB[(n * 7 + i * (1 + n % 5)) % len(VOCAB)]
             for i in range(3 + n % 6)]
    return " ".join(words)


# ------------------------------------------------------------------ #
# the op interpreter: one logical op stream, either warren
# ------------------------------------------------------------------ #
def _apply_ops(warren, ops, store=None):
    """Apply the op stream; freeze/compact ops act only when ``store`` is
    given (the tiered side) but flush the staged batch on both sides so
    the two op streams stay transaction-aligned.  Returns committed doc
    extents (identical between sides by sequential address allocation)."""
    docs, staged = [], []

    def flush(commit: bool):
        nonlocal staged
        batch, staged = staged, []
        if not batch:
            return
        with warren:
            warren.transaction()
            spans = []
            for op in batch:
                kind, a, b, c = op
                if kind == "append":
                    spans.append(index_document(warren, _doc_text(a),
                                                docid=f"d{a}"))
                elif kind == "annotate" and docs:
                    lo, hi = docs[a % len(docs)]
                    warren.annotate(f"tag{b % 4}:", lo, hi, float(c))
                elif kind == "erase" and docs:
                    lo, hi = docs[a % len(docs)]
                    warren.erase(lo, hi)
            if commit:
                remap = warren.commit()
                docs.extend((remap(lo), remap(hi)) for lo, hi in spans)
            else:
                warren.abort()

    for op in ops:
        kind = op[0]
        if kind == "commit":
            flush(True)
        elif kind == "abort":
            flush(False)
        elif kind == "freeze":
            flush(True)
            if store is not None:
                store.freeze()
        elif kind == "compact":
            flush(True)
            if store is not None:
                store.compact_runs()
        else:
            staged.append(op)
    flush(True)
    return docs


_OPS = st.lists(
    st.tuples(st.sampled_from(["append", "append", "append", "annotate",
                               "erase", "commit", "abort", "freeze",
                               "compact"]),
              st.integers(0, 30), st.integers(0, 10), st.integers(0, 100)),
    min_size=4, max_size=36)


@settings(max_examples=12, deadline=None)
@given(_OPS)
def test_tiered_equivalence_property(ops):
    ref = Warren(DynamicIndex())
    with tempfile.TemporaryDirectory() as td:
        store = TieredStore(td + "/t", auto_merge_threshold=3)
        tw = store.warren()
        docs_t = _apply_ops(tw, ops, store=store)
        docs_r = _apply_ops(ref, ops, store=None)
        assert docs_t == docs_r            # identical address layout

        features = ([":", "dl:"] + [f"tag{i}:" for i in range(4)]
                    + [f"docid:d{i}" for i in range(31)]
                    + VOCAB)
        with tw, ref:
            for f in features:
                assert tw.annotations(f) == ref.annotations(f), f
            for lo, hi in docs_r:
                assert tw.translate(lo, hi) == ref.translate(lo, hi)
                assert tw.tokens(lo, hi) == ref.tokens(lo, hi)
            q = " ".join(VOCAB[:4])
            assert score_bm25(tw, q, k=10) == score_bm25(ref, q, k=10)
        store.close()


# ------------------------------------------------------------------ #
# manifest crash recovery
# ------------------------------------------------------------------ #
def _build(store, n=12, per_txn=4):
    w = store.warren()
    for i in range(0, n, per_txn):
        with w:
            w.transaction()
            for j in range(i, min(i + per_txn, n)):
                index_document(w, _doc_text(j), docid=f"d{j}")
            w.commit()
    return w


def test_crash_between_run_write_and_manifest_swap(tmp_path):
    """The run lands on disk but the manifest swap never happens: recovery
    serves everything from the WAL (latest-good manifest) and GCs the
    orphaned — potentially torn — run directory."""
    d = str(tmp_path / "t")
    store = TieredStore(d)
    _build(store, n=10)
    boom = RuntimeError("simulated crash before manifest publish")

    def crash(_m):
        raise boom
    store.manifests.publish = crash
    with pytest.raises(RuntimeError):
        store.freeze()
    store.close()

    runs_dir = os.path.join(d, "runs")
    assert os.listdir(runs_dir)            # the orphan run is on disk

    store2 = TieredStore(d)
    assert store2.n_runs == 0              # latest-good manifest: no runs
    assert os.listdir(runs_dir) == []      # orphan GC'd, no torn runs live
    w = store2.warren()
    with w:
        assert len(w.annotations(":")) == 10
        assert len(w.annotations("docid:d7")) == 1
    store2.close()


def test_torn_manifest_falls_back_to_latest_good(tmp_path):
    d = str(tmp_path / "t")
    store = TieredStore(d)
    _build(store, n=8)
    store.freeze()
    good_version = store.manifest.version
    store.close()
    # a torn (half-written) higher manifest version from a crash
    with open(os.path.join(d, f"MANIFEST-{good_version + 1:08d}.json"),
              "w") as fh:
        fh.write('{"crc": 1, "manifest": {"version": ')
    store2 = TieredStore(d)
    assert store2.manifest.version == good_version
    w = store2.warren()
    with w:
        assert len(w.annotations(":")) == 8
    store2.close()


def test_crash_after_manifest_before_wal_compaction(tmp_path):
    """Manifest published, hot tier detached, but the WAL still holds the
    frozen segments: reopening must not double-count them."""
    d = str(tmp_path / "t")
    store = TieredStore(d)
    _build(store, n=9)

    orig = store.hot.compact_log

    def crash():
        if store.manifest.frozen_upto >= 0:   # only the post-swap call
            raise RuntimeError("simulated crash before WAL compaction")
        orig()
    store.hot.compact_log = crash
    with pytest.raises(RuntimeError):
        store.freeze()
    assert store.manifest.frozen_upto >= 0
    store.hot._log.close()

    store2 = TieredStore(d)
    assert store2.n_runs == 1
    w = store2.warren()
    with w:
        assert len(w.annotations(":")) == 9          # not 18
        assert len(w.annotations("docid:d3")) == 1
    store2.close()


def test_freeze_never_strands_a_pending_lower_seq_txn(tmp_path):
    """A readied-but-uncommitted transaction sits below later commits in
    seqnum order; a freeze must not advance frozen_upto past it, or its
    eventual commit would be discarded as "already frozen" on reopen."""
    d = str(tmp_path / "t")
    store = TieredStore(d)
    w = _build(store, n=4)
    pending = store.hot.transaction()
    pending.append("pendingalpha limbo tokens")
    pending.ready()                          # durable phase 1, no commit
    with w:
        w.transaction()
        index_document(w, _doc_text(99), docid="d99")   # higher seqnum
        w.commit()
    store.freeze()
    assert store.manifest.frozen_upto < pending._segment.seqnum
    pending.commit()                         # acknowledged-committed
    store.close()

    store2 = TieredStore(d)
    w2 = store2.warren()
    with w2:
        assert len(w2.annotations("pendingalpha")) == 1
        assert len(w2.annotations("docid:d99")) == 1
        assert len(w2.annotations(":")) == 5
    store2.close()


def test_commit_racing_a_group_demotion_is_not_lost(tmp_path):
    """A transaction staged before its group is demoted must survive: the
    quorum commit promotes the group back instead of publishing onto the
    wiped replicas of a cold group."""
    from repro.dist.shard_router import ShardedWarren

    w = ShardedWarren(n_shards=1, replicas=2, static_dir=str(tmp_path))
    with w:
        w.transaction()
        for i in range(4):
            index_document(w, _doc_text(i), docid=f"d{i}")
        w.commit()

    writer = w.clone()
    writer.start()
    writer.transaction()
    index_document(writer, "late racing document", docid="dlate")
    w.demote_group(0)                        # demotion wins the race
    assert w.demoted()[0] is not None
    writer.commit()                          # must promote, then publish
    writer.end()

    assert w.demoted()[0] is None
    with w:
        assert len(w.annotations("docid:dlate")) == 1
        assert len(w.annotations(":")) == 5
        lst = w.annotations("docid:dlate")
        assert w.translate(int(lst.starts[0]),
                           int(lst.ends[0])) == "late racing document"


# ------------------------------------------------------------------ #
# compaction runs concurrently with readers, never blocking a pinned
# snapshot
# ------------------------------------------------------------------ #
def test_pinned_reader_during_concurrent_compaction(tmp_path):
    store = TieredStore(str(tmp_path / "t"))
    w = _build(store, n=24, per_txn=4)
    with w:
        expect_docs = w.annotations(":")
        lo, hi = int(expect_docs.starts[0]), int(expect_docs.ends[0])
        expect_text = w.translate(lo, hi)

    # slow the maintenance path down so reads demonstrably overlap it
    orig_publish = store.manifests.publish

    def slow_publish(m):
        time.sleep(0.15)
        orig_publish(m)
    store.manifests.publish = slow_publish

    w.start()                                # pin a pre-compaction view
    done = threading.Event()
    errors = []

    def maintain():
        try:
            store.freeze()
            store.freeze()                   # no-op: nothing new committed
            store.compact_runs(min_runs=1)
        except Exception as e:               # pragma: no cover
            errors.append(e)
        finally:
            done.set()

    t = threading.Thread(target=maintain)
    t.start()
    reads = 0
    while not done.is_set():
        assert w.annotations(":") == expect_docs
        assert w.translate(lo, hi) == expect_text
        reads += 1
    t.join()
    w.end()
    assert not errors
    assert reads > 3                         # reader made progress throughout
    assert store.metrics.n_freezes == 1
    with w:                                  # post-compaction view agrees
        assert w.annotations(":") == expect_docs
        assert w.translate(lo, hi) == expect_text
    store.close()


# ------------------------------------------------------------------ #
# hot-tier size-tiered auto-merge policy
# ------------------------------------------------------------------ #
def test_auto_merge_policy_bounds_segment_count():
    idx = DynamicIndex(auto_merge_threshold=4)
    w = Warren(idx)
    for i in range(14):
        with w:
            w.transaction()
            index_document(w, _doc_text(i), docid=f"d{i}")
            w.commit()
    assert len(idx._segments) <= 5           # merged back under the cap
    with w:
        assert len(w.annotations(":")) == 14
        d = w.annotations("docid:d11")
        assert w.translate(int(d.starts[0]), int(d.ends[0])) == _doc_text(11)


def test_default_behavior_never_auto_merges():
    idx = DynamicIndex()
    w = Warren(idx)
    for i in range(8):
        with w:
            w.transaction()
            index_document(w, _doc_text(i))
            w.commit()
    assert len(idx._segments) == 8


# ------------------------------------------------------------------ #
# cold-shard demotion on the ShardedWarren
# ------------------------------------------------------------------ #
def test_sharded_demote_query_parity_and_write_promotion(tmp_path):
    from repro.dist.shard_router import ShardedWarren

    w = ShardedWarren(n_shards=3, replicas=2, static_dir=str(tmp_path))
    for i in range(0, 36, 6):
        with w:
            w.transaction()
            for j in range(i, i + 6):
                index_document(w, _doc_text(j), docid=f"d{j}")
            w.commit()
    with w:
        before = w.search("school education student", k=10)
        d5 = w.annotations("docid:d5")
        span5 = (int(d5.starts[0]), int(d5.ends[0]))
        text5 = w.translate(*span5)

    for g in range(3):
        w.demote_group(g)
    assert all(d is not None for d in w.demoted())

    with w:                                  # all-cold reads: exact parity
        assert w.search("school education student", k=10) == before
        assert w.translate(*span5) == text5
        assert len(w.annotations(":")) == 36
        assert w.search_gcl("[docid:d5]")

    with w:                                  # a write wakes its group only
        w.transaction()
        index_document(w, "fresh hot wind conductor doc", docid="dnew")
        w.commit()
    cold = [d is not None for d in w.demoted()]
    assert cold.count(False) == 1 and cold.count(True) == 2
    with w:                                  # mixed hot+cold scatter-gather
        assert len(w.annotations(":")) == 37
        assert w.translate(*span5) == text5
        assert w.search("wind conductor", k=5)

    for g in range(3):
        w.promote_group(g)
    assert all(d is None for d in w.demoted())
    assert all(all(row) for row in w.health())
    with w:
        assert len(w.annotations(":")) == 37
        assert w.translate(*span5) == text5


def test_demote_resurrect_index_roundtrip(tmp_path):
    idx = DynamicIndex()
    w = Warren(idx)
    for i in range(6):
        with w:
            w.transaction()
            index_document(w, _doc_text(i), docid=f"d{i}")
            w.commit()
    with w:
        lst = w.annotations("docid:d2")
        victim = (int(lst.starts[0]), int(lst.ends[0]))
    with w:
        w.transaction()
        w.erase(*victim)
        w.commit()

    d = str(tmp_path / "cold")
    m = demote_index(idx, d)
    assert m.next_addr == idx._next_addr and m.next_seq == idx._next_seq

    for replica in resurrect_index(d, n=2):
        w2 = Warren(replica)
        with w, w2:
            for f in (":", "docid:d0", "docid:d2", "dl:"):
                assert w2.annotations(f) == w.annotations(f)
            assert w2.translate(*victim) is None
        assert replica._next_addr == idx._next_addr
        assert replica._next_seq == idx._next_seq


# ------------------------------------------------------------------ #
# serving: RetrievalServer scores merged hot+cold lists
# ------------------------------------------------------------------ #
def test_retrieval_server_over_tiered_warren(tmp_path):
    from repro.train.serve import RetrievalServer

    store = TieredStore(str(tmp_path / "t"))
    w = _build(store, n=20, per_txn=5)
    store.freeze()                           # cold runs...
    with w:
        w.transaction()
        index_document(w, _doc_text(3) + " school education", docid="dhot")
        w.commit()                           # ...plus a hot segment on top
    with w:
        host = score_bm25(w, "school education student", k=10)
        full = dict(score_bm25(w, "school education student", k=21))
    server = RetrievalServer(w, k=10)
    server.refresh_stats()
    got = server.query("school education student", timeout=30)
    server.close()
    # same score profile; doc order may differ only within exact ties
    np.testing.assert_allclose([s for _, s in got],
                               [s for _, s in host], rtol=1e-5)
    for d, s in got:                         # each served doc scored as host
        np.testing.assert_allclose(s, full[d], rtol=1e-5)
    store.close()


# ------------------------------------------------------------------ #
# background compactor end-to-end
# ------------------------------------------------------------------ #
def test_background_compactor_converges(tmp_path):
    store = TieredStore(str(tmp_path / "t"), auto_merge_threshold=4)
    compactor = Compactor(store, freeze_segments=2, max_runs=2,
                          interval_s=0.01).start()
    w = store.warren()
    for i in range(0, 30, 3):
        with w:
            w.transaction()
            for j in range(i, i + 3):
                index_document(w, _doc_text(j), docid=f"d{j}")
            w.commit()
    compactor.stop(drain=True)
    assert store.metrics.n_freezes >= 1
    assert store.n_runs <= 2 + 1
    with w:
        assert len(w.annotations(":")) == 30
        assert score_bm25(w, "school education", k=10)
    store.close()
