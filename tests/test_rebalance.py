"""Live shard rebalancing: split/merge replica groups under load.

Tier-1 here covers the acceptance criteria of the rebalancing issue: a
live split (and merge) is bit-identical to a single-index oracle —
including random op interleavings and ops issued *during* a split with
concurrent writers — readers are never aborted, tombstones survive the
partition, transactions staged across a swap are re-staged transparently,
demoted groups merge by shipping run manifests (no promotion), and the
routing table round-trips through checkpoints.  The chaos variants
(replica kills mid-migration) live behind the ``stress`` marker.
"""

import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DynamicIndex, Warren, index_document, score_bm25
from repro.dist.checkpoint import CheckpointManager
from repro.dist.elastic import (merge_shard_groups, repartition_replica_groups,
                                repartition_shards, split_shard_group)
from repro.dist.rebalance import (RebalanceAborted, RebalanceError,
                                  Rebalancer)
from repro.dist.shard_router import ShardedWarren

VOCAB = ["school", "education", "student", "government", "law", "state",
         "stock", "money", "business", "vibration", "conductor", "wind"]

QUERIES = ["school education student", "government law state",
           "stock money business", "vibration conductor wind"]


def _text(n: int) -> str:
    return " ".join(VOCAB[(n * 7 + i * (1 + n % 5)) % len(VOCAB)]
                    for i in range(3 + n % 6))


def _ingest(warren, ids, batch=16):
    ids = list(ids)
    while ids:
        chunk, ids = ids[:batch], ids[batch:]
        with warren:
            warren.transaction()
            for n in chunk:
                index_document(warren, _text(n), docid=f"d{n}")
            warren.commit()


def _erase_doc(warren, docid):
    with warren:
        lst = warren.annotations("docid:" + docid)
        assert len(lst) == 1
        warren.transaction()
        warren.erase(int(lst.starts[0]), int(lst.ends[0]))
        warren.commit()


def _annotation_view(warren, feature):
    """Address-free view of a feature's list: sorted (text, value) pairs."""
    lst = warren.annotations(feature)
    out = []
    for i in range(len(lst)):
        out.append((warren.translate(int(lst.starts[i]), int(lst.ends[i])),
                    float(lst.values[i])))
    return sorted(out, key=lambda t: (t[0] or "", t[1]))


def _assert_search_parity(sharded, single, queries=QUERIES, k=10):
    for q in queries:
        got = score_bm25(sharded, q, k=k)
        ref = score_bm25(single, q, k=k)
        np.testing.assert_allclose([s for _, s in got],
                                   [s for _, s in ref], rtol=1e-9)


def _pair(n_docs=120, n_shards=2, replicas=2):
    sharded = ShardedWarren(n_shards=n_shards, replicas=replicas)
    single = Warren(DynamicIndex())
    _ingest(sharded, range(n_docs))
    _ingest(single, range(n_docs))
    return sharded, single


# ------------------------------------------------------------------ #
# deterministic acceptance checks
# ------------------------------------------------------------------ #
def test_live_split_is_bit_identical_to_single_index():
    sharded, single = _pair()
    for d in ("d3", "d40"):                       # tombstones BEFORE the split
        _erase_doc(sharded, d)
        _erase_doc(single, d)
    rb = Rebalancer(sharded)
    new_gid = rb.split_group(0)
    assert new_gid == 2 and sharded.n_shards == 3
    assert sharded.routing.epoch == 1
    stats = rb.last_stats
    assert stats.kind == "split" and stats.swap_s >= 0.0
    for d in ("d7", "d50"):                       # tombstones AFTER the split
        _erase_doc(sharded, d)
        _erase_doc(single, d)
    _ingest(sharded, range(500, 540))             # appends after the split
    _ingest(single, range(500, 540))
    with sharded, single:
        assert len(sharded.annotations(":")) == len(single.annotations(":"))
        for d in ("d3", "d40", "d7", "d50"):
            assert len(sharded.annotations("docid:" + d)) == 0
        feats = [":", "docid:d10", "docid:d80", "docid:d510"]
        for f in feats:
            assert _annotation_view(sharded, f) == _annotation_view(single, f)
        _assert_search_parity(sharded, single)


def test_split_then_merge_roundtrip_and_retired_group_addressable():
    sharded, single = _pair(n_docs=100)
    rb = Rebalancer(sharded)
    new_gid = rb.split_group(0)
    _ingest(sharded, range(700, 720))
    _ingest(single, range(700, 720))
    rb.merge_groups(0, new_gid)
    assert rb.last_stats.kind == "merge"
    grp = sharded.groups[new_gid]
    assert grp.retired
    # retired groups stay addressable: health, demote refusal, empty reads
    assert len(sharded.health()) == sharded.n_shards == 3
    with pytest.raises(ValueError, match="retired"):
        sharded.demote_group(new_gid, "/tmp/never-used")
    with pytest.raises(RebalanceError, match="retired"):
        rb.split_group(new_gid)
    _ingest(sharded, range(800, 830))             # writes after the merge
    _ingest(single, range(800, 830))
    with sharded, single:
        assert len(sharded.annotations(":")) == len(single.annotations(":"))
        for f in (":", "docid:d0", "docid:d705", "docid:d820"):
            assert _annotation_view(sharded, f) == _annotation_view(single, f)
        _assert_search_parity(sharded, single)


def test_native_retrieval_server_is_exact_after_rebalance():
    """The per-group serving pipeline (global stats, posting cap, device
    top-k, address-keyed merge) returns exactly ``score_bm25``'s top-k over
    the merged surface after a split has broken the group-order ==
    address-order assumption: the same documents in the same order, tie
    order included."""
    from repro.train.serve import RetrievalServer

    sharded, _ = _pair(n_docs=90)
    Rebalancer(sharded).split_group(0)
    server = RetrievalServer(sharded, k=10)
    try:
        got = server._handle(QUERIES)
        with sharded:
            ref = [score_bm25(sharded, q, k=10) for q in QUERIES]
        for q, g_hits, r_hits in zip(QUERIES, got, ref):
            assert [d for d, _ in g_hits] == [d for d, _ in r_hits], q
            # the device scores in float32, the reference in float64
            np.testing.assert_allclose([s for _, s in g_hits],
                                       [s for _, s in r_hits], rtol=1e-6)
    finally:
        server.close()


def test_transaction_staged_across_split_is_restaged():
    """A transaction staged against the pre-split topology commits cleanly
    after the swap: the warren re-stages the logical ops against the new
    routing table instead of surfacing RouteEpochError."""
    sharded, single = _pair(n_docs=60, n_shards=2, replicas=1)
    with sharded:
        docs = sharded.annotations(":")
        picks = [(int(docs.starts[i]), int(docs.ends[i]))
                 for i in range(0, len(docs), max(len(docs) // 5, 1))]
    writer = sharded.clone()
    writer.start()
    writer.transaction()
    for p, q in picks:
        writer.annotate("xtag:", p, q, 1.0)
    index_document(writer, _text(999), docid="d999")
    # the swap lands between staging and commit
    Rebalancer(sharded).split_group(0)
    writer.commit()
    writer.end()
    with sharded:
        assert len(sharded.annotations("xtag:")) == len(picks)
        assert len(sharded.annotations("docid:d999")) == 1


def test_split_with_concurrent_writers_and_readers():
    """ISSUE acceptance: live split completes with concurrent writers and
    zero aborted reader transactions; the result matches a single index
    holding exactly the committed documents; the only writer stall is the
    swap (measured)."""
    sharded = ShardedWarren(n_shards=2, replicas=2)
    _ingest(sharded, range(80))
    errors, committed = [], []
    stop = threading.Event()

    def writer(wid):
        wc = sharded.clone()
        for i in range(30):
            n = 1000 + wid * 100 + i
            try:
                with wc:
                    wc.transaction()
                    index_document(wc, _text(n), docid=f"d{n}")
                    wc.commit()
                committed.append(n)
            except Exception as e:            # noqa: BLE001 — test invariant
                errors.append(f"writer d{n}: {type(e).__name__}: {e}")
                return

    def reader():
        wc = sharded.clone()
        seen = 0
        while not stop.is_set():
            try:
                with wc:
                    n = len(wc.annotations(":"))
                    score_bm25(wc, "school education", k=5)
                if n < seen:
                    errors.append(f"reader went backwards: {n} < {seen}")
                    return
                seen = n
            except Exception as e:            # noqa: BLE001 — zero aborts
                errors.append(f"reader: {type(e).__name__}: {e}")
                return

    writers = [threading.Thread(target=writer, args=(w,)) for w in range(3)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in writers + readers:
        t.start()
    rb = Rebalancer(sharded)
    new_gid = rb.split_group(0)
    for t in writers:
        t.join(timeout=120)
    stop.set()
    for t in readers:
        t.join(timeout=30)
    assert errors == [], errors
    assert len(committed) == 90
    stats = rb.last_stats
    assert stats.swap_s > 0.0 and stats.segments_streamed > 0

    single = Warren(DynamicIndex())
    _ingest(single, range(80))
    _ingest(single, sorted(committed), batch=1)
    with sharded, single:
        assert len(sharded.annotations(":")) == 80 + len(committed)
        for q in QUERIES:
            got = sorted(s for _, s in score_bm25(sharded, q, k=10))
            ref = sorted(s for _, s in score_bm25(single, q, k=10))
            np.testing.assert_allclose(got, ref, rtol=1e-9)
    assert new_gid == 2


def test_merge_demoted_groups_ships_manifests_not_records(tmp_path):
    sharded = ShardedWarren(n_shards=3, replicas=2,
                            static_dir=str(tmp_path))
    single = Warren(DynamicIndex())
    _ingest(sharded, range(90))
    _ingest(single, range(90))
    sharded.demote_group(0)
    sharded.demote_group(1)
    rb = Rebalancer(sharded)
    rb.merge_groups(0, 1)
    assert rb.last_stats.kind == "merge-demoted"
    # no promotion happened: the surviving group is still cold, replicas
    # still hold zero in-memory segments, the run count is the sum
    grp = sharded.groups[0]
    assert grp.demoted is not None
    assert all(len(r._segments) == 0 for r in grp.replicas)
    assert sharded.groups[1].retired and sharded.groups[1].demoted is None
    with sharded, single:
        assert len(sharded.annotations(":")) == 90
        for f in (":", "docid:d5", "docid:d42"):
            assert _annotation_view(sharded, f) == _annotation_view(single, f)
        _assert_search_parity(sharded, single)
    # the first write still promotes the (merged) cold group
    _ingest(sharded, [600])
    _ingest(single, [600])
    with sharded, single:
        assert len(sharded.annotations(":")) == 91
        _assert_search_parity(sharded, single)


def test_split_demoted_ships_sliced_runs_no_promotion(tmp_path):
    """A demoted split ships sliced run sets: neither side is promoted,
    both sides stay cold with zero in-memory segments, tombstones recorded
    before demotion hide content on whichever side they landed, and the
    family is bit-identical to the single-index oracle."""
    sharded = ShardedWarren(n_shards=2, replicas=2,
                            static_dir=str(tmp_path))
    single = Warren(DynamicIndex())
    _ingest(sharded, range(100))
    _ingest(single, range(100))
    for d in ("d3", "d40"):
        _erase_doc(sharded, d)
        _erase_doc(single, d)
    sharded.demote_group(0)
    rb = Rebalancer(sharded)
    new_gid = rb.split_group(0)
    assert rb.last_stats.kind == "split-demoted"
    assert rb.last_stats.segments_streamed >= 1
    src, dst = sharded.groups[0], sharded.groups[new_gid]
    assert src.demoted is not None and dst.demoted is not None
    for grp in (src, dst):
        assert all(len(r._segments) == 0 for r in grp.replicas)
    with sharded, single:
        assert len(sharded.annotations(":")) == 98
        for f in (":", "docid:d5", "docid:d42", "docid:d3"):
            assert _annotation_view(sharded, f) == _annotation_view(single, f)
        _assert_search_parity(sharded, single)
    # tombstones recorded after the split land on the owning side only
    for d in ("d7", "d50"):
        _erase_doc(sharded, d)
        _erase_doc(single, d)
    # both sides keep serving and keep allocating without collisions
    _ingest(sharded, range(500, 540))
    _ingest(single, range(500, 540))
    with sharded, single:
        assert len(sharded.annotations(":")) == 136
        for f in (":", "docid:d520", "docid:d7"):
            assert _annotation_view(sharded, f) == _annotation_view(single, f)
        _assert_search_parity(sharded, single)


def test_routing_table_survives_checkpoint_restore(tmp_path):
    sharded, single = _pair(n_docs=80)
    rb = Rebalancer(sharded)
    new_gid = rb.split_group(0)
    rb.merge_groups(1, new_gid)       # leave a retired group in the family
    _ingest(sharded, range(300, 330))
    _ingest(single, range(300, 330))
    cm = CheckpointManager(str(tmp_path), async_write=False)
    sharded.checkpoint(cm, 13)
    restored = ShardedWarren.restore(cm, 13, replicas=2)
    assert restored.n_shards == sharded.n_shards
    assert restored.routing.to_record() == sharded.routing.to_record()
    assert restored.groups[new_gid].retired
    with restored, single:
        assert len(restored.annotations(":")) == len(single.annotations(":"))
        for f in (":", "docid:d0", "docid:d310"):
            assert _annotation_view(restored, f) == _annotation_view(single, f)
        _assert_search_parity(restored, single)
    # the restored family keeps allocating without address collisions
    _ingest(restored, range(400, 420))
    _ingest(single, range(400, 420))
    with restored, single:
        assert len(restored.annotations(":")) == len(single.annotations(":"))
        _assert_search_parity(restored, single)

    # losing or tearing the routing record of a REBALANCED checkpoint must
    # fail loudly, never silently fall back to striped routing
    import os

    from repro.dist.checkpoint import CheckpointCorrupt
    routing_file = tmp_path / "routing_00000013.routing.json"
    good = routing_file.read_text()
    routing_file.write_text(good.replace('"crc": ', '"crc": 1'))
    with pytest.raises(CheckpointCorrupt, match="routing"):
        ShardedWarren.restore(cm, 13, replicas=2)
    os.unlink(routing_file)
    with pytest.raises(CheckpointCorrupt, match="routing"):
        ShardedWarren.restore(cm, 13, replicas=2)


def test_split_preserves_wal_durability(tmp_path):
    """Regression: a log-backed family must keep EVERY document durable
    across a split — the destination group gets its own per-replica logs
    and the moved half must be recoverable from them after the source
    compacts its logs down to the kept half."""
    from repro.core.index import DynamicIndex as DI

    sharded = ShardedWarren(n_shards=2, replicas=2, log_dir=str(tmp_path))
    _ingest(sharded, range(60))
    new_gid = Rebalancer(sharded).split_group(0)
    _ingest(sharded, range(200, 220))          # post-split commits log too
    with sharded:
        expect = len(sharded.annotations(":"))
    recovered = 0
    for g in range(sharded.n_shards):
        path = tmp_path / f"shard{g:02d}r0.log"
        assert path.exists(), f"group {g} lost its durable log"
        idx = DI.recover(str(path))
        w = Warren(idx)
        with w:
            lst = w.annotations(sharded.featurize(":"))
            recovered += len(lst)
    assert recovered == expect == 80           # nothing lost, nothing doubled
    assert new_gid == 2


def test_repartition_keeps_empty_groups_addressable():
    """Regression: ``k_new > k_old`` leaving shards unpopulated must yield
    exactly k_new groups — empty ones included and replica-fanned — and
    routing must be deterministic across repeated calls."""
    groups = [["only-doc-a", "only-doc-b", "only-doc-c"]]
    out = repartition_replica_groups(groups, 6, replicas=2)
    assert len(out) == 6                           # nothing dropped
    empties = [g for g in out if g[0] == []]
    assert empties, "expected at least one unpopulated group"
    for grp in out:
        assert len(grp) == 2                       # replicas fan out too
        assert grp[0] == grp[1] and grp[0] is not grp[1]
    assert out == repartition_replica_groups(groups, 6, replicas=2)
    flat = [x for grp in out for x in grp[0]]
    assert sorted(flat) == sorted(groups[0])
    with pytest.raises(ValueError):
        repartition_shards(groups, 0)
    with pytest.raises(ValueError, match="returned"):
        repartition_shards(groups, 2, route=lambda item, k: k + 7)


def test_elastic_live_wrappers():
    sharded, single = _pair(n_docs=60, replicas=1)
    new_gid = split_shard_group(sharded, 0)
    merge_shard_groups(sharded, 0, new_gid)
    with sharded, single:
        assert len(sharded.annotations(":")) == 60
        _assert_search_parity(sharded, single)


def test_split_refuses_bad_inputs():
    sharded = ShardedWarren(n_shards=2)
    rb = Rebalancer(sharded)
    with pytest.raises(RebalanceError, match="nothing to split"):
        rb.split_group(0)                       # empty group
    with pytest.raises(RebalanceError, match="no shard group"):
        rb.split_group(7)
    _ingest(sharded, range(20))
    with pytest.raises(RebalanceError, match="not inside"):
        rb.split_group(0, pivot=-5)
    with pytest.raises(RebalanceError):
        rb.merge_groups(1, 1)


# ------------------------------------------------------------------ #
# the property test: random interleavings around a split (+ merge)
# ------------------------------------------------------------------ #
def _run_ops(warren, ops, state):
    """Apply logical ops; targets resolve by docid so both warrens pick the
    same logical documents regardless of address layout."""
    committed, next_doc = state
    for kind, arg in ops:
        if kind == "append":
            n = next_doc[0]
            next_doc[0] += 1
            with warren:
                warren.transaction()
                index_document(warren, _text(n), docid=f"d{n}")
                warren.commit()
            committed.append(f"d{n}")
        elif kind == "annotate":
            if not committed:
                continue
            docid = committed[arg % len(committed)]
            with warren:
                lst = warren.annotations("docid:" + docid)
                if not len(lst):
                    continue
                warren.transaction()
                warren.annotate(f"tag{arg % 4}:", int(lst.starts[0]),
                                int(lst.ends[0]), float(arg % 7))
                warren.commit()
        else:  # erase
            if not committed:
                continue
            docid = committed[arg % len(committed)]
            with warren:
                lst = warren.annotations("docid:" + docid)
                if not len(lst):
                    continue
                warren.transaction()
                warren.erase(int(lst.starts[0]), int(lst.ends[0]))
                warren.commit()
            committed.remove(docid)


OPS = st.lists(
    st.tuples(st.sampled_from(["append", "append", "append", "annotate",
                               "erase"]),
              st.integers(0, 999)),
    min_size=8, max_size=24)


@settings(max_examples=6, deadline=None)
@given(OPS, OPS, st.booleans())
def test_random_ops_around_split_match_single_index(before, after, also_merge):
    sharded = ShardedWarren(n_shards=2, replicas=2)
    single = Warren(DynamicIndex())
    state_s = ([], [0])
    state_1 = ([], [0])
    _ingest(sharded, range(30))          # enough mass to make splits legal
    _ingest(single, range(30))
    state_s[0].extend(f"d{n}" for n in range(30))
    state_1[0].extend(f"d{n}" for n in range(30))
    state_s[1][0] = state_1[1][0] = 30
    _run_ops(sharded, before, state_s)
    _run_ops(single, before, state_1)
    rb = Rebalancer(sharded)
    try:
        new_gid = rb.split_group(0)
    except RebalanceError:
        return     # the op stream erased group 0 down to < 2 documents
    _run_ops(sharded, after, state_s)
    _run_ops(single, after, state_1)
    if also_merge:
        rb.merge_groups(1, new_gid)
    assert state_s[0] == state_1[0]
    features = [":"] + [f"tag{i}:" for i in range(4)] + \
        [f"docid:{d}" for d in state_s[0][:8]]
    with sharded, single:
        for f in features:
            assert _annotation_view(sharded, f) == \
                _annotation_view(single, f), f
        for q in ("school education", "money business state", "wind"):
            got = score_bm25(sharded, q, k=10)
            ref = score_bm25(single, q, k=10)
            np.testing.assert_allclose([s for _, s in got],
                                       [s for _, s in ref], rtol=1e-9)


# ------------------------------------------------------------------ #
# chaos: replica kills mid-migration (stress marker, own CI job)
# ------------------------------------------------------------------ #
@pytest.mark.stress
def test_chaos_losing_every_replica_mid_migration_aborts_cleanly():
    """Kill ALL source replicas mid-migration: the swap must abort with no
    torn routing table, and a retry after resurrection must succeed."""
    sharded = ShardedWarren(n_shards=2, replicas=2)
    _ingest(sharded, range(60))
    table_before = sharded.routing.to_record()

    def kill_all(warren, stage, gid):
        if stage == "after_copy":
            for r in range(warren.groups[gid].n_replicas):
                warren.groups[gid].mark_failed(r)

    sharded.hooks["mid_migration"] = kill_all
    rb = Rebalancer(sharded)
    with pytest.raises(RebalanceAborted):
        rb.split_group(0)
    sharded.hooks.clear()
    # no torn state: table unchanged, no half-registered group
    assert sharded.routing.to_record() == table_before
    assert sharded.n_shards == 2
    assert rb.history == []
    # repair (ops override re-joins the intact first replica) and retry
    sharded.groups[0].alive[0] = True
    sharded.resurrect(0, 1)
    new_gid = rb.split_group(0)
    assert new_gid == 2
    single = Warren(DynamicIndex())
    _ingest(single, range(60))
    with sharded, single:
        assert len(sharded.annotations(":")) == 60
        _assert_search_parity(sharded, single)


@pytest.mark.stress
def test_chaos_single_replica_kill_mid_migration_split_survives():
    """Kill one source replica mid-migration while writers run: the split
    streams from a survivor, writers keep committing (R=3 keeps quorum at
    2 with one replica down), and the killed replica resurrects into the
    post-split group in lockstep."""
    sharded = ShardedWarren(n_shards=2, replicas=3)
    _ingest(sharded, range(60))
    killed = []

    def kill_one(warren, stage, gid):
        if stage == "after_copy" and not killed:
            warren.groups[gid].mark_failed(1)
            killed.append((gid, 1))

    sharded.hooks["mid_migration"] = kill_one
    errors, committed = [], []

    def writer(wid):
        wc = sharded.clone()
        for i in range(25):
            n = 2000 + wid * 100 + i
            try:
                with wc:
                    wc.transaction()
                    index_document(wc, _text(n), docid=f"d{n}")
                    wc.commit()
                committed.append(n)
            except Exception as e:            # noqa: BLE001
                errors.append(f"writer d{n}: {type(e).__name__}: {e}")
                return

    writers = [threading.Thread(target=writer, args=(w,)) for w in range(2)]
    for t in writers:
        t.start()
    rb = Rebalancer(sharded)
    new_gid = rb.split_group(0)
    for t in writers:
        t.join(timeout=120)
    sharded.hooks.clear()
    assert errors == [], errors
    assert killed == [(0, 1)]
    sharded.resurrect(0, 1)
    grp = sharded.groups[0]
    a, b, c = grp.replicas
    assert a._next_addr == b._next_addr == c._next_addr
    assert a._next_seq == b._next_seq == c._next_seq
    single = Warren(DynamicIndex())
    _ingest(single, range(60))
    _ingest(single, sorted(committed), batch=1)
    with sharded, single:
        assert len(sharded.annotations(":")) == 60 + len(committed)
        for q in QUERIES:
            np.testing.assert_allclose(
                sorted(s for _, s in score_bm25(sharded, q, k=10)),
                sorted(s for _, s in score_bm25(single, q, k=10)), rtol=1e-9)
    assert new_gid == 2
