"""Replicated ShardedWarren: equivalence with a single DynamicIndex.

The property test drives identical random interleaved append / annotate /
erase / commit / abort sequences into a ``ShardedWarren(n_shards=3,
replicas=2)`` and a single-index ``Warren`` and requires identical logical
state: for every feature touched, the same annotation multiset (values +
the text each interval annotates — addresses differ by design, stripes vs.
sequential), and the same ``search()`` top-10.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DynamicIndex, Warren, index_document
from repro.dist.checkpoint import CheckpointManager
from repro.dist.elastic import repartition_replica_groups
from repro.dist.shard_router import (QuorumError, ReplicaFailure,
                                     ShardedWarren, shard_of)

VOCAB = ["school", "education", "student", "government", "law", "state",
         "stock", "money", "business", "vibration", "conductor", "wind"]


def _doc_text(n: int) -> str:
    words = [VOCAB[(n * 7 + i * (1 + n % 5)) % len(VOCAB)]
             for i in range(3 + n % 6)]
    return " ".join(words)


# ------------------------------------------------------------------ #
# the op interpreter: one logical op stream, two warrens
# ------------------------------------------------------------------ #
def _run_ops(warren, ops):
    """Apply the logical op stream; returns (docids committed, tags used).

    Transactions are batched: append/annotate/erase ops stage logical
    intents, "commit"/"abort" replays the staged batch inside one
    start/end bracket and commits or aborts it.  Annotate/erase targets
    are resolved by docid lookup inside the bracket, so both warrens pick
    the same logical documents regardless of address layout.
    """
    committed = []                 # docids alive (committed, not erased)
    staged = []
    tags = set()
    next_doc = [0]

    def flush(commit: bool):
        if not staged:
            return
        batch, staged[:] = list(staged), []
        with warren:
            warren.transaction()
            appended, erased = [], []
            for op in batch:
                if op[0] == "append":
                    n = next_doc[0]
                    next_doc[0] += 1
                    index_document(warren, _doc_text(n), docid=f"d{n}")
                    appended.append(f"d{n}")
                elif op[0] == "annotate":
                    if not committed:
                        continue
                    docid = committed[op[1] % len(committed)]
                    lst = warren.annotations("docid:" + docid)
                    if not len(lst):
                        continue
                    tag = f"tag{op[1] % 4}:"
                    tags.add(tag)
                    warren.annotate(tag, int(lst.starts[0]),
                                    int(lst.ends[0]), float(op[1] % 7))
                else:  # erase
                    live = [d for d in committed if d not in erased]
                    if not live:
                        continue
                    docid = live[op[1] % len(live)]
                    lst = warren.annotations("docid:" + docid)
                    if not len(lst):
                        continue
                    warren.erase(int(lst.starts[0]), int(lst.ends[0]))
                    erased.append(docid)
            if commit:
                warren.commit()
                committed.extend(appended)
                for d in erased:
                    committed.remove(d)
            else:
                warren.abort()
                next_doc[0] -= len(appended)   # replayed identically later

    for op in ops:
        if op[0] == "commit":
            flush(True)
        elif op[0] == "abort":
            flush(False)
        else:
            staged.append(op)
    flush(True)
    return committed, tags


def _annotation_view(warren, feature):
    """Address-free view of a feature's list: sorted (text, value) pairs."""
    lst = warren.annotations(feature)
    out = []
    for i in range(len(lst)):
        out.append((warren.translate(int(lst.starts[i]), int(lst.ends[i])),
                    float(lst.values[i])))
    return sorted(out, key=lambda t: (t[0] or "", t[1]))


def _search_view(warren, query, k=10):
    """(rounded score, text) pairs, ties grouped as frozensets."""
    hits = warren.search(query, k=k) if isinstance(warren, ShardedWarren) \
        else _single_search(warren, query, k)
    docs = warren.annotations(":")
    ends = {int(s): int(e) for s, e in zip(docs.starts, docs.ends)}
    pairs = [(round(s, 9), warren.translate(d, ends[d])) for d, s in hits]
    groups, i = [], 0
    while i < len(pairs):
        j = i
        while j < len(pairs) and pairs[j][0] == pairs[i][0]:
            j += 1
        groups.append((pairs[i][0], frozenset(t for _, t in pairs[i:j])))
        i = j
    return groups


def _single_search(warren, query, k):
    from repro.core import score_bm25
    return score_bm25(warren, query, k=k)


OPS = st.lists(
    st.tuples(st.sampled_from(["append", "append", "annotate", "erase",
                               "commit", "abort"]),
              st.integers(0, 999)),
    min_size=6, max_size=40)


@settings(max_examples=8, deadline=None)
@given(OPS)
def test_replicated_sharded_equals_single_property(ops):
    sharded = ShardedWarren(n_shards=3, replicas=2)
    single = Warren(DynamicIndex())
    docs_s, tags_s = _run_ops(sharded, ops)
    docs_1, tags_1 = _run_ops(single, ops)
    assert docs_s == docs_1 and tags_s == tags_1

    features = [":"] + sorted(tags_s) + [f"docid:{d}" for d in docs_s]
    with sharded, single:
        for f in features:
            assert _annotation_view(sharded, f) == _annotation_view(single, f), f
        for q in ("school education", "money business state", "wind"):
            assert _search_view(sharded, q) == _search_view(single, q), q


# ------------------------------------------------------------------ #
# deterministic acceptance checks
# ------------------------------------------------------------------ #
def _ingest(warren, n_docs, batch=32):
    n = 0
    while n < n_docs:
        with warren:
            warren.transaction()
            for _ in range(min(batch, n_docs - n)):
                index_document(warren, _doc_text(n), docid=f"d{n}")
                n += 1
            warren.commit()


@pytest.fixture(scope="module")
def replicated_pair():
    sharded = ShardedWarren(n_shards=3, replicas=2)
    single = Warren(DynamicIndex())
    _ingest(sharded, 150)
    _ingest(single, 150)
    return sharded, single


QUERIES = ["school education student", "government law state",
           "stock money business", "vibration conductor wind"]


def test_search_parity_with_one_replica_killed_per_group(replicated_pair):
    """ISSUE acceptance: replicas=2, one replica of EVERY group dead →
    ``search`` still returns the exact single-index top-10 scores."""
    sharded, single = replicated_pair
    for g in range(sharded.n_shards):
        sharded.mark_failed(g, g % 2)       # alternate which replica dies
    try:
        assert all(sum(a) == 1 for a in sharded.health())
        with sharded, single:
            for q in QUERIES:
                ref = _search_view(single, q)
                got = _search_view(sharded, q)
                assert got == ref, q
                np.testing.assert_allclose(
                    [s for _, s in sharded.search(q, k=10)],
                    [s for _, s in _single_search(single, q, 10)], rtol=1e-9)
    finally:
        for g in range(sharded.n_shards):
            sharded.resurrect(g, g % 2)


def test_resurrect_restores_lockstep(replicated_pair):
    """A resurrected replica streams segments from its sibling and ends up
    address-identical (same starts/ends for every feature probed)."""
    sharded, single = replicated_pair
    sharded.mark_failed(1, 0)
    _ingest(sharded, 20)                     # writes the dead replica misses
    _ingest(single, 20)                      # keep the reference in sync
    sharded.resurrect(1, 0)
    for grp in sharded.groups:
        a, b = grp.replicas
        assert a._next_addr == b._next_addr
        assert a._next_seq == b._next_seq
        wa, wb = Warren(a), Warren(b)
        with wa, wb:
            for f in (":", "school", "docid:d0"):
                fv = sharded.featurize(f)
                la, lb = wa.annotations(fv), wb.annotations(fv)
                assert np.array_equal(la.starts, lb.starts)
                assert np.array_equal(la.ends, lb.ends)
                assert np.array_equal(la.values, lb.values)


def test_quorum_abort_is_clean(replicated_pair):
    """Killing a replica below quorum aborts the WHOLE cross-shard
    transaction; nothing is published on any group and the warren keeps
    serving."""
    sharded, single = replicated_pair
    with sharded:
        docs = sharded.annotations(":")
        picks = [(int(docs.starts[i]), int(docs.ends[i]))
                 for i in range(0, len(docs), max(len(docs) // 5, 1))]
    assert len({shard_of(p) for p, _ in picks}) > 1   # cross-shard txn
    sharded.mark_failed(0, 0)                         # group 0: 1/2 < quorum
    try:
        with sharded:
            before = len(sharded.annotations("qtag:"))
            sharded.transaction()
            for p, q in picks:
                sharded.annotate("qtag:", p, q, 1.0)
            with pytest.raises(QuorumError):
                sharded.commit()
        with sharded:                                  # fully aborted
            assert len(sharded.annotations("qtag:")) == before == 0
    finally:
        sharded.resurrect(0, 0)
    with sharded:                                      # retry succeeds
        sharded.transaction()
        for p, q in picks:
            sharded.annotate("qtag:", p, q, 1.0)
        sharded.commit()
    with sharded:
        assert len(sharded.annotations("qtag:")) == len(picks)


def test_read_failover_when_all_replicas_of_a_group_die(replicated_pair):
    sharded, _ = replicated_pair
    sharded.mark_failed(2, 0)
    sharded.mark_failed(2, 1)
    try:
        with pytest.raises(ReplicaFailure):
            with sharded:
                pass
    finally:
        # resurrect needs a live sibling: revive in reverse order
        sharded.groups[2].alive[0] = True      # ops override: force re-join
        sharded.resurrect(2, 1)
    with sharded:
        assert len(sharded.annotations(":")) > 0


def test_replicated_checkpoint_restore_fans_out(tmp_path, replicated_pair):
    """One snapshot per group on save; restore fans each group out to R
    independent replicas that all serve and stay in their stripe."""
    sharded, single = replicated_pair
    cm = CheckpointManager(str(tmp_path), async_write=False)
    sharded.checkpoint(cm, 11)
    restored = ShardedWarren.restore(cm, 11, replicas=2)
    assert restored.n_shards == sharded.n_shards
    assert restored.replicas == 2
    for g, grp in enumerate(restored.groups):
        assert len(grp.replicas) == 2
        for idx in grp.replicas:
            assert shard_of(idx._next_addr) == g
        assert grp.replicas[0] is not grp.replicas[1]
    # kill one replica per group: restored warren still answers exactly
    for g in range(restored.n_shards):
        restored.mark_failed(g, 1)
    with restored, single:
        for q in QUERIES:
            assert _search_view(restored, q) == _search_view(single, q)
    # a shared transaction log across restored replicas is refused
    with pytest.raises(ValueError, match="per-replica"):
        cm.restore_index_replicas(11, name="shard00", n=2,
                                  log_path=str(tmp_path / "shared.log"))


def test_repartition_replica_groups_moves_whole_groups():
    groups = [[f"doc{i}" for i in range(20)],
              [f"doc{i}" for i in range(20, 50)]]
    out = repartition_replica_groups(groups, 3, replicas=2)
    assert len(out) == 3
    flat = [x for grp in out for x in grp[0]]
    assert sorted(flat) == sorted(x for g in groups for x in g)
    for grp in out:
        assert len(grp) == 2
        assert grp[0] == grp[1]              # replicas carry identical state
        assert grp[0] is not grp[1]          # ...in independent lists
    with pytest.raises(ValueError):
        repartition_replica_groups(groups, 3, replicas=0)
