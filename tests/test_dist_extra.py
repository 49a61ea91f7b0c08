"""repro.dist beyond the seed tests: compression round-trips (property),
ShardedWarren == single Warren, sharded checkpoints, codec fallback."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DynamicIndex, Warren, collection_stats,
                        index_document, score_bm25)
from repro.core import codec
from repro.core.query import solve
from repro.data.synth import doc_generator
from repro.dist.checkpoint import (CheckpointCorrupt, CheckpointManager,
                                   CheckpointShapeMismatch)
from repro.dist.compression import (compress_with_feedback, compression_ratio,
                                    decompress, init_residual)
from repro.dist.elastic import repartition_shards, shrink_mesh
from repro.dist.shard_router import STRIPE, ShardedWarren, shard_of


# ------------------------------------------------------------------ #
# dist.compression: property round-trips
# ------------------------------------------------------------------ #
@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(-100.0, 100.0), min_size=1, max_size=64))
def test_quantize_dequantize_error_bound(xs):
    g = {"w": jnp.asarray(np.array(xs, np.float32))}
    r = init_residual(g)
    q, s, new_r = compress_with_feedback(g, r)
    deq = decompress(q, s)
    step = float(np.abs(np.asarray(g["w"])).max()) / 127.0
    err = np.asarray(deq["w"]) - np.asarray(g["w"])
    assert np.abs(err).max() <= step + 1e-6
    # the residual is exactly the negated rounding error
    np.testing.assert_allclose(np.asarray(new_r["w"]), -err,
                               rtol=1e-5, atol=1e-6)
    assert q["w"].dtype == jnp.int8


@settings(max_examples=15, deadline=None)
@given(st.lists(st.floats(-1.0, 1.0), min_size=4, max_size=32))
def test_residual_carry_is_unbiased(xs):
    """Across repeated sends of the same gradient the carried residual
    keeps the stream unbiased: cumulative dequantized mass tracks n*g."""
    g = {"w": jnp.asarray(np.array(xs, np.float32))}
    r = init_residual(g)
    acc = np.zeros(len(xs), np.float64)
    n = 25
    for _ in range(n):
        q, s, r = compress_with_feedback(g, r)
        acc += np.asarray(decompress(q, s)["w"], np.float64)
    scale = float(np.abs(np.asarray(g["w"])).max()) / 127.0
    err = np.abs(acc / n - np.asarray(g["w"], np.float64)).max()
    assert err <= scale  # a plain (no-feedback) quantizer only gives n*scale


def test_compression_ratio_helper():
    g = {"a": jnp.ones((256, 256)), "b": jnp.ones((128,))}
    assert compression_ratio(g) < 0.26


# ------------------------------------------------------------------ #
# core.codec: zlib fallback
# ------------------------------------------------------------------ #
def test_codec_roundtrip_and_tagging():
    blob = codec.compress(b"annotative indexing" * 100)
    assert blob[0] in (codec.ZSTD, codec.ZLIB)
    assert codec.decompress(blob) == b"annotative indexing" * 100
    with pytest.raises(ValueError):
        codec.decompress(bytes([99]) + blob[1:])


# ------------------------------------------------------------------ #
# dist.shard_router: sharded == single-index retrieval
# ------------------------------------------------------------------ #
def _ingest(w, docs, batch=32):
    it = iter(docs)
    while True:
        chunk = [d for _, d in zip(range(batch), it)]
        if not chunk:
            return
        with w:
            w.transaction()
            for docid, text in chunk:
                index_document(w, text, docid=docid)
            w.commit()


@pytest.fixture(scope="module")
def corpus():
    return list(doc_generator(42, 240, mean_len=50))


@pytest.fixture(scope="module")
def single(corpus):
    w = Warren(DynamicIndex())
    _ingest(w, corpus)
    return w


@pytest.fixture(scope="module")
def sharded(corpus):
    sw = ShardedWarren(n_shards=4)
    _ingest(sw, corpus)
    return sw


QUERIES = ["vibration conductor wind", "school education student",
           "government law state", "stock money business"]


def _texts(w, results):
    stats = collection_stats(w)
    ends = {int(s): int(e) for s, e in zip(stats.doc_starts, stats.doc_ends)}
    return [w.translate(d, ends[d]) for d, _ in results]


def test_sharded_topk_equals_single(single, sharded):
    assert len({shard_of(s._next_addr) for s in sharded.shards}) == 4
    for q in QUERIES:
        with single:
            ref = score_bm25(single, q, k=10)
            ref_texts = _texts(single, ref)
        with sharded:
            merged = score_bm25(sharded, q, k=10)      # zero-change surface
            merged_texts = _texts(sharded, merged)
        np.testing.assert_allclose([s for _, s in merged],
                                   [s for _, s in ref], rtol=1e-9)
        # identical documents modulo equal-score ties
        i = 0
        ref_scores = [round(s, 9) for _, s in ref]
        while i < len(ref):
            j = i
            while j < len(ref) and ref_scores[j] == ref_scores[i]:
                j += 1
            assert set(merged_texts[i:j]) == set(ref_texts[i:j])
            i = j


def test_sharded_gcl_solutions_match(single, sharded):
    with single:
        ref = solve("school", single, limit=10_000)
    with sharded:
        got = sharded.search_gcl("school", limit=10_000)
    assert len(got) == len(ref) > 0


def test_sharded_erase_visible_through_merged_reads(sharded):
    with sharded:
        docs = sharded.annotations(":")
        n0 = len(docs)
        victim = (int(docs.starts[0]), int(docs.ends[0]))
    with sharded:
        sharded.transaction()
        sharded.erase(*victim)
        sharded.commit()
    with sharded:
        assert len(sharded.annotations(":")) == n0 - 1
        assert sharded.translate(*victim) is None


def test_sharded_cross_shard_transaction(sharded):
    """One transaction annotating committed docs on several shards."""
    with sharded:
        docs = sharded.annotations(":")
        picks = [(int(docs.starts[i]), int(docs.ends[i]))
                 for i in range(1, len(docs), max(len(docs) // 6, 1))]
    with sharded:
        sharded.transaction()
        for p, q in picks:
            sharded.annotate("audit:", p, q, 1.0)
        sharded.commit()
    assert len({shard_of(p) for p, _ in picks}) > 1
    with sharded:
        assert len(sharded.annotations("audit:")) == len(picks)


def test_sharded_checkpoint_roundtrip(tmp_path, sharded):
    cm = CheckpointManager(str(tmp_path), async_write=False)
    sharded.checkpoint(cm, 7)
    restored = ShardedWarren.restore(cm, 7)
    assert restored.n_shards == sharded.n_shards
    q = QUERIES[0]
    with sharded:
        ref = score_bm25(sharded, q, k=10)
    with restored:
        got = score_bm25(restored, q, k=10)
    assert [(d, round(s, 9)) for d, s in got] == \
        [(d, round(s, 9)) for d, s in ref]
    # restored shards keep allocating inside their stripe
    for i, s in enumerate(restored.shards):
        assert shard_of(s._next_addr) == i


# ------------------------------------------------------------------ #
# dist.checkpoint: corruption tolerance
# ------------------------------------------------------------------ #
def test_restore_latest_good_skips_corrupt(tmp_path):
    cm = CheckpointManager(str(tmp_path), async_write=False)
    cm.save(1, {"w": np.arange(3.0)})
    cm.save(2, {"w": np.arange(3.0) * 2})
    with open(os.path.join(str(tmp_path), "step_00000002",
                           "state.msgpack"), "r+b") as fh:
        fh.seek(10)
        fh.write(b"\xff\xff\xff")
    with pytest.raises(CheckpointCorrupt):
        cm.restore(2, {"w": np.zeros(3)})
    step, state = cm.restore_latest_good({"w": np.zeros(3)})
    assert step == 1
    np.testing.assert_array_equal(state["w"], np.arange(3.0))


def test_torn_shard_snapshot_refuses_restore(tmp_path):
    """A missing middle shard must be an error, not a truncated warren."""
    sw = ShardedWarren(n_shards=3)
    _ingest(sw, list(doc_generator(5, 60, mean_len=30)))
    cm = CheckpointManager(str(tmp_path), async_write=False)
    sw.checkpoint(cm, 3)
    os.unlink(os.path.join(str(tmp_path), "shard01_00000003.log"))
    with pytest.raises(CheckpointCorrupt, match="missing shard"):
        ShardedWarren.restore(cm, 3)


def test_shape_mismatch_is_loud_not_skipped(tmp_path):
    """A config change must not silently restart training from step 0."""
    cm = CheckpointManager(str(tmp_path), async_write=False)
    cm.save(1, {"w": np.zeros(4), "step": 1})
    bigger = {"w": np.zeros(4), "extra": np.zeros(2), "step": 0}
    with pytest.raises(CheckpointShapeMismatch):
        cm.restore(1, bigger)
    with pytest.raises(CheckpointShapeMismatch):
        cm.restore_latest_good(bigger)


def test_async_write_failure_surfaces_on_wait(tmp_path):
    cm = CheckpointManager(str(tmp_path / "ck"), async_write=True)
    cm.save(1, {"w": np.zeros(4)}, block=True)          # healthy write
    broken = tmp_path / "not_a_dir"
    broken.write_text("occupied")                       # mkdir will fail
    cm.directory = str(broken)
    with pytest.raises(RuntimeError, match="checkpoint write failed"):
        cm.save(2, {"w": np.zeros(4)}, block=True)
    cm.directory = str(tmp_path / "ck")                 # error is one-shot
    cm.save(3, {"w": np.zeros(4)}, block=True)
    assert cm.all_steps() == [1, 3]


def test_index_checkpoint_roundtrip(tmp_path):
    w = Warren(DynamicIndex())
    _ingest(w, list(doc_generator(3, 40, mean_len=30)))
    cm = CheckpointManager(str(tmp_path), async_write=False)
    cm.save_index(5, w.index)
    assert cm.index_steps() == [5]
    idx2 = cm.restore_index(5)
    w2 = Warren(idx2)
    with w, w2:
        assert score_bm25(w, "school education", k=5) == \
            score_bm25(w2, "school education", k=5)


# ------------------------------------------------------------------ #
# dist.elastic + trainer integration
# ------------------------------------------------------------------ #
def test_shrink_mesh_edge_cases():
    with pytest.raises(ValueError):
        shrink_mesh({"data": 4, "model": 4}, lost_devices=16)
    with pytest.raises(ValueError):
        shrink_mesh({"data": 1, "model": 8}, lost_devices=4)
    out = shrink_mesh({"pod": 4, "data": 8, "model": 4}, lost_devices=100)
    assert out["model"] == 4 and out["pod"] * out["data"] * 4 <= 28


def test_repartition_shards_covers_all_items():
    shards = [[f"doc{i}" for i in range(20)], [f"doc{i}" for i in range(20, 50)]]
    out = repartition_shards(shards, 3)
    assert sorted(x for s in out for x in s) == sorted(x for s in shards for x in s)
    assert sum(bool(s) for s in out) == 3


def test_trainer_with_compressed_grads():
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    def loss(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    w_true = rng.standard_normal((8, 4)).astype(np.float32)

    class Stream:
        def __init__(self):
            self.step = 0

        def __iter__(self):
            return self

        def __next__(self):
            r = np.random.default_rng(self.step)
            x = r.standard_normal((32, 8)).astype(np.float32)
            self.step += 1
            return {"x": x, "y": x @ w_true}

    params = {"w": jnp.zeros((8, 4), jnp.float32)}
    cfg = TrainerConfig(total_steps=40, ckpt_every=1000, ckpt_dir=None,
                        compress_grads=True,
                        opt=AdamWConfig(lr=3e-2, warmup_steps=2,
                                        total_steps=40))
    t = Trainer(loss, params, cfg, Stream())
    out = t.train()
    assert out["step"] == 40
    assert "ef" in t.opt_state            # residual rides in the opt state
    losses = [m["loss"] for m in out["metrics"]]
    assert losses[-1] < losses[0] * 0.5   # converges despite int8 grads
