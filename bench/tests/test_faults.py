"""Whole runs on the CPU at a small size (the harness's look for a chip is
skipped): a sound run is correct, and each fault the cells can have,
planted in the program underneath, and the lower-precision control come
out as not correct."""

import pytest

import control
import harness

SECONDS = 2.0

def _run(root, cell, seed=11, **kw):
    return harness.run(cell, seed, SECONDS, False, platform="cpu", root=root,
                       **kw)

@pytest.mark.parametrize("cell", ["passage.steady", "acid.ycsb-b",
                                  "passage.saturate"])
def test_sound_run_is_correct(cpu_state, cell):
    out = _run(cpu_state, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["score_gap"]["value"] < 1e-6

@pytest.mark.parametrize("cell", ["passage.steady", "passage.saturate"])
def test_altered_answer_is_caught(cpu_state, monkeypatch, cell):
    from repro.train import serve
    real = serve.bm25_topk

    class Altered:
        """The scorer, with every top score raised by one part in 10^4."""

        def __call__(self, *a, **k):
            scores, ids = real(*a, **k)
            return scores.at[:, 0].multiply(1.0001), ids

        def lower(self, *a, **k):
            return real.lower(*a, **k)
    monkeypatch.setattr(serve, "bm25_topk", Altered())
    out = _run(cpu_state, cell)
    assert not out["correct"]
    assert out["checks"]["answers_wrong"]["value"] > 0

def test_half_the_batch_left_out_is_caught(cpu_state, monkeypatch):
    from repro.train.serve import RetrievalServer
    real = RetrievalServer._handle

    def half(self, queries):
        """Score the first half of the batch; the rest get its answers."""
        keep = queries[:max(1, len(queries) // 2)]
        rows = real(self, keep)
        return [rows[i % len(rows)] for i in range(len(queries))]
    monkeypatch.setattr(RetrievalServer, "_handle", half)
    out = _run(cpu_state, "passage.saturate")
    assert not out["correct"]
    assert out["checks"]["answers_wrong"]["value"] > 0

def test_commit_that_leaves_the_state_unchanged_is_caught(cpu_state,
                                                          monkeypatch):
    from repro.dist.shard_router import ShardedWarren

    def commit(self):
        """Acknowledge, publish nothing."""
        self.abort()
        return lambda a: a
    monkeypatch.setattr(ShardedWarren, "commit", commit)
    out = _run(cpu_state, "acid.ycsb-b")
    assert not out["correct"]
    assert out["checks"]["replica_doc_diff"]["value"] > 0
    assert out["checks"]["durable_diff"]["value"] > 0

def test_lost_log_is_caught(cpu_state, monkeypatch):
    from repro.core.log import TransactionLog
    real = TransactionLog._write_frame

    def drop(self, record, sync=True):
        """Commit frames never reach the file."""
        if record.get("t") != "commit" or self.path is None:
            real(self, record, sync)
    monkeypatch.setattr(TransactionLog, "_write_frame", drop)
    out = _run(cpu_state, "acid.ycsb-b")
    assert not out["correct"]
    assert out["checks"]["durable_diff"]["value"] > 0

@pytest.mark.parametrize("cell", ["passage.steady", "acid.ycsb-b",
                                  "passage.saturate"])
def test_bf16_control_is_not_correct(cpu_state, cell):
    out = _run(cpu_state, cell, hooks={"server": control.Bf16Server})
    assert not out["correct"]
    assert out["checks"]["score_gap"]["value"] > 1e-4
