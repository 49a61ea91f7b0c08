"""Per-layer readers on hand-made inputs."""

import numpy as np
import pytest

import cells
import harness
from conftest import ROOT


def _ctx(**kw):
    base = dict(batch={}, phase={}, kernel="bm25_topk",
                timings=({"scatter_s": 0, "merge_s": 0},
                         {"scatter_s": 0, "merge_s": 0}))
    base.update(kw)
    return harness.Context(**base)


def test_scorer_roofline_counts_8_bytes_per_posting_of_distinct_terms():
    # word ranks 1, 2, 3 in 4, 2 and 1 passages
    df = np.array([0, 4, 2, 1])
    pool = [[1, 2], [3], [1, 3]]

    class Reads:
        query = np.array([0, 0, 1, 2, 2])
        done = np.array([1.0, np.nan, 1.0, 1.0, 1.0])
    ctx = _ctx(reads=Reads, pool=pool, corpus_df=df,
               trace={"busy_s": 1e-9, "window_s": 1.0},
               peaks={"hbm_bw": 8.0 * 22 * 1e9})
    # answered: query 0 once (6 postings), query 1 (1), query 2 twice (5)
    nbytes = 8 * (6 + 1 + 5 + 5)
    want = 100.0 * nbytes / (8.0 * 22 * 1e9) / 1e-9
    assert cells.reader("scorer_roofline.lat")(ctx) == pytest.approx(want)


def test_roofline_and_idle_say_nothing_without_device_time():
    ctx = _ctx(trace={"busy_s": 0.0, "window_s": 0.0})
    assert cells.reader("scorer_roofline.lat")(ctx) is None
    assert cells.reader("device_idle.lat")(ctx) is None


def test_per_batch_readers():
    key = (("kernel", "bm25_topk"), ("phase", "gather"))
    ctx = _ctx(batch={(): (4, 10.0)},
               phase={key: (16, 40.0),
                      (("kernel", "bm25_topk"), ("phase", "compute")):
                      (4, 8.0)},
               timings=({"scatter_s": 1.0, "merge_s": 2.0},
                        {"scatter_s": 1.2, "merge_s": 2.004}),
               trace={"busy_s": 0.25, "window_s": 1.0})
    assert cells.reader("batch_size.lat")(ctx) == 2.5
    assert cells.reader("pack_ms.lat")(ctx) == 10.0
    assert cells.reader("device_wait_ms.lat")(ctx) == 2.0
    assert cells.reader("scatter_ms.lat")(ctx) == pytest.approx(50.0)
    assert cells.reader("merge_ms.lat")(ctx) == pytest.approx(1.0)
    assert cells.reader("device_idle.lat")(ctx) == 75.0


def test_commit_ms_reads_only_the_windows_writes():
    class Writes:
        ack = [5.0, 1.010, 2.030, np.nan]
        commit_start = [0.0, 1.0, 2.0, 3.0]
    ctx = _ctx(writes=Writes, w_lo=1)
    assert cells.reader("commit_ms")(ctx) == pytest.approx(20.0)
    assert cells.reader("commit_ms")(_ctx(writes=Writes, w_lo=4)) is None


def test_commit_p50_counts_from_when_the_update_was_due():
    class Writes:
        ack = [5.0, 1.050, 2.130, 3.070, np.nan]
        due = [0.0, 1.0, 2.0, 3.0, 4.0]
    ctx = _ctx(writes=Writes, w_lo=1)
    assert cells.reader("commit_p50_ms.lat")(ctx) == pytest.approx(70.0)
    assert cells.reader("commit_p50_ms.lat")(_ctx(writes=Writes, w_lo=5)) \
        is None


def test_every_metric_of_every_cell_has_a_reader():
    for w in cells.benchmark(ROOT)["workloads"]:
        cell = cells.load(w["name"], ROOT)
        for m in cell.per_layer:
            assert callable(cells.reader(m["name"]))
