"""Readers of the serving path's ``impacts`` and ``dispatch`` phases, on
hand-made inputs."""

import pytest

import cells
import harness


def _phase(name):
    return (("kernel", "bm25_topk"), ("phase", name))


def _ctx(phase):
    return harness.Context(batch={(): (4, 10.0)}, phase=phase,
                           kernel="bm25_topk")


@pytest.mark.parametrize("metric, phase", [("impacts_ms.lat", "impacts"),
                                           ("dispatch_ms.lat", "dispatch")])
def test_phase_ms_per_batch(metric, phase):
    ctx = _ctx({_phase("gather"): (16, 40.0), _phase(phase): (16, 30.0)})
    assert cells.reader(metric)(ctx) == 7.5


@pytest.mark.parametrize("metric", ["impacts_ms.lat", "dispatch_ms.lat"])
def test_a_program_without_the_phase_reads_nothing(metric):
    assert cells.reader(metric)(_ctx({_phase("gather"): (16, 40.0)})) is None
    assert cells.reader(metric)(harness.Context(
        batch={}, phase={}, kernel="bm25_topk")) is None
