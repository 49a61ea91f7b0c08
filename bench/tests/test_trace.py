"""The trace reduction, on interval arithmetic and on a small trace
recorded on a TPU v5e by ``record_trace.py``."""

from pathlib import Path

import numpy as np
import pytest

import trace as trace_mod

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_trace.xplane.pb"


def test_union_complement_overlap():
    iv = np.array([[5, 7], [0, 2], [1, 3], [7, 8]], float)
    u = trace_mod.union(iv)
    assert u.tolist() == [[0, 3], [5, 8]]
    assert trace_mod.length(u) == 6
    gaps = trace_mod.complement(u, -1, 10)
    assert gaps.tolist() == [[-1, 0], [3, 5], [8, 10]]
    assert trace_mod.overlap(gaps, np.array([[2, 6], [9, 20]], float)) == 3
    assert trace_mod.clip(u, 1, 6).tolist() == [[1, 3], [5, 6]]


def test_recorded_v5e_trace():
    red = trace_mod.reduce(RECORDED)
    # 20 scorer calls inside a window of 20 x (2 ms of host work + a call)
    assert 0.04 < red["window_s"] < 1.0
    assert 0 < red["busy_s"] < red["window_s"]
    ops = dict(red["device_ops"])
    assert len(ops) <= trace_mod.TOP and sum(ops.values()) > 0
    # the scorer's scatter-add fusion and its top-k
    assert max(ops, key=ops.get) == "fusion" and "custom-call:TopK" in ops
    gaps = dict(red["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    # the device idles while the host packs, 20 x 2 ms; the clocks agree
    # to about a millisecond, so at least half of it lands under the label
    assert 0.02 <= gaps["bench.pack"] <= idle + 1e-9
    assert gaps["bench.pack"] + gaps["(no host span)"] == pytest.approx(idle)


def test_op_names():
    hlo = ('%custom-call.2 = (f32[16,10]{1,0}, s32[16,10]{1,0}) custom-call('
           'f32[16,16384]{1,0} %reshape.7), custom_call_target="TopK"')
    assert trace_mod.op_name(hlo) == "custom-call:TopK"
    assert trace_mod.op_name("%fusion = f32[65536]{0} fusion(s32[1]{0} %a)") \
        == "fusion"


def test_a_trace_without_a_tpu_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no TPU"):
        trace_mod.reduce(trace_mod.find_xplane(tmp_path))
