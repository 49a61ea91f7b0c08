"""Dry-run machinery under test: a reduced mesh in a subprocess (the forced
device count must be set before jax init, so this runs out of process)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.launch.dryrun import run_cell, collective_stats
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rec = run_cell("internlm2-1.8b", "train_4k", mesh, "test4x2")
    print(json.dumps({k: rec[k] for k in
                      ("ok", "cost", "collectives", "memory")
                      if k in rec}))
""")


@pytest.mark.slow
def test_dryrun_cell_on_small_mesh():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]
    assert rec["cost"]["flops"] > 0
    assert rec["memory"]["peak_bytes"] > 0
    # a DP+TP train step must produce gradient/activation collectives
    assert rec["collectives"], "no collectives found in SPMD HLO"
    total = sum(v["bytes"] for v in rec["collectives"].values())
    assert total > 0


def test_collective_parser():
    from repro.launch.dryrun import collective_stats
    hlo = """
      %all-reduce.1 = f32[1024,512]{1,0} all-reduce(f32[1024,512]{1,0} %x)
      %ag = bf16[64]{0} all-gather(bf16[32]{0} %y), dim=0
      %t = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
      %other = f32[2,2]{1,0} add(f32[2,2]{1,0} %p, f32[2,2]{1,0} %q)
    """
    stats = collective_stats(hlo)
    assert stats["all-reduce"]["bytes"] == 1024 * 512 * 4
    assert stats["all-gather"]["bytes"] == 64 * 2
    assert stats["all-to-all"]["count"] == 1
    assert "collective-permute" not in stats


def test_roofline_correction_math():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.roofline import correct_scan_once
    r1 = {"arch": "internlm2-1.8b", "shape": "train_4k", "ok": True,
          "cost": {"flops": 100.0, "bytes accessed": 50.0},
          "collectives": {"all-reduce": {"bytes": 10.0, "count": 2}}}
    r2 = {"arch": "internlm2-1.8b", "shape": "train_4k", "ok": True,
          "cost": {"flops": 104.0, "bytes accessed": 52.0},
          "collectives": {"all-reduce": {"bytes": 11.0, "count": 3}}}
    out = correct_scan_once(r1, r2)
    # L = 24: true = 100 + 23 * 4
    assert out["cost"]["flops"] == 100.0 + 23 * 4.0
    assert out["cost"]["bytes accessed"] == 50.0 + 23 * 2.0
    assert out["collectives"]["all-reduce"]["bytes"] == 10.0 + 23 * 1.0


@pytest.mark.parametrize("kind,ok", [("TPU v5 lite", True), ("cpu", False),
                                     ("TPU v4", False)])
def test_roofline_peaks_refuse_unknown_device(kind, ok):
    """Peaks come from the published table only: a device it does not
    name is an error, never a default."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.roofline import peaks
    if ok:
        assert peaks(kind)["hbm_bw"] == 819e9
    else:
        with pytest.raises(ValueError, match="no published peaks"):
            peaks(kind)
