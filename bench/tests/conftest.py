"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
repository root, on the CPU (``JAX_PLATFORMS=cpu``)."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory holding the real ``BENCHMARK.json`` and
    mixes, with every configuration cut to 400 passages and every mix to a
    few seconds at a low rate or 8 clients sharing 200 queries, so a run
    fits the CPU."""
    root = tmp_path_factory.mktemp("tiny")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["passages"] = 400
        (root / c["file"]).write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        mix["warmup_s"] = 1
        if "rate_per_s" in mix:
            mix["rate_per_s"] = 40
        if "clients" in mix:
            mix["clients"], mix["set_size"] = 8, 200
        (root / "bench" / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(mix))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def cpu_state(tiny_root, monkeypatch):
    """Snapshots, logs, traces and the compile cache under the tiny root."""
    import device
    import harness
    monkeypatch.setattr(harness, "STATE", tiny_root / "state")
    monkeypatch.setattr(device, "CACHE_DIR", tiny_root / "jax_cache")
    return tiny_root
