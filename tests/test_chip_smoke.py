"""``chip_smoke.py`` rehearsed on the CPU at a tiny size: every phase runs
and agrees with host BM25, and a run that expects a TPU refuses the CPU."""

import importlib.util
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_runs_every_phase_on_cpu(chip_smoke, capsys):
    device = chip_smoke.run(docs=300, n_queries=16, platform="cpu")
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    out = capsys.readouterr().out
    assert out.count("mismatches=0") == 2
    assert "agrees with host=True" in out
    assert '"ok"' not in out          # only main() prints the verdict


def test_chip_smoke_refuses_a_platform_it_does_not_find(chip_smoke, capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="expected a tpu"):
        chip_smoke.run(docs=300, platform="tpu")
    assert "ingest" not in capsys.readouterr().out


@pytest.mark.parametrize("got,ok", [
    ([(1, 3.0), (2, 2.0), (4, 2.0)], True),
    ([(1, 3.0), (4, 2.0), (2, 2.0)], True),    # docs 2 and 4 tie on the host
    ([(1, 3.0), (2, 2.0), (5, 2.0)], False),   # doc 5 scores 0.5 on the host
    ([(1, 3.0), (2, 2.0 * (1 + 1e-4)), (4, 2.0)], False),
    ([(1, 3.0), (2, 2.0)], False),
    ([(1, 3.0), (2, 2.0), (2, 2.0)], False),
])
def test_chip_smoke_top_k_comparison(chip_smoke, monkeypatch, got, ok):
    monkeypatch.setattr(chip_smoke, "K", 3)
    ranked = [(1, 3.0), (2, 2.0), (4, 2.0), (3, 1.0), (5, 0.5)]
    assert chip_smoke.same_top_k(got, ranked) is ok


def test_compile_cache_lives_in_the_checkout(tmp_path):
    from repro.launch import cache
    assert cache.checkout_cache_dir() == ROOT / ".jax_cache"
    # an installed copy (site-packages/repro/launch) has no checkout
    copy = tmp_path / "site-packages" / "repro" / "launch" / "cache.py"
    copy.parent.mkdir(parents=True)
    shutil.copy(cache.__file__, copy)
    spec = importlib.util.spec_from_file_location("installed_cache", copy)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        mod.checkout_cache_dir()
