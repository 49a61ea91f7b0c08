"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), its configuration's deployment kind
(``bench/kinds/<kind>.py`` or the package ``bench/kinds/<kind>/``, named
by the configuration's ``"kind"``; ``harness`` says what a kind provides),
its metrics and their readers (``bench/metrics/<name before the first
dot>.py``, a ``read(ctx)`` function each).  A new cell, configuration,
kind, mix or metric is a new file and a new entry; nothing here changes."""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: Path = ROOT) -> Cell:
    bench = benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, name)]
    return Cell(name, w["chips"], config, mix, e2e, per_layer)


def reader(metric: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<metric up to its first dot>.py``."""
    base = metric.split(".", 1)[0]
    path = root / "bench" / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kind(name: str, root: Path = ROOT):
    """The deployment kind ``name``, imported as ``kinds.<name>`` from
    ``root``'s ``bench/kinds`` (a namespace package, so the checkout's
    own kinds stay found beside a test root's)."""
    bench = str(root / "bench")
    if bench not in sys.path:
        sys.path.append(bench)
    importlib.invalidate_caches()       # a kind written since the last look
    return importlib.import_module(f"kinds.{name}")
