"""Every cell loads by name, and BENCHMARK.json keeps to its contract."""

import json
import re
import shutil

import pytest

import cells
import generate
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.benchmark(ROOT)


def test_every_cell_loads_by_name(bench):
    for w in bench["workloads"]:
        cell = cells.load(w["name"], ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        assert cell.config["name"] == w["config"]
        assert cell.mix["loop"] in ("open", "closed")
        # the configuration's kind serves every request kind of the mix
        generate.shares(cell.mix, cells.kind(cell.config["kind"], ROOT))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cells.load("no.such.cell", ROOT)


def test_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    n = len(bench["workloads"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, n // 2)
    # 24 cells at this run length fit the check's 43,200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    seen = set()
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["name"] not in seen
        seen.add(c["name"])
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg and key in cfg["reduced"]
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    cell_names = {w["name"] for w in bench["workloads"]}
    assert len(cell_names) == n
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == n
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in seen and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    metric_names = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in metric_names
        metric_names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cell_names
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        moved = e2e[m["moves"]].get("workloads", cell_names)
        assert set(m["workloads"]) <= set(moved)


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A cell, a mix, a configuration and a metric added as new files and
    BENCHMARK.json entries load without any change to the harness."""
    root = tmp_path
    shutil.copytree(BENCH / "configs", root / "bench" / "configs")
    shutil.copytree(BENCH / "traffic", root / "bench" / "traffic")
    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics")
    bench = cells.benchmark(ROOT)
    cfg = json.loads((BENCH / "configs" / "msmarco-passage-4x2.json")
                     .read_text())
    cfg["name"] = "msmarco-passage-8x2"
    cfg["deployment"]["n_shards"] = 8
    (root / "bench" / "configs" / "msmarco-passage-8x2.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "steady.json").read_text())
    # a new arrival shape: 1 s bursts at three times the mean rate
    mix["rate_per_s"] = 10
    mix["pattern"] = [[1.0, 3.0], [4.0, 0.5]]
    mix["queries"]["max_df_share"] = 0.05
    (root / "bench" / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "constant.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    bench["configs"].append({"name": "msmarco-passage-8x2",
                             "source": "x", "why": "x", "reduced": [],
                             "file": "bench/configs/msmarco-passage-8x2.json"})
    bench["workloads"].append({"name": "passage.trickle",
                               "config": "msmarco-passage-8x2",
                               "traffic": "trickle", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("passage.trickle")
    bench["per_layer"].append({"name": "constant.lat", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "Device", "moves": "query_p50_ms",
                               "workloads": ["passage.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load("passage.trickle", root)
    assert cell.config["deployment"]["n_shards"] == 8
    assert cell.mix["rate_per_s"] == 10
    cfg = dict(cell.config, passages=500)
    plan = generate.plan(cell.mix, cells.kind(cfg["kind"], root), cfg,
                         2**31 + 5, 50.0)
    assert len(plan.due) == 500
    assert 0.5 < ((plan.due % 5.0) < 1.0).mean() < 0.7
    assert [m["name"] for m in cell.per_layer] == ["constant.lat"]
    assert cells.reader("constant.lat", root)(None) == 1.0
