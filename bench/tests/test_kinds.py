"""The runner and its deployment kinds: the passages kind plans the same
requests and checks the same numbers as the harness did before kinds
existed, and a new kind is new files and entries alone."""

import hashlib
import json

import numpy as np
import pytest

import cells
import generate
import harness
from conftest import ROOT

# sha256 of the plans at full size (40,000 passages), computed before the
# harness was split into a runner and kinds; see ``_fingerprint``
PARENT_PLANS = {
    ("passage.steady", 11, harness.WARM_STREAM):
        "0a09b0ddd4d2a097fefe698717cfa11432b9b353cfeb8deb9f024610df215dd0",
    ("passage.steady", 11, harness.WINDOW_STREAM):
        "3358693a06a0fbe73cae768460c79ad7174fe35f0d7547d4f57042c6ae743da1",
    ("passage.steady", 12, harness.WARM_STREAM):
        "da336d94d22053c99cd9c7dba5303b00ccda2a8c384a4a758aac8f877d6fb941",
    ("passage.steady", 12, harness.WINDOW_STREAM):
        "5a015997a1791559e9da5cde51440a018454dd7a21d7da6148072686660902f9",
    ("acid.ycsb-b", 11, harness.WARM_STREAM):
        "ea31943906097869ea3dd2a0187e296af0dabe9cc864cfd52a3a57da569768be",
    ("acid.ycsb-b", 11, harness.WINDOW_STREAM):
        "4dea1bc22e23c8c40937bbf7e456a7099a00577d57c8280fced947875e96dfe8",
    ("acid.ycsb-b", 12, harness.WARM_STREAM):
        "79f50b467f818025713ba30b26b1c3a84b2e92ddfd47e7dfd1f393ab3bcb7e7a",
    ("acid.ycsb-b", 12, harness.WINDOW_STREAM):
        "4bcd474dbccdd1f1b0c1f2958d4d970fcdd85c90a167eb74ccdc50b4b2a93fa7",
}
PARENT_LIMITS = {
    "passage.steady": [["unanswered", 0], ["answers_wrong", 0],
                       ["score_gap", 1e-05]],
    "acid.ycsb-b": [["unanswered", 0], ["answers_wrong", 0],
                    ["score_gap", 1e-05], ["replica_doc_diff", 0],
                    ["durable_diff", 0]],
}


def _fingerprint(s) -> str:
    """Due times, then each request in order: a query's word ids, or an
    update's passage and new words."""
    h = hashlib.sha256()
    h.update(np.asarray(s.due, np.float64).tobytes())
    for q, u in zip(s.query, s.updates):
        if q >= 0:
            h.update(b"q" + np.asarray(s.queries[q], np.int64).tobytes())
        else:
            h.update(b"u" + np.int64(u.passage).tobytes()
                     + np.asarray(u.ranks, np.int64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", ["passage.steady", "acid.ycsb-b"])
def test_plans_are_the_parents(cell):
    c = cells.load(cell, ROOT)
    kind = cells.kind(c.config["kind"], ROOT)
    seconds = {harness.WARM_STREAM: c.mix["warmup_s"],
               harness.WINDOW_STREAM: cells.benchmark(ROOT)["run_seconds"]}
    for seed in (11, 12):
        for stream, s in seconds.items():
            plan = generate.plan(c.mix, kind, c.config, seed, s, stream)
            assert _fingerprint(plan) == PARENT_PLANS[cell, seed, stream]


@pytest.mark.parametrize("cell", ["passage.steady", "acid.ycsb-b"])
def test_check_names_and_limits_are_the_parents(cpu_state, cell):
    out = harness.run(cell, 11, 1.0, False, platform="cpu", root=cpu_state)
    assert [[n, c["limit"]] for n, c in out["checks"].items()] == \
        PARENT_LIMITS[cell]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]


ECHO = '''"""A kind of the harness's tests: the program's MicroBatcher in front
of a handler that doubles numbers."""

import harness
from repro.dist.parallel import ScatterTimings
from repro.train.serve import BatcherConfig, MicroBatcher

REQUESTS = {"ping": "read"}


class Server:
    def __init__(self):
        self.batcher = MicroBatcher(lambda xs: [2 * x for x in xs],
                                    BatcherConfig(max_batch=4,
                                                  max_wait_ms=1.0))
        self.timings = ScatterTimings()

    def close(self):
        self.batcher.close()


def open(cell, state_dir, log_dir, span):
    return harness.Deployment(Server(), {})


def payloads(mix, config, counts, stream):
    return {"ping": [config["base"] + i for i in range(counts["ping"])]}


def submit(server, x):
    return server.batcher.submit(x)


def warm(session, slack):
    return "nothing"


def check(cell, session, window, seed):
    r = window.reads
    wrong = sum(a != 2 * session.pool[q]
                for q, a in zip(r.query, r.answers) if a is not None)
    return {"echo_wrong": {"value": int(wrong), "limit": 0}}


def context(session):
    return {}
'''


@pytest.fixture(scope="module")
def echo_root(tiny_root):
    """``tiny_root`` with a kind of its own, ``echo``, as new files and
    entries: a configuration, two mixes (one that asks for a request kind
    the kind does not serve), and their cells."""
    bench_dir = tiny_root / "bench"
    (bench_dir / "kinds").mkdir(exist_ok=True)
    (bench_dir / "kinds" / "echo.py").write_text(ECHO)
    (bench_dir / "configs" / "echo.json").write_text(json.dumps(
        {"name": "echo", "kind": "echo", "base": 100}))
    mix = {"loop": "open", "rate_per_s": 40, "requests": {"ping": 1.0},
           "queries": {"set_seed": 5}, "warmup_s": 1}
    (bench_dir / "traffic" / "ping.json").write_text(json.dumps(mix))
    mix["requests"] = {"ping": 0.5, "query": 0.5}
    (bench_dir / "traffic" / "ping-query.json").write_text(json.dumps(mix))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "echo", "source": "x", "why": "x",
                             "reduced": [],
                             "file": "bench/configs/echo.json"})
    for traffic in ("ping", "ping-query"):
        bench["workloads"].append({"name": f"echo.{traffic}",
                                   "config": "echo", "traffic": traffic,
                                   "chips": 1, "why": "x"})
        bench["end_to_end"][0]["workloads"].append(f"echo.{traffic}")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    return tiny_root


def test_a_new_kind_is_files_and_entries_only(echo_root, cpu_state):
    out = harness.run("echo.ping", 2**31 + 7, 1.0, False, platform="cpu",
                      root=echo_root)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == 40
    assert out["checks"] == {"echo_wrong": {"value": 0, "limit": 0}}
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"query_p50_ms", "setup_s"}


def test_a_request_kind_the_kind_does_not_serve_is_refused(echo_root,
                                                           cpu_state):
    with pytest.raises(ValueError, match=r"\['ping'\]"):
        harness.run("echo.ping-query", 1, 1.0, False, platform="cpu",
                    root=echo_root)
