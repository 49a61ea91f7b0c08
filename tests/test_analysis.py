"""Tier-1: the concurrency contract checker + runtime lock witness.

Static half: fixture modules under ``tests/fixtures/analysis/`` with a
known lock-order inversion, a blocking-call-under-lock, a
metric-contract violation, and a clean module — asserting the *exact*
finding id sets.  Shipped-tree half: ``repro.analysis`` over ``src/``
must be clean under the checked-in hierarchy/suppressions, and must see
the checkpoint path's rebalance→group_write discipline.  Runtime half:
a LockWitness must catch a seeded AB/BA inversion across two threads.
"""

import ast
import os
import threading
from pathlib import Path

import pytest

from repro import obs
from repro.analysis import (Catalog, Hierarchy, Suppressions,
                            SuppressionError, run_analysis)
from repro.analysis.config import load_toml
from repro.analysis.callgraph import CallGraph
from repro.analysis.contracts import analyze_contracts
from repro.analysis.driver import main
from repro.analysis.lockmap import build_lockmap

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "analysis"


def ids(report):
    return sorted(f.id for f in report.active)


# --------------------------------------------------------------------- #
# TOML loading + config plumbing
# --------------------------------------------------------------------- #
def test_toml_lite_roundtrip(tmp_path):
    p = tmp_path / "t.toml"
    p.write_text(
        '# comment\n[a]\nx = 1\ny = "two"\nz = [1, 2, 3]\n'
        'flag = true\n[locks."Dotted.name"]\nrank = 7\n'
        '[[suppress]]\nid = "k"\nreason = "because"\n')
    doc = load_toml(str(p))
    assert doc["a"] == {"x": 1, "y": "two", "z": [1, 2, 3], "flag": True}
    assert doc["locks"]["Dotted.name"]["rank"] == 7
    assert doc["suppress"] == [{"id": "k", "reason": "because"}]


def test_suppressions_require_reason(tmp_path):
    p = tmp_path / "s.toml"
    p.write_text('[[suppress]]\nid = "some:finding"\n')
    with pytest.raises(SuppressionError):
        Suppressions.load(str(p))


def test_suppressions_reject_wildcards(tmp_path):
    p = tmp_path / "s.toml"
    p.write_text('[[suppress]]\nid = "blocking-*"\nreason = "all of it"\n')
    with pytest.raises(SuppressionError):
        Suppressions.load(str(p))


def test_catalog_parses_markdown_tables():
    text = (
        "| metric | type | labels | emitted from |\n"
        "|---|---|---|---|\n"
        "| `ops_total` | counter | `op`, `shard` (id) | here |\n"
        "\n"
        "| span | emitted from |\n"
        "|---|---|\n"
        "| `scatter` | router |\n")
    cat = Catalog.parse(text)
    assert cat.metrics == {"ops_total": {"op", "shard"}}
    assert cat.spans == {"scatter"}


def test_hierarchy_rejects_duplicate_ranks(tmp_path):
    p = tmp_path / "h.toml"
    p.write_text("[locks.a]\nrank = 1\n[locks.b]\nrank = 1\n")
    with pytest.raises(ValueError):
        Hierarchy.load(str(p))


# --------------------------------------------------------------------- #
# fixture modules: exact finding sets
# --------------------------------------------------------------------- #
def test_fixture_inversion_detects_cycle():
    rep = run_analysis([str(FIXTURES / "fix_inversion.py")],
                       use_defaults=False)
    assert ids(rep) == [
        "lock-cycle:Inverted._alpha->Inverted._beta->Inverted._alpha"]
    assert rep.exit_code == 1


def test_fixture_inversion_hierarchy_named(tmp_path):
    # with declared ranks the same fixture also yields the rank violation
    h = tmp_path / "h.toml"
    h.write_text('[locks."Inverted._alpha"]\nrank = 1\n'
                 '[locks."Inverted._beta"]\nrank = 2\n')
    rep = run_analysis([str(FIXTURES / "fix_inversion.py")],
                       hierarchy_path=str(h), use_defaults=False)
    assert ids(rep) == [
        "lock-cycle:Inverted._alpha->Inverted._beta->Inverted._alpha",
        "lock-hierarchy:Inverted._beta->Inverted._alpha"]


def test_fixture_blocking_under_hot_lock(tmp_path):
    h = tmp_path / "h.toml"
    h.write_text('[locks."HotPath._lock"]\nrank = 1\nhot = true\n')
    rep = run_analysis([str(FIXTURES / "fix_blocking.py")],
                       hierarchy_path=str(h), use_defaults=False)
    assert ids(rep) == [
        "blocking-under-lock:HotPath._lock:HotPath.flush:os.fsync",
        "blocking-under-lock:HotPath._lock:HotPath.save:os.fsync"]


def test_fixture_blocking_quiet_when_not_hot(tmp_path):
    h = tmp_path / "h.toml"
    h.write_text('[locks."HotPath._lock"]\nrank = 1\n')
    rep = run_analysis([str(FIXTURES / "fix_blocking.py")],
                       hierarchy_path=str(h), use_defaults=False)
    assert ids(rep) == []


def test_fixture_metric_contracts(tmp_path):
    cat = tmp_path / "arch.md"
    cat.write_text("| metric | type | labels | emitted from |\n"
                   "|---|---|---|---|\n"
                   "| `fixture_ops_total` | counter | `op` | fixture |\n")
    rep = run_analysis([str(FIXTURES / "fix_metrics.py")],
                       catalog_path=str(cat), use_defaults=False)
    assert ids(rep) == [
        "metric-labels:fixture_ops_total:Meter.count",
        "undeclared-metric:fixture_undeclared_ms"]


def test_fixture_clean_has_no_findings(tmp_path):
    h = tmp_path / "h.toml"
    h.write_text('[locks."Clean._outer"]\nrank = 1\nhot = true\n'
                 '[locks."Clean._inner"]\nrank = 2\n')
    cat = tmp_path / "arch.md"
    cat.write_text("| metric | type | labels | emitted from |\n"
                   "|---|---|---|---|\n"
                   "| `fixture_ops_total` | counter | `op` | fixture |\n")
    rep = run_analysis([str(FIXTURES / "fix_clean.py")],
                       hierarchy_path=str(h), catalog_path=str(cat),
                       use_defaults=False)
    assert ids(rep) == []
    assert rep.exit_code == 0
    assert ("Clean._outer", "Clean._inner") in rep.lock_order.edges


def test_cli_exit_codes(capsys):
    assert main([str(FIXTURES / "fix_inversion.py"), "--no-defaults"]) == 1
    assert "lock-cycle" in capsys.readouterr().out
    assert main([str(FIXTURES / "fix_clean.py"), "--no-defaults"]) == 0


# --------------------------------------------------------------------- #
# guard lint (inline hot-path module)
# --------------------------------------------------------------------- #
def _contract_findings(code, module="x/train/serve.py", catalog=None):
    modules = {module: ast.parse(code)}
    graph = CallGraph(modules, build_lockmap(modules))
    return analyze_contracts(graph, catalog or Catalog())


def test_unguarded_metric_in_hot_module():
    found = _contract_findings(
        "import repro.obs as obs\n"
        "def handle(n):\n"
        "    obs.registry().counter('reqs_total').inc()\n")
    assert [f.id for f in found] == ["unguarded-metric:reqs_total:handle"]


def test_guarded_variants_pass():
    found = _contract_findings(
        "import repro.obs as obs\n"
        "def direct(n):\n"
        "    reg = obs.registry()\n"
        "    if reg.enabled:\n"
        "        reg.counter('reqs_total').inc()\n"
        "def early(n):\n"
        "    reg = obs.registry()\n"
        "    if not reg.enabled:\n"
        "        return\n"
        "    reg.counter('reqs_total').inc()\n"
        "def derived(n):\n"
        "    observe = obs.registry().enabled and n > 0\n"
        "    if observe:\n"
        "        obs.registry().counter('reqs_total').inc()\n")
    assert [f.id for f in found] == []


def test_undeclared_span():
    cat = Catalog.parse("| span | emitted from |\n|---|---|\n"
                        "| `scatter` | router |\n")
    found = _contract_findings(
        "import repro.obs as obs\n"
        "def go():\n"
        "    with obs.span('mystery'):\n"
        "        pass\n"
        "    with obs.span('scatter'):\n"
        "        pass\n", catalog=cat)
    assert [f.id for f in found] == ["undeclared-span:mystery"]


# --------------------------------------------------------------------- #
# the shipped tree
# --------------------------------------------------------------------- #
def test_shipped_tree_is_clean():
    rep = run_analysis([str(REPO / "src")])
    assert ids(rep) == []
    assert rep.exit_code == 0
    assert not rep.unused_suppressions
    # every suppression carries a justification
    assert all(reason for _, reason in rep.suppressed)


def test_checkpoint_discipline_is_visible():
    """The acceptance path: checkpoint takes the rebalance lock, then
    every group write lock ascending — the analyzer must see the edge
    and the declared hierarchy must call it legal."""
    rep = run_analysis([str(REPO / "src")])
    edges = rep.lock_order.edges
    assert ("rebalance", "group_write") in edges
    h = Hierarchy.load(str(REPO / "analysis" / "lock_hierarchy.toml"))
    assert h.rank("rebalance") < h.rank("group_write")
    assert h.multi("group_write") == "ascending"
    # and the WAL sits below the group locks, as the 2PC design requires
    assert ("group_write", "wal") in edges
    assert h.rank("group_write") < h.rank("wal")


# --------------------------------------------------------------------- #
# runtime lock witness
# --------------------------------------------------------------------- #
def _in_thread(fn):
    err = []

    def run():
        try:
            fn()
        except BaseException as e:          # pragma: no cover
            err.append(e)

    t = threading.Thread(target=run)
    t.start()
    t.join()
    assert not err


def test_witness_catches_ab_ba_inversion():
    """Seeded AB/BA across two threads — neither deadlocks (they run
    sequentially), but the witness must still convict the pair."""
    a = obs.ProfiledLock("fix_a")
    b = obs.ProfiledLock("fix_b")
    w = obs.install_witness(obs.LockWitness())
    try:
        def t1():
            with a:
                with b:
                    pass

        def t2():
            with b:
                with a:
                    pass

        _in_thread(t1)
        assert w.violations() == []         # A→B alone is fine
        _in_thread(t2)
        assert any("cycle" in v for v in w.violations())
        with pytest.raises(obs.LockOrderViolation):
            w.check()
    finally:
        obs.uninstall_witness()


def test_witness_hierarchy_and_ascending():
    w = obs.LockWitness(ranks={"outer": 1, "inner": 2},
                        multi={"grp": "ascending"})
    w.note_acquire("inner", None, 1)
    w.note_acquire("outer", None, 2)        # rank inversion
    w.note_release("outer", 2)
    w.note_release("inner", 1)
    w.note_acquire("grp", 2, 3)
    w.note_acquire("grp", 1, 4)             # descending order key
    w.note_release("grp", 4)
    w.note_release("grp", 3)
    v = w.violations()
    assert any("hierarchy" in x for x in v)
    assert any("ascending-order" in x for x in v)


def test_witness_allows_clean_orders():
    w = obs.LockWitness(ranks={"outer": 1, "inner": 2},
                        multi={"grp": "ascending", "re": "reentrant"})
    w.note_acquire("outer", None, 1)
    w.note_acquire("inner", None, 2)
    w.note_release("inner", 2)
    w.note_release("outer", 1)
    w.note_acquire("grp", 1, 3)
    w.note_acquire("grp", 2, 4)             # ascending: legal
    w.note_release("grp", 4)
    w.note_release("grp", 3)
    w.note_acquire("re", None, 5)
    w.note_acquire("re", None, 5)           # same instance: reentrant
    w.note_release("re", 5)
    w.note_release("re", 5)
    assert w.violations() == []
    w.check()                               # must not raise


def test_witness_profiledlock_overhead_hook_is_inert():
    """With no witness installed a ProfiledLock round-trip must work and
    record nothing anywhere."""
    assert obs.witness_active() is None
    lk = obs.ProfiledLock("inert")
    with lk:
        assert lk.locked()
    assert not lk.locked()


def test_group_write_order_key_is_group_id():
    from repro.dist.shard_router import ReplicaGroup
    from repro.core.index import DynamicIndex
    g = ReplicaGroup(3, [DynamicIndex()])
    assert g.write_lock.order_key == 3
    assert g.write_lock.name == "group_write"
