"""Build or restore a configuration's deployment through the program.

The first run of a configuration in a checkout builds a snapshot.  Each
passage goes to the group that the program's own append routing picks for
it (``route_text`` over the routing table's write groups, the group a
one-passage transaction would land on), and each group's passages are
ingested through the program's transaction path (``ranking.index_document``
inside one ``ShardedWarren`` transaction per group: a bulk load).  The four
groups are built at once, each in a process of its own that never touches
JAX, and each hands back its committed segments.  The snapshot holds them
pickled, with the routing table and the address pair of every passage,
in the run's state directory (``bench/.state/``) keyed by the corpus, the
number of groups and a hash of the program's sources and of this build,
so a changed program never restores another program's index.

Later runs restore it: every replica of a group gets its own copy of the
group's segments, as ``CheckpointManager.restore_index_replicas`` makes
them, and the family is put together as ``ShardedWarren.restore`` does.  The
program's own checkpoint files are not used: at an open vocabulary they
encode and decode every posting list in vByte, minutes per run (PERF.md,
section 7).
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import pickle
import shutil
import subprocess
import sys
import time
from pathlib import Path
from typing import Tuple

import numpy as np

from . import corpus as corpus_mod
from .corpus import CORPUS_KEYS, Corpus

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1]
ROOT = BENCH.parent


def source_hash() -> str:
    """Hash of what makes the snapshot: every Python source of the program,
    and the benchmark's corpus generator and build."""
    h = hashlib.sha256()
    files = sorted((ROOT / "src" / "repro").rglob("*.py"))
    for p in files + [HERE / "corpus.py", HERE / "deploy.py"]:
        h.update(str(p.relative_to(ROOT)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def snapshot_dir(config: dict, state: Path) -> Path:
    key = json.dumps({"corpus": {k: config[k] for k in CORPUS_KEYS},
                      "n_shards": config["deployment"]["n_shards"],
                      "src": source_hash()}, sort_keys=True)
    return state / ("snapshot-" + hashlib.sha256(key.encode()).hexdigest()[:16])


def append_groups(corpus: Corpus, n_shards: int) -> np.ndarray:
    """The group the program routes each passage's append to."""
    from repro.dist.shard_router import RoutingTable, route_text
    wg = RoutingTable.striped(n_shards).write_groups
    return np.array([wg[route_text(corpus.text(i), len(wg))]
                     for i in range(corpus.n)], np.int64)


def build_group(config: dict, group: int, out: Path) -> None:
    """Ingest the passages routed to ``group`` in one transaction and
    pickle the group's committed state to ``out``."""
    from repro.core import ranking
    from repro.dist.shard_router import ShardedWarren

    c = corpus_mod.make_corpus(config)
    n_shards = config["deployment"]["n_shards"]
    ids = np.flatnonzero(append_groups(c, n_shards) == group)
    warren = ShardedWarren(n_shards=n_shards, replicas=1)
    with warren:
        warren.transaction()
        staged = [ranking.index_document(warren, c.text(int(i))) for i in ids]
        remap = warren.commit()
    idx = warren.groups[group].replicas[0]
    if any(warren.groups[g].replicas[0]._segments
           for g in range(n_shards) if g != group):
        raise RuntimeError(f"group {group}'s passages landed elsewhere")
    addrs = np.array([(remap(lo), remap(hi)) for lo, hi in staged],
                     np.int64).reshape(-1, 2)
    state = {"ids": ids, "addrs": addrs, "segments": idx._segments,
             "next_addr": idx._next_addr, "next_seq": idx._next_seq,
             "table": warren.routing.to_record()}
    warren.close()
    with open(out, "wb") as fh:
        pickle.dump(state, fh, protocol=pickle.HIGHEST_PROTOCOL)


def build_snapshot(config: dict, corpus: Corpus, out: Path) -> None:
    """Build every group at once, one process each, and publish ``out``."""
    n_shards = config["deployment"]["n_shards"]
    tmp = out.with_name(out.name + f".tmp-{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    (tmp / "config.json").write_text(json.dumps(config))
    # the builders never touch the chip: this process may hold it
    path = [str(BENCH), str(ROOT / "src"), os.environ.get("PYTHONPATH")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(filter(None, path)))
    procs = [subprocess.Popen([sys.executable, "-m", __name__,
                               str(tmp / "config.json"), str(g),
                               str(tmp / f"group{g:02d}.pkl")], env=env)
             for g in range(n_shards)]
    codes = [p.wait() for p in procs]
    if any(codes):
        raise RuntimeError(f"snapshot build failed: exit codes {codes}")
    addrs = np.full((corpus.n, 2), -1, np.int64)
    for g in range(n_shards):
        with open(tmp / f"group{g:02d}.pkl", "rb") as fh:
            st = pickle.load(fh)
        addrs[st["ids"]] = st["addrs"]
    if (addrs < 0).any():
        raise RuntimeError("snapshot build lost passages")
    np.save(tmp / "passage_addrs.npy", addrs)
    try:
        os.rename(tmp, out)
    except OSError:             # another run published it first
        shutil.rmtree(tmp, ignore_errors=True)


def open_deployment(config: dict, corpus: Corpus, state: Path,
                    log_dir: Path = None) -> Tuple[object, np.ndarray, dict]:
    """The configuration's ``ShardedWarren``, restored from the snapshot
    in ``state`` (built first when absent), with its passage address table
    and a dict of set-up timings.  With ``log_dir`` every replica commits
    to a durable transaction log of its own there, started empty."""
    from repro.core.featurizer import JsonFeaturizer
    from repro.core.tokenizer import Utf8Tokenizer
    from repro.dist.shard_router import ShardedWarren

    dep = config["deployment"]
    snap = snapshot_dir(config, state)
    times = {}
    if not (snap / "passage_addrs.npy").exists():
        t0 = time.perf_counter()
        state.mkdir(parents=True, exist_ok=True)
        build_snapshot(config, corpus, snap)
        times["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    if log_dir is not None:
        shutil.rmtree(log_dir, ignore_errors=True)
        log_dir.mkdir(parents=True)
    tokenizer, featurizer = Utf8Tokenizer(), JsonFeaturizer()
    # millions of objects come in at once: collecting while they load only
    # costs time (the set-up collects once when it is done)
    gc_was = gc.isenabled()
    gc.disable()
    try:
        groups, table = _load_groups(snap, dep, log_dir, tokenizer, featurizer)
    finally:
        if gc_was:
            gc.enable()
    warren = ShardedWarren(tokenizer=tokenizer, featurizer=featurizer,
                           async_scatter=dep["async_scatter"],
                           _groups=groups, _table=table)
    addrs = np.load(snap / "passage_addrs.npy")
    times["restore_s"] = time.perf_counter() - t0
    return warren, addrs, times


def _load_groups(snap: Path, dep: dict, log_dir, tokenizer, featurizer):
    from repro.core.index import DynamicIndex
    from repro.dist.shard_router import ReplicaGroup, RoutingTable

    groups, table = [], None
    for g in range(dep["n_shards"]):
        blob = (snap / f"group{g:02d}.pkl").read_bytes()
        reps = []
        for r in range(dep["replicas"]):
            st = pickle.loads(blob)
            path = (str(log_dir / f"shard{g:02d}r{r}.log")
                    if log_dir is not None else None)
            idx = DynamicIndex(tokenizer, featurizer, log_path=path)
            idx._segments = st["segments"]
            idx._version = 1
            idx._next_addr = st["next_addr"]
            idx._next_seq = st["next_seq"]
            reps.append(idx)
        table = RoutingTable.from_record(st["table"])
        grp = ReplicaGroup(g, reps)
        grp.epoch = table.group_epochs[g]
        groups.append(grp)
    return groups, table


if __name__ == "__main__":
    # one group of a snapshot build: <config.json> <group> <out.pkl>
    build_group(json.loads(Path(sys.argv[1]).read_text()), int(sys.argv[2]),
                Path(sys.argv[3]))
