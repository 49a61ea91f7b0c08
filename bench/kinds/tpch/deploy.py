"""Build or restore the ``lineitem`` deployment through the program, as
the passages kind does its corpus (``kinds/passages/deploy.py``).

The first run in a checkout builds a snapshot: each row goes to the group
that the program's append routing picks for it (``route_text`` of the
content ``add_json`` appends), and each group's rows are stored with
``add_json(w, row, collection="lineitem")`` inside one ``ShardedWarren``
transaction, then given day numbers for their dates by ``annotate_dates``
in a second (the post-hoc pass), then compacted.  The groups are built at
once, one process each that never touches JAX, each pickling its
committed segments into the state directory, keyed by the data's
parameters, the number of groups and a hash of the program's sources and
of this build.  Later runs restore every replica from it, as the passages
kind does."""

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

import numpy as np

from kinds.passages import deploy as passages_deploy

from . import lineitem

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1]
ROOT = BENCH.parent


def source_hash() -> str:
    h = hashlib.sha256()
    files = sorted((ROOT / "src" / "repro").rglob("*.py"))
    for p in files + [HERE / "lineitem.py", HERE / "deploy.py"]:
        h.update(str(p.relative_to(ROOT)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def snapshot_dir(config: dict, state: Path) -> Path:
    key = json.dumps({"data": {k: config[k] for k in lineitem.KEYS},
                      "n_shards": config["deployment"]["n_shards"],
                      "src": source_hash()}, sort_keys=True)
    return state / ("tpch-" + hashlib.sha256(key.encode()).hexdigest()[:16])


def append_groups(rows: list, n_shards: int) -> np.ndarray:
    """The group the program routes each row's append to."""
    from repro.core.json_store import json_text
    from repro.dist.shard_router import RoutingTable, route_text
    wg = RoutingTable.striped(n_shards).write_groups
    return np.array([wg[route_text(json_text(r), len(wg))] for r in rows],
                    np.int64)


def build_group(config: dict, group: int, out: Path) -> None:
    """Store the rows routed to ``group``, date them, compact, and pickle
    the group's committed state to ``out``."""
    from repro.core import json_store
    from repro.dist.shard_router import ShardedWarren

    rows = lineitem.generate(config)["rows"]
    n_shards = config["deployment"]["n_shards"]
    ids = np.flatnonzero(append_groups(rows, n_shards) == group)
    warren = ShardedWarren(n_shards=n_shards, replicas=1)
    with warren:
        warren.transaction()
        for i in ids:
            json_store.add_json(warren, rows[i],
                                collection=lineitem.COLLECTION)
        warren.commit()
    with warren:
        warren.transaction()
        json_store.annotate_dates(warren, lineitem.DATE_PATHS)
        warren.commit()
    idx = warren.groups[group].replicas[0]
    if any(warren.groups[g].replicas[0]._segments
           for g in range(n_shards) if g != group):
        raise RuntimeError(f"group {group}'s rows landed elsewhere")
    idx.merge_segments()
    state = {"ids": ids, "segments": idx._segments,
             "next_addr": idx._next_addr, "next_seq": idx._next_seq,
             "table": warren.routing.to_record()}
    warren.close()
    with open(out, "wb") as fh:
        pickle.dump(state, fh, protocol=pickle.HIGHEST_PROTOCOL)
    np.save(out.with_suffix(".ids.npy"), ids)


def build_snapshot(config: dict, out: Path) -> None:
    """Build every group at once, one process each, and publish ``out``."""
    n_shards = config["deployment"]["n_shards"]
    tmp = out.with_name(out.name + f".tmp-{os.getpid()}")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    (tmp / "config.json").write_text(json.dumps(config))
    # the build processes never touch the chip: this one may hold it
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
    n = sum(len(np.load(tmp / f"group{g:02d}.ids.npy"))
            for g in range(n_shards))
    if n != len(lineitem.generate(config)["rows"]):
        raise RuntimeError("snapshot build lost rows")
    (tmp / "done").write_text(str(n))
    try:
        os.rename(tmp, out)
    except OSError:             # another run published it first
        shutil.rmtree(tmp, ignore_errors=True)


def open_deployment(config: dict, state: Path):
    """The configuration's ``ShardedWarren``, restored from the snapshot in
    ``state`` (built first when absent), and a dict of set-up timings."""
    from repro.core.featurizer import JsonFeaturizer
    from repro.core.tokenizer import Utf8Tokenizer
    from repro.dist.shard_router import ShardedWarren

    dep = config["deployment"]
    snap = snapshot_dir(config, state)
    times = {}
    if not (snap / "done").exists():
        t0 = time.perf_counter()
        state.mkdir(parents=True, exist_ok=True)
        build_snapshot(config, snap)
        times["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    tokenizer, featurizer = Utf8Tokenizer(), JsonFeaturizer()
    gc_was = gc.isenabled()
    gc.disable()
    try:
        groups, table = passages_deploy._load_groups(
            snap, dep, None, tokenizer, featurizer)
    finally:
        if gc_was:
            gc.enable()
    warren = ShardedWarren(tokenizer=tokenizer, featurizer=featurizer,
                           async_scatter=dep["async_scatter"],
                           _groups=groups, _table=table)
    times["restore_s"] = time.perf_counter() - t0
    return warren, times


if __name__ == "__main__":
    # one group of a snapshot build: <config.json> <group> <out.pkl>
    build_group(json.loads(Path(sys.argv[1]).read_text()), int(sys.argv[2]),
                Path(sys.argv[3]))
