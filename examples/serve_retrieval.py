"""Serving: batched first-stage retrieval from an annotative index,
plus two-tower candidate scoring (the learned-retrieval hand-off).

Shows the three scoring paths agreeing and their relative speed:
  1. lazy host engine (paper-faithful Cottontail-style),
  2. batched device scoring (vectorized τ/ρ + scatter-add),
  3. Block-Max Pallas kernel (compiled on a TPU, interpreted on the CPU).

    PYTHONPATH=src python examples/serve_retrieval.py [--docs 2000]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (DynamicIndex, Warren, build_block_impacts,
                        collection_stats, ingest_documents, score_bm25)
from repro.core.ranking import block_impact_array
from repro.data.synth import doc_generator
from repro.kernels import bm25_blockmax_topk
from repro.launch.cache import enable_compile_cache
from repro.train.serve import RetrievalServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--shards", type=int, default=1,
                    help="serve from N hash-partitioned index shards")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replicas per shard group (quorum commits, "
                         "read failover)")
    ap.add_argument("--async-scatter", action="store_true",
                    help="with --shards: fan per-group reads out on the "
                         "ScatterGather worker pool and print the "
                         "scatter/score/merge breakdown")
    ap.add_argument("--tiered", action="store_true",
                    help="serve through the LSM-style tiered engine "
                         "(hot memtable + on-disk runs, background "
                         "compaction)")
    ap.add_argument("--demote-cold", action="store_true",
                    help="with --shards: demote every shard group to a "
                         "static run set after the build and show query "
                         "parity (a write promotes a group back)")
    ap.add_argument("--metrics-dump", metavar="PATH", default=None,
                    help="on exit, append the obs metrics snapshot to PATH "
                         "as a JSONL record and write the Prometheus text "
                         "exposition to PATH + '.prom'")
    ap.add_argument("--trace-slow", metavar="MS", type=float, default=None,
                    help="dump any request trace slower than MS milliseconds "
                         "to traces_slow.jsonl next to --metrics-dump (or "
                         "the cwd)")
    ap.add_argument("--admin-port", type=int, default=None,
                    help="serve the obs admin endpoint (/metrics, /routing, "
                         "/traces, /profile/cpu, ...) on this port for the "
                         "duration of the run (0 = ephemeral)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace_slow is not None:
        import os

        from repro import obs
        slow_path = os.path.join(
            os.path.dirname(args.metrics_dump) if args.metrics_dump else ".",
            "traces_slow.jsonl")
        obs.tracer().set_slow_dump(args.trace_slow, slow_path)
    if args.tiered and (args.shards > 1 or args.replicas > 1):
        ap.error("--tiered is the single-node engine; for sharded cold "
                 "storage use --shards N --demote-cold")

    tmpdir = None
    compactor = None
    if args.shards > 1 or args.replicas > 1:
        import tempfile

        from repro.dist.shard_router import ShardedWarren
        tmpdir = tempfile.TemporaryDirectory()
        warren = ShardedWarren(n_shards=args.shards, replicas=args.replicas,
                               static_dir=tmpdir.name,
                               async_scatter=args.async_scatter)
    elif args.tiered:
        import tempfile

        from repro.tiered import Compactor, TieredStore
        tmpdir = tempfile.TemporaryDirectory()
        store = TieredStore(tmpdir.name + "/tiered")
        compactor = Compactor(store, freeze_segments=3,
                              interval_s=0.01).start()
        warren = store.warren()
    else:
        warren = Warren(DynamicIndex())
    admin = None
    if args.admin_port is not None:
        from repro import obs
        admin = obs.AdminServer(
            port=args.admin_port,
            warren=warren if hasattr(warren, "describe_routing") else None,
            slo=obs.SLOMonitor()).start()
        print(f"admin endpoint: {admin.url()}")
    t0 = time.time()
    ingest_documents(warren, doc_generator(0, args.docs), batch=256)
    print(f"indexed {args.docs} docs in {time.time() - t0:.1f}s")
    if compactor is not None:
        compactor.stop(drain=True)   # hot tier -> immutable runs
        print(f"tiered state: {store.n_runs} runs, "
              f"{len(store.hot._segments)} hot segments "
              f"({store.metrics.summary()})")

    queries = ["vibration conductor wind", "school education student",
               "government law state", "stock money business"] * 4

    # 1. host engine
    with warren:
        stats = collection_stats(warren)
        t0 = time.time()
        host = [score_bm25(warren, q, k=10, stats=stats) for q in queries]
        t_host = time.time() - t0

    # 2. batched device serving (dynamic micro-batching server); over a
    # ShardedWarren this is the NATIVE scatter-gather path: one fan-out per
    # group per micro-batch, per-group device top-k, global k-way merge
    server = RetrievalServer(warren, k=10)
    t0 = time.time()
    handles = [server.batcher.submit(q) for q in queries]
    dev = [h.get(timeout=30) for h in handles]
    t_dev = time.time() - t0
    if args.shards > 1 or args.replicas > 1:
        print(f"sharded serving ({'async' if args.async_scatter else 'seq'} "
              f"scatter): {server.timing_summary()}")
    server.close()

    # 3. block-max kernel on one query
    with warren:
        terms = queries[0].split()
        bidx = build_block_impacts(warren, terms, block_size=128, stats=stats)
    impacts = block_impact_array(bidx)          # [NB, T, BS]
    bmax = impacts.max(axis=2)
    t0 = time.time()
    scores, ids = bm25_blockmax_topk(jnp.asarray(impacts), jnp.asarray(bmax),
                                     k=10)
    t_kernel = time.time() - t0

    # agreement
    host_top = {d for d, _ in host[0]}
    dev_top = {d for d, _ in dev[0]}
    kern_top = {int(bidx.doc_starts[i]) for i, s in
                zip(np.asarray(ids), np.asarray(scores)) if s > 0}
    print(f"top-10 agreement host/device: "
          f"{len(host_top & dev_top)}/10, host/kernel: "
          f"{len(host_top & kern_top)}/10")
    # replica failover: kill one replica of every group, answers unchanged
    if args.replicas > 1:
        with warren:
            before = score_bm25(warren, queries[0], k=10)
        for g in range(warren.n_shards):
            warren.mark_failed(g, g % args.replicas)
        with warren:
            after = score_bm25(warren, queries[0], k=10)
        same = [round(s, 9) for _, s in before] == \
               [round(s, 9) for _, s in after]
        print(f"failover (1 replica/group killed): scores identical={same}")
        for g in range(warren.n_shards):
            warren.resurrect(g, g % args.replicas)
    # cold-shard demotion: freeze every group to on-disk runs, answers
    # unchanged; the next write transparently promotes its group
    if args.demote_cold and args.shards > 1:
        with warren:
            before = score_bm25(warren, queries[0], k=10)
        for g in range(warren.n_shards):
            warren.demote_group(g)
        with warren:
            after = score_bm25(warren, queries[0], k=10)
        same = [round(s, 9) for _, s in before] == \
               [round(s, 9) for _, s in after]
        print(f"cold demotion ({warren.n_shards} groups -> static runs): "
              f"scores identical={same}")
        from repro.core import index_document as _idx
        with warren:
            warren.transaction()
            _idx(warren, "fresh hot document wind conductor", docid="dX")
            warren.commit()
        n_cold = sum(1 for d in warren.demoted() if d is not None)
        print(f"write-through promotion: {warren.n_shards - n_cold} group(s) "
              f"hot again, {n_cold} still cold")

    print(f"host engine      : {1e3 * t_host / len(queries):7.2f} ms/query")
    print(f"batched device   : {1e3 * t_dev / len(queries):7.2f} ms/query "
          f"(includes jit)")
    print(f"block-max kernel : {1e3 * t_kernel:7.2f} ms (1 query, includes "
          f"jit; {jax.default_backend()})")
    if admin is not None:
        admin.close()
    if args.tiered:
        store.close()
    if args.shards > 1 or args.replicas > 1:
        warren.close()               # shuts the scatter pool, if any
    if tmpdir is not None:
        tmpdir.cleanup()
    if args.metrics_dump:
        from repro import obs
        from repro.obs import JsonlSink
        reg = obs.registry()
        JsonlSink(args.metrics_dump).write(reg)
        with open(args.metrics_dump + ".prom", "w") as fh:
            fh.write(reg.to_prometheus())
        print(f"metrics dumped to {args.metrics_dump} (+ .prom)")
    if args.trace_slow is not None:
        tr = obs.tracer()
        print(f"slow traces (> {args.trace_slow:g} ms): "
              f"{tr.n_slow_dumped} dumped to {slow_path}")


if __name__ == "__main__":
    main()
