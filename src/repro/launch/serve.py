"""Serving launcher: LM decode smoke or index-backed retrieval.

  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch yi-9b
  PYTHONPATH=src python -m repro.launch.serve --mode retrieval --docs 1000
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.cache import enable_compile_cache


def serve_lm(args):
    from repro.train.serve import LMServer
    spec = get_arch(args.arch)
    cfg = spec.smoke_config
    params = spec.init_fn(cfg, jax.random.PRNGKey(0))
    server = LMServer(params, cfg, max_slots=4, max_len=64)
    prompts = [[1, 5, 9], [2, 7], [3, 3, 3, 3], [4]]
    t0 = time.time()
    outs = server.generate(prompts, max_new=args.tokens)
    dt = time.time() - t0
    total = sum(len(o) for o in outs)
    print(f"decoded {total} tokens for {len(prompts)} sequences in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, continuous batching)")
    for p, o in zip(prompts, outs):
        print(f"  prompt {p} -> {o[:8]}")


def serve_retrieval(args):
    from repro.core import DynamicIndex, Warren, ingest_documents
    from repro.data.synth import doc_generator
    from repro.train.serve import RetrievalServer
    if args.shards > 1:
        from repro.dist.shard_router import ShardedWarren
        warren = ShardedWarren(n_shards=args.shards,
                               async_scatter=args.async_scatter)
    else:
        warren = Warren(DynamicIndex())
    ingest_documents(warren, doc_generator(0, args.docs))
    server = RetrievalServer(warren, k=10)
    queries = ["vibration conductor", "school student", "stock money"] * 8
    t0 = time.time()
    handles = [server.batcher.submit(q) for q in queries]
    results = [h.get(timeout=60) for h in handles]
    dt = time.time() - t0
    print(f"served {len(queries)} queries in {dt:.2f}s "
          f"({1e3 * dt / len(queries):.2f} ms/query, micro-batched)")
    if args.shards > 1:
        print(f"sharded serving breakdown: {server.timing_summary()}")
    print(f"top-3 for {queries[0]!r}: {results[0][:3]}")
    server.close()
    if args.shards > 1:
        warren.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["lm", "retrieval"], default="lm")
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--docs", type=int, default=1000)
    ap.add_argument("--shards", type=int, default=1,
                    help="retrieval mode: serve a ShardedWarren natively")
    ap.add_argument("--async-scatter", action="store_true",
                    help="with --shards: pool-based per-group fan-out")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mode == "lm":
        serve_lm(args)
    else:
        serve_retrieval(args)


if __name__ == "__main__":
    main()
