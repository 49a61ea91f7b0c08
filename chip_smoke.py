"""Smoke run of the served retrieval path on one TPU chip.

Builds the README's deployment, a ``ShardedWarren`` of 4 groups × 2
replicas with async scatter, and ingests a seeded corpus shaped like MS
MARCO passages (about 60 words each).  It serves seeded queries of 2–6
terms through ``RetrievalServer``'s batcher and checks every top-10
against exhaustive host BM25 (``core.ranking.score_bm25``) on the same
snapshot, before and after a commit.  Then it runs the compiled block-max
kernel for one query and checks it against the host too.

    python chip_smoke.py [--docs 40000] [--seed 0]

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU, or if any phase fails or disagrees, it exits non-zero and prints no
such line.
"""

import argparse
import json
import sys
import time
from pathlib import Path

K = 10
RTOL = 1e-5
NEW_DOCS = 8           # passages in the one transaction committed mid-run
N_QUERIES = 64
TIMEOUT_S = 600.0      # per query; warm-up compiles inside it
# a micro-batch waits this long to fill: the whole stream is submitted well
# inside it, so batches are cut by size and every pass scores the same
# batches, the shape buckets of the judged passes included
BATCH_WAIT_MS = 200.0
# MS MARCO passage ranking has 8.8M passages; the host indexes ~200
# passages/s, so the run keeps a slice of the collection (ROADMAP S1, R2)
MSMARCO_PASSAGES = 8_841_823


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def same_top_k(got, ranked) -> bool:
    """``got``, a served top-K, against ``ranked``, host BM25's whole
    ranking: every rank's score equals the host's at that rank, and every
    served document scores the same on the host, so an id that differs
    from the host's at some rank is a tie there."""
    import numpy as np
    host = dict(ranked)
    want = ranked[:K]
    return (len(got) == len(want) == len(dict(got)) and all(
        np.isclose(gs, ws, rtol=RTOL, atol=0.0) and gd in host
        and np.isclose(host[gd], gs, rtol=RTOL, atol=0.0)
        for (gd, gs), (_, ws) in zip(got, want)))


def smoke_queries(seed: int, n: int) -> list:
    """``n`` queries of 2 to 6 distinct words, each drawn uniformly from
    the synthetic corpus's vocabulary.  The number of terms is MS MARCO's
    query length; the choice of terms follows no real query log, and every
    word is common, so nearly every posting list spans the corpus."""
    import numpy as np

    from repro.data.synth import WORDS
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(WORDS, size=int(rng.integers(2, 7)),
                                replace=False)) for _ in range(n)]


def serve_and_check(server, warren, queries) -> dict:
    """Warm every shape bucket the query stream reaches, then serve it
    again concurrently and compare each answer with host BM25 on the same
    snapshot.  Returns the phase's counts."""
    from repro import obs
    from repro.core.ranking import collection_stats, score_bm25

    # one count per device shape bucket the server scores (one compile each)
    recompiles = obs.registry().counter("serve_jit_recompile_total")
    t0 = time.perf_counter()
    before = recompiles.value
    # warm until a pass over the stream compiles nothing new
    for _ in range(3):
        seen = recompiles.value
        for h in [server.batcher.submit(q) for q in queries]:
            h.get(timeout=TIMEOUT_S)
        if recompiles.value == seen:
            break
    warm_s = time.perf_counter() - t0
    warm_buckets = recompiles.value - before

    judged_from = recompiles.value
    t0 = time.perf_counter()
    handles = [server.batcher.submit(q) for q in queries]
    served = [h.get(timeout=TIMEOUT_S) for h in handles]
    serve_s = time.perf_counter() - t0
    with warren:
        stats = collection_stats(warren)
        want = [score_bm25(warren, q, k=stats.n_docs, stats=stats)
                for q in queries]
    bad = [q for q, g, w in zip(queries, served, want) if not same_top_k(g, w)]
    return {"n_docs": stats.n_docs, "warm_s": warm_s,
            "warm_buckets": warm_buckets, "serve_s": serve_s,
            "served": len(served), "mismatches": len(bad),
            "judged_recompiles": recompiles.value - judged_from,
            "example": bad[:1]}


def blockmax_check(warren, query: str, platform: str) -> dict:
    """The block-max kernel for one query on its block-impact layout,
    against host BM25; on a TPU the lowered program must hold the Mosaic
    kernel (``tpu_custom_call``), not its interpretation."""
    import jax.numpy as jnp

    from repro.core.ranking import (block_impact_array, build_block_impacts,
                                    collection_stats, ranking_tokens,
                                    score_bm25)
    from repro.kernels import bm25_blockmax_topk

    with warren:
        stats = collection_stats(warren)
        terms = list(dict.fromkeys(ranking_tokens(query)))
        bidx = build_block_impacts(warren, terms, block_size=128, stats=stats)
        want = score_bm25(warren, query, k=stats.n_docs, stats=stats)
    impacts = block_impact_array(bidx)
    imp, bmax = jnp.asarray(impacts), jnp.asarray(impacts.max(axis=2))
    compiled = "tpu_custom_call" in bm25_blockmax_topk.lower(
        imp, bmax, k=K).as_text()
    check(compiled == (platform == "tpu"),
          f"block-max kernel compiled={compiled} on {platform}")
    scores, ids = bm25_blockmax_topk(imp, bmax, k=K)
    got = [(int(bidx.doc_starts[i]), float(s))
           for i, s in zip(ids.tolist(), scores.tolist()) if s > 0]
    return {"shape": tuple(impacts.shape), "compiled": compiled,
            "agrees": same_top_k(got, want)}


def run(docs: int = 40_000, n_queries: int = N_QUERIES, seed: int = 0,
        platform: str = "tpu") -> dict:
    """Drive every phase once on ``platform``; raise on any failure."""
    import jax

    from repro.core.ranking import DOC_FEATURE, TF_PREFIX, ingest_documents
    from repro.core.stemmer import porter_stem
    from repro.data.synth import WORDS, doc_generator
    from repro.dist.shard_router import ShardedWarren
    from repro.train.serve import BatcherConfig, RetrievalServer

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device['kind']} ({device['platform']}) "
          f"x{device['count']}", flush=True)
    check(dev.platform == platform,
          f"expected a {platform} device, JAX found {dev.platform}")
    print(f"scale: {docs} passages of MS MARCO's {MSMARCO_PASSAGES} "
          f"(cut: host ingest ~200 passages/s bounds the run; "
          f"ROADMAP S1/R2)", flush=True)

    # backend compiles (persistent-cache reads included) per jitted function
    compiles: dict = {}
    cache_hits = [0]

    def on_duration(event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n, total = compiles.get(fun_name, (0, 0.0))
            compiles[fun_name] = (n + 1, total + secs)

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    warren = ShardedWarren(n_shards=4, replicas=2, async_scatter=True)
    server = None
    try:
        t0 = time.perf_counter()
        n = ingest_documents(warren, doc_generator(seed, docs, mean_len=60))
        ingest_s = time.perf_counter() - t0
        with warren:
            per_group = warren.map_groups(
                lambda w: len(w.annotations(DOC_FEATURE)))
        check(n == docs == sum(per_group),
              f"ingested {n} of {docs} passages, groups hold {per_group}")
        print(f"ingest: {n} passages over {len(per_group)} groups x 2 "
              f"replicas {per_group} in {ingest_s:.3f}s "
              f"({n / ingest_s:.1f}/s)", flush=True)

        # exact BM25: no posting list may be cut, the commit's included
        with warren:
            max_df = max(len(warren.annotations(TF_PREFIX + porter_stem(w)))
                         for w in WORDS)
        max_postings = -(-(max_df + NEW_DOCS) // 256) * 256
        server = RetrievalServer(
            warren, k=K, max_postings=max_postings,
            batcher=BatcherConfig(max_wait_ms=BATCH_WAIT_MS))
        print(f"server: k={K}, max_postings={max_postings} "
              f"(largest df {max_df})", flush=True)
        queries = smoke_queries(seed, n_queries)

        def serve_phase(phase: str) -> dict:
            r = serve_and_check(server, warren, queries)
            print(f"{phase}: docs={r['n_docs']} warm-up {r['warm_s']:.3f}s "
                  f"({r['warm_buckets']} shape buckets), "
                  f"served={r['served']} in {r['serve_s']:.3f}s, "
                  f"mismatches={r['mismatches']}, "
                  f"recompiles while judged={r['judged_recompiles']}",
                  flush=True)
            check(r["served"] == n_queries and r["mismatches"] == 0,
                  f"{phase}: {r['mismatches']} of {r['served']} top-{K} "
                  f"differ from score_bm25, e.g. {r['example']}")
            check(r["judged_recompiles"] == 0,
                  f"{phase}: {r['judged_recompiles']} compiles after "
                  f"warm-up; a query could time out on one")
            return r

        serve_phase("before_commit")
        added = ingest_documents(
            warren, doc_generator(seed + 1, NEW_DOCS, mean_len=60),
            batch=NEW_DOCS)
        check(added == NEW_DOCS, f"committed {added} of {NEW_DOCS}")
        print(f"commit: one transaction of {added} passages", flush=True)
        after = serve_phase("after_commit")
        check(after["n_docs"] == docs + NEW_DOCS,
              "the commit is not visible to the reference")
        from repro import obs
        total = obs.registry().counter("serve_jit_recompile_total").value
        print(f"serve_jit_recompile_total={total:g}", flush=True)

        bm = blockmax_check(warren, queries[0], platform)
        print(f"block-max kernel: impacts {bm['shape']}, "
              f"compiled={bm['compiled']}, agrees with host={bm['agrees']}",
              flush=True)
        check(bm["agrees"], "block-max kernel top-10 differs from host BM25")
    finally:
        if server is not None:
            server.close()
        warren.close()

    by_fn = ", ".join(f"{name} {n}x {secs:.3f}s" for name, (n, secs) in
                      sorted(compiles.items(), key=lambda kv: -kv[1][1]))
    print(f"compiles: {sum(n for n, _ in compiles.values())} backend "
          f"compiles in {sum(s for _, s in compiles.values()):.3f}s "
          f"({by_fn}); persistent cache hits {cache_hits[0]}", flush=True)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak_bytes_in_use={stats['peak_bytes_in_use']}", flush=True)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=40_000,
                    help="passages to ingest (MS MARCO has 8.8M)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    check(args.docs >= 1, "nothing to run")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    device = run(args.docs, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
