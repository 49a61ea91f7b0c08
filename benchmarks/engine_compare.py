"""Query engines compared: lazy host GCL vs vectorized JAX vs Pallas kernel.

Covers (a) structural containment joins and (b) BM25 top-k — the two hot
query paths — at increasing list sizes.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gcl
from repro.core.annotation import reduce_minimal
from repro.core.vectorized import bm25_topk, contained_in_mask, pack
from repro.kernels import bm25_blockmax_topk, interval_join


def random_gc(rng, n, span):
    s = np.sort(rng.choice(span, size=min(n, span), replace=False))
    e = s + rng.integers(0, 30, size=len(s))
    return reduce_minimal(s, e, np.zeros(len(s)))


def bench_joins(sizes=(1000, 10_000, 100_000), repeats=5):
    print("## containment join A ⊲ B (|B| = |A|/10)")
    print(f"{'|A|':>9s} {'lazy host':>12s} {'vector jnp':>12s} "
          f"{'pallas':>12s}")
    rng = np.random.default_rng(0)
    for n in sizes:
        A = random_gc(rng, n, n * 20)
        B = random_gc(rng, n // 10, n * 20)
        t0 = time.time()
        node = gcl.ContainedIn(gcl.Term(A), gcl.Term(B))
        lazy = node.solutions()
        t_lazy = time.time() - t0

        a_s, a_e, _ = pack(A.starts, A.ends)
        b_s, b_e, _ = pack(B.starts, B.ends)
        f = jax.jit(contained_in_mask)
        f(a_s, a_e, b_s, b_e).block_until_ready()
        t0 = time.time()
        for _ in range(repeats):
            mask = f(a_s, a_e, b_s, b_e).block_until_ready()
        t_vec = (time.time() - t0) / repeats
        assert int(np.asarray(mask).sum()) == len(lazy)

        interval_join(a_s, a_e, b_s, b_e)  # warm
        t0 = time.time()
        m2 = interval_join(a_s, a_e, b_s, b_e)
        jax.block_until_ready(m2)
        t_pl = time.time() - t0
        print(f"{n:9d} {1e3 * t_lazy:10.2f}ms {1e3 * t_vec:10.2f}ms "
              f"{1e3 * t_pl:10.2f}ms")


def bench_bm25(n_docs=200_000, n_terms=4, postings=20_000, repeats=3):
    print(f"\n## BM25 top-10, {n_docs} docs, {n_terms} terms × {postings} "
          f"postings")
    rng = np.random.default_rng(1)
    doc_idx = np.stack([np.sort(rng.choice(n_docs, size=postings,
                                           replace=False))
                        for _ in range(n_terms)]).astype(np.int32)
    impacts = rng.random((n_terms, postings)).astype(np.float32) * 3

    # host numpy
    t0 = time.time()
    for _ in range(repeats):
        acc = np.zeros(n_docs, np.float32)
        for t in range(n_terms):
            np.add.at(acc, doc_idx[t], impacts[t])
        top = np.argpartition(-acc, 10)[:10]
    t_host = (time.time() - t0) / repeats

    # vectorized device scatter-add
    di = jnp.asarray(doc_idx)[None]
    im = jnp.asarray(impacts)[None]
    qm = jnp.ones((1, n_terms), jnp.float32)
    bm25_topk(di, im, qm, n_docs=n_docs, k=10)  # warm
    t0 = time.time()
    for _ in range(repeats):
        s, i = bm25_topk(di, im, qm, n_docs=n_docs, k=10)
        jax.block_until_ready(s)
    t_vec = (time.time() - t0) / repeats

    # block-impact + pallas blockmax
    bs = 256
    nb = -(-n_docs // bs)
    blocked = np.zeros((nb, n_terms, bs), np.float32)     # block-major
    blocked[doc_idx // bs, np.arange(n_terms)[:, None], doc_idx % bs] = impacts
    bmax = blocked.max(axis=2)
    jb, jm = jnp.asarray(blocked), jnp.asarray(bmax)
    bm25_blockmax_topk(jb, jm, k=10)  # warm
    t0 = time.time()
    s2, i2 = bm25_blockmax_topk(jb, jm, k=10)
    jax.block_until_ready(s2)
    t_kernel = time.time() - t0

    np.testing.assert_allclose(np.sort(np.asarray(s)[0])[::-1][:10],
                               np.sort(np.asarray(s2))[::-1][:10], rtol=1e-5)
    print(f"host numpy        {1e3 * t_host:10.2f}ms")
    print(f"vector device     {1e3 * t_vec:10.2f}ms")
    print(f"pallas block-max  {1e3 * t_kernel:10.2f}ms "
          f"({'compiled' if jax.default_backend() == 'tpu' else 'interpreted'})")


def run():
    bench_joins()
    bench_bm25()


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
