"""Pure-jnp oracle: exhaustive BM25 scoring over the block-impact layout."""

import jax


def bm25_score_ref(impacts):
    """impacts [NB, T, BS] → scores [NB * BS] (sum over terms, no pruning)."""
    return impacts.sum(axis=1).reshape(-1)


def bm25_topk_ref(impacts, k: int):
    scores = bm25_score_ref(impacts)
    return jax.lax.top_k(scores, k)
