"""Jit'd Block-Max BM25 top-k: θ pre-pass + pruned kernel sweep + final top-k."""

import functools

import jax
import jax.numpy as jnp

from .kernel import blockmax_scores_pallas
from .ref import bm25_topk_ref


def _top_k(x, k: int):
    """``lax.top_k`` of a 1-D array, taken over a [1, N] view: for a TPU
    the compiler spends tens of seconds on a rank-1 top_k of 2^15 or more
    elements, and about a second on the same length as one row."""
    values, idx = jax.lax.top_k(x.reshape(1, -1), k)
    return values[0], idx[0]


@functools.partial(jax.jit, static_argnames=("k", "use_pallas",
                                             "probe_blocks"))
def bm25_blockmax_topk(impacts, block_max, k: int, use_pallas: bool = True,
                       probe_blocks: int = None):
    """Top-k docs by BM25 with block-max pruning.

    impacts    [NB, T, BS] block-major impact layout (0 where term absent)
    block_max  [NB, T]     per-(block, term) maxima
    Returns (scores [k], flat_doc_ids [k]); exact (pruning is conservative).
    """
    nb, t, bs = impacts.shape
    if not use_pallas:
        return bm25_topk_ref(impacts, k)

    # --- θ pre-pass: exactly score the highest-UB blocks ----------------- #
    probe = probe_blocks or max(1, min(nb, -(-k // bs) * 2))
    ub = block_max.sum(axis=1)                       # [NB]
    _, best_blocks = _top_k(ub, probe)               # indices of probe blocks
    probe_imp = jnp.take(impacts, best_blocks, axis=0)   # [probe, T, BS]
    probe_scores = probe_imp.sum(axis=1).reshape(-1)     # [probe * BS]
    # kth over a SUBSET of true scores is <= the true kth-best, so pruning
    # on it is safe.  Lowering it by the rounding bound of a T-term float
    # sum keeps that true when ub and the scores are summed in different
    # orders.
    kth = _top_k(probe_scores, min(k, probe * bs))[0][-1]
    theta = kth * (1.0 - 2 * t * jnp.finfo(jnp.float32).eps)

    # --- pruned sweep ----------------------------------------------------- #
    scores = blockmax_scores_pallas(impacts, ub, theta)  # [NB, BS]
    # pruned blocks carry -inf; clamp to the true score floor (impacts are
    # non-negative) so a top-k that spills past the last positive doc reads
    # 0 exactly like the exhaustive oracle
    scores = jnp.maximum(scores, 0.0)
    return _top_k(scores.reshape(-1), k)


def pruned_fraction(block_max, theta) -> jnp.ndarray:
    """Diagnostic: fraction of blocks the kernel skips at threshold θ."""
    ub = block_max.sum(axis=1)
    return jnp.mean((ub < theta).astype(jnp.float32))
