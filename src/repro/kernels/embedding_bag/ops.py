"""Jit'd EmbeddingBag with pallas/ref switch and ragged→padded adapter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import embedding_bag_pallas
from .ref import embedding_bag_ref


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def embedding_bag_padded(table, indices, weights, use_pallas: bool = False):
    """Padded-bag embedding lookup.

    table [V, D]; indices [B, L] (0-padded); weights [B, L] (0 on padding).
    The jnp path (default; used by the models and the dry-run) computes
    take + weighted sum; the Pallas path fuses gather and reduce.
    """
    if use_pallas:
        return embedding_bag_pallas(table, indices, weights)
    rows = jnp.take(table, indices, axis=0)           # [B, L, D]
    return jnp.einsum("bld,bl->bd", rows, weights.astype(table.dtype))


def pad_ragged(indices: np.ndarray, offsets: np.ndarray, max_bag: int):
    """Host adapter: CSR-style ragged bags → padded [B, max_bag] + weights."""
    b = len(offsets) - 1
    out = np.zeros((b, max_bag), dtype=np.int32)
    w = np.zeros((b, max_bag), dtype=np.float32)
    for i in range(b):
        lo, hi = offsets[i], min(offsets[i + 1], offsets[i] + max_bag)
        n = hi - lo
        out[i, :n] = indices[lo:hi]
        w[i, :n] = 1.0
    return out, w
