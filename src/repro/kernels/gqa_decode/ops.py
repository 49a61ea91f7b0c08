"""Jit'd GQA decode attention with pallas/ref switch."""

import functools

import jax

from .kernel import gqa_decode_pallas
from .ref import gqa_decode_ref


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_size"))
def gqa_decode(q, k, v, length, use_pallas: bool = True,
               block_size: int = 512):
    """q [B, Hkv, G, D]; k/v [B, S, Hkv, D]; length [B] → [B, Hkv, G, D]."""
    if use_pallas:
        return gqa_decode_pallas(q, k, v, length, block_size=block_size)
    return gqa_decode_ref(q, k, v, length).astype(q.dtype)
