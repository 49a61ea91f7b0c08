"""Flash-decoding GQA attention Pallas kernel.

Decode shape: one query token per sequence against a long KV cache — the
memory-bound regime of `decode_32k` / `long_500k`.  The kernel streams KV in
BS-sized tiles (grid innermost dim), maintaining the online-softmax running
max m, normalizer l, and accumulator in VMEM scratch; the G query heads
sharing one KV head are processed together so each KV tile is read once for
all of them (the GQA arithmetic-intensity win: G MACs per KV byte).

KV tiles beyond the valid `length` are skipped entirely with `@pl.when` —
the kernel's analogue of not launching work for unused cache (and on
hardware, of skipping the DMA).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs, scale):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0, 0]
    base = j * bs

    @pl.when(base < length)
    def _():
        q = q_ref[0, 0]                    # [G, D]
        k = k_ref[0, :, 0, :]              # [BS, D]
        v = v_ref[0, :, 0, :]              # [BS, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, BS]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]                # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)             # [G, BS]
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def gqa_decode_pallas(q, k, v, length, *, block_size: int = 512):
    """q [B, Hkv, G, D]; k/v [B, S, Hkv, D]; length [B] → [B, Hkv, G, D]."""
    b, hkv, g, d = q.shape
    s = k.shape[1]
    bs = min(block_size, s)
    n_blocks = -(-s // bs)
    s_pad = n_blocks * bs
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    scale = 1.0 / (d ** 0.5)
    length2 = length.astype(jnp.int32).reshape(b, 1)

    kernel = functools.partial(_decode_kernel, bs=bs, scale=scale)
    return pallas_call(
        kernel,
        grid=(b, hkv, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, h, j: (i, 0)),
            pl.BlockSpec((1, 1, g, d), lambda i, h, j: (i, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda i, h, j: (i, j, h, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda i, h, j: (i, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda i, h, j: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )(length2, q, k, v)
