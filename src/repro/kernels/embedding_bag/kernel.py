"""EmbeddingBag Pallas TPU kernel: fused gather + in-register reduce.

Recsys hot path (DLRM/xDeepFM/two-tower): many small bags gathered from a
huge table.  JAX's take+segment_sum materializes the [N, D] gathered rows in
HBM; this kernel keeps the accumulation in VMEM, reading each row once and
never writing the intermediate.

Bag boundaries arrive as scalar-prefetch operands (offsets), so the grid and
DMA pattern are known before the kernel body runs — the Pallas TPU idiom for
data-dependent gathers.  Rows are fetched with dynamic slices on the sublane
axis (one row per loop step); bags are padded to `max_bag` items with index
0 / weight 0.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call


def _bag_kernel(idx_ref, w_ref, table_ref, o_ref, *, max_bag):
    # idx_ref [B, max_bag] (SMEM, scalar prefetch); table [V, D]; out [1, D]
    b = pl.program_id(0)

    def body(i, acc):
        row_id = idx_ref[b, i]
        w = w_ref[b, i]
        row = table_ref[pl.ds(row_id, 1), :]                           # [1, D]
        return acc + w * row[0].astype(jnp.float32)

    acc = jax.lax.fori_loop(0, max_bag,  body,
                            jnp.zeros((o_ref.shape[-1],), jnp.float32))
    o_ref[0, :] = acc.astype(o_ref.dtype)


def embedding_bag_pallas(table, indices, weights):
    """table [V, D]; indices [B, max_bag] int32 (0-padded);
    weights [B, max_bag] f32 (0 where padded) → [B, D]."""
    bsz, max_bag = indices.shape
    v, d = table.shape
    kernel = functools.partial(_bag_kernel, max_bag=max_bag)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((v, d), lambda b, *_: (0, 0))],
        out_specs=pl.BlockSpec((1, d), lambda b, *_: (b, 0)),
    )
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, d), table.dtype),
    )(indices.astype(jnp.int32), weights.astype(jnp.float32), table)
