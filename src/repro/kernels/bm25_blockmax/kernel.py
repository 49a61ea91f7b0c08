"""Block-Max BM25 Pallas TPU kernel (paper §2.2, Ding & Suel 2011 adapted).

TPU adaptation of Block-Max WAND (DESIGN §2): the CPU algorithm moves one
pivot pointer and skips compressed blocks; a TPU wants regular tiles.  The
doc space is cut into BS-doc blocks, laid out block-major as [NB, T, BS] so
one grid step reads one whole (T, BS) tile.  The per-block upper bound
Σ_t max_t arrives by scalar prefetch, next to θ.  A cheap pre-pass (ops.py)
scores only the highest-UB blocks to establish a top-k threshold θ; the
kernel then sweeps all blocks and *skips the scoring arithmetic* of any
block whose upper bound is < θ (`@pl.when`), writing -inf instead.

The pruning is *conservative* (θ from a subset of true scores), so the
final top-k equals the exhaustive oracle exactly.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call

NEG_INF = float("-inf")


def _blockmax_kernel(theta_ref, ub_ref, impacts_ref, o_ref):
    # theta [1] and ub [NB] in SMEM; impacts (T, BS) of this block; out (1, BS)
    j = pl.program_id(0)
    # θ comes from a subset of true scores, so θ <= true kth-best; a block
    # at ub == θ may still hold a doc scoring exactly kth-best (the probe
    # pre-pass hits this whenever it scored the top block itself), so only
    # strictly-below blocks may be skipped.
    keep = ub_ref[j] >= theta_ref[0]

    @pl.when(keep)
    def _():
        o_ref[...] = jnp.sum(impacts_ref[...], axis=0, keepdims=True)

    @pl.when(jnp.logical_not(keep))
    def _():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)


def blockmax_scores_pallas(impacts, ub, theta):
    """impacts [NB, T, BS], ub [NB] per-block bounds, theta scalar →
    scores [NB, BS] with pruned blocks = -inf."""
    nb, t, bs = impacts.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[pl.BlockSpec((None, t, bs), lambda j, *_: (j, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, bs), lambda j, *_: (j, 0, 0)),
    )
    out = pallas_call(
        _blockmax_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, 1, bs), jnp.float32),
    )(jnp.asarray(theta, jnp.float32).reshape(1), ub.astype(jnp.float32),
      impacts)
    return out.reshape(nb, bs)
