"""Containment-join Pallas TPU kernel.

TPU adaptation (DESIGN §2): the lazy engine's per-cursor galloping search is
pointer chasing — fast on a Xeon, serial on a TPU.  Binary search *could* be
vectorized, but data-dependent gathers are slow on the VPU.  Instead each
(A-tile × B-tile) pair is tested with a dense [TA, TB] comparison — pure
vector compares + reductions at ~arithmetic peak — and tiles of B whose
address range cannot overlap the A-tile are skipped via `@pl.when`
(block-level skipping: the same asymptotic win WAND gets from galloping,
at tile granularity).

Grid: (n_a_tiles, n_b_tiles), B innermost so the output tile accumulates in
place across B-tiles.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_call


def _join_kernel(a_s_ref, a_e_ref, b_s_ref, b_e_ref, o_ref, *, mode, pad):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    a_s = a_s_ref[...]          # [1, TA]
    a_e = a_e_ref[...]
    b_s = b_s_ref[...]          # [1, TB]
    b_e = b_e_ref[...]

    # tile-skip test: overlap of [min(a_s), max(a_e)] with [min(b_s), max(b_e)]
    a_valid = a_s != pad
    b_valid = b_s != pad
    a_lo = jnp.min(jnp.where(a_valid, a_s, pad))
    a_hi = jnp.max(jnp.where(a_valid, a_e, -pad))
    b_lo = jnp.min(jnp.where(b_valid, b_s, pad))
    b_hi = jnp.max(jnp.where(b_valid, b_e, -pad))
    # containment of a in b needs b_s <= a_s and a_e <= b_e: a B-tile is
    # relevant only if its span can bracket part of the A-tile span.
    relevant = (b_lo <= a_hi) & (b_hi >= a_lo)

    @pl.when(relevant)
    def _():
        if mode == "contained_in":
            cmp = (b_s[0][None, :] <= a_s[0][:, None]) & \
                  (a_e[0][:, None] <= b_e[0][None, :])
        else:  # containing
            cmp = (a_s[0][:, None] <= b_s[0][None, :]) & \
                  (b_e[0][None, :] <= a_e[0][:, None])
        cmp = cmp & b_valid[0][None, :] & a_valid[0][:, None]
        hit = jnp.any(cmp, axis=1).astype(jnp.int32)
        o_ref[...] = jnp.maximum(o_ref[...], hit[None, :])


def interval_join_pallas(a_s, a_e, b_s, b_e, *, mode: str = "contained_in",
                         tile_a: int = 256, tile_b: int = 256,
                         pad: int = None):
    """Returns int32 mask[NA]: 1 where A[i] is contained in (contains) some B."""
    from repro.core.vectorized import PAD
    pad = int(PAD if pad is None else pad)
    na, nb = a_s.shape[0], b_s.shape[0]
    na_p = -(-na // tile_a) * tile_a
    nb_p = -(-nb // tile_b) * tile_b

    def padto(x, n):
        return jnp.pad(x, (0, n - x.shape[0]), constant_values=pad)[None, :]

    a_s2, a_e2 = padto(a_s, na_p), padto(a_e, na_p)
    b_s2, b_e2 = padto(b_s, nb_p), padto(b_e, nb_p)

    grid = (na_p // tile_a, nb_p // tile_b)
    out = pallas_call(
        lambda *refs: _join_kernel(*refs, mode=mode, pad=pad),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_a), lambda i, j: (0, i)),
            pl.BlockSpec((1, tile_a), lambda i, j: (0, i)),
            pl.BlockSpec((1, tile_b), lambda i, j: (0, j)),
            pl.BlockSpec((1, tile_b), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, tile_a), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, na_p), jnp.int32),
    )(a_s2, a_e2, b_s2, b_e2)
    return out[0, :na]
