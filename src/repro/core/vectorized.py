"""Vectorized (TPU-native) GCL algebra: batched array programs in JAX.

The lazy engine (gcl.py) chases one cursor at a time — ideal on a CPU,
hostile to a TPU.  Here the same operators are re-derived as dense array
programs over struct-of-arrays GC-lists:

  * τ/ρ become `searchsorted` over the starts/ends arrays (vmap-able),
  * containment operators become masks computed with one searchsorted probe
    per element (O(n log m), fully parallel),
  * combination operators materialize a *candidate* solution per input
    element (each candidate provably a solution; every minimal solution is a
    candidate) followed by a parallel G-reduction,
  * G-reduction = sort + suffix-min masking (no data-dependent shapes:
    everything returns fixed-size arrays + validity masks).

Padding convention: entries with start == PAD (= int32 max) are invalid.
Lists are int32 on device; segment-local coordinates (< 2^31) by
construction — the host index rebases segments before overflow (DESIGN §2).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAD = np.int32(np.iinfo(np.int32).max)


def pack(starts, ends, values=None, size: int = None):
    """Host → device: pad a GC-list to `size` entries."""
    n = len(starts)
    size = size or max(n, 1)
    s = np.full(size, PAD, dtype=np.int32)
    e = np.full(size, PAD, dtype=np.int32)
    v = np.zeros(size, dtype=np.float32)
    s[:n] = starts
    e[:n] = ends
    if values is not None:
        v[:n] = values
    return jnp.asarray(s), jnp.asarray(e), jnp.asarray(v)


def unpack(s, e, v=None) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    s, e = np.asarray(s), np.asarray(e)
    keep = s != PAD
    vv = np.asarray(v)[keep] if v is not None else np.zeros(keep.sum())
    return s[keep], e[keep], vv


# --------------------------------------------------------------------- #
# access methods: batched τ/ρ
# --------------------------------------------------------------------- #
def tau(starts, ends, k):
    """Batched τ: first annotation with start >= k (k may be an array)."""
    i = jnp.searchsorted(starts, k, side="left")
    i = jnp.minimum(i, starts.shape[0] - 1)
    s, e = starts[i], ends[i]
    ok = s >= k
    return jnp.where(ok, s, PAD), jnp.where(ok, e, PAD)


def rho(starts, ends, k):
    i = jnp.searchsorted(ends, k, side="left")
    i = jnp.minimum(i, ends.shape[0] - 1)
    s, e = starts[i], ends[i]
    ok = e >= k
    return jnp.where(ok, s, PAD), jnp.where(ok, e, PAD)


# --------------------------------------------------------------------- #
# G-reduction: parallel minimality mask over candidate intervals
# --------------------------------------------------------------------- #
def g_reduce_mask(s, e):
    """Given candidate intervals (PAD-padded), return (s, e, keep_mask) with
    the surviving minimal intervals, sorted by start.

    Sorting key pushes PAD entries to the tail.  Equal (p,q) duplicates keep
    one representative (the first after a stable sort)."""
    order = jnp.lexsort((e, s))
    s, e = s[order], e[order]
    n = s.shape[0]
    valid = s != PAD
    # drop exact duplicates
    dup = jnp.concatenate([jnp.zeros(1, bool),
                           (s[1:] == s[:-1]) & (e[1:] == e[:-1])])
    # equal-start run: keep first (others contain it)
    eq_start = jnp.concatenate([jnp.zeros(1, bool), s[1:] == s[:-1]])
    # contains a later-starting interval iff e >= suffix-min of later ends
    e_for_min = jnp.where(valid & ~dup, e, PAD)
    suffix_min = jax.lax.cummin(e_for_min[::-1])[::-1]
    nxt = jnp.concatenate([suffix_min[1:], jnp.full(1, PAD, suffix_min.dtype)])
    keep = valid & ~dup & ~eq_start & (e < nxt)
    return s, e, keep, order


# --------------------------------------------------------------------- #
# containment operators: masks over A
# --------------------------------------------------------------------- #
def contained_in_mask(a_s, a_e, b_s, b_e):
    """mask[i]: A[i] ⊑ some B[j].  First B ending >= A.end must start <= A.start."""
    j = jnp.searchsorted(b_e, a_e, side="left")
    j = jnp.minimum(j, b_e.shape[0] - 1)
    ok = (b_e[j] >= a_e) & (b_s[j] <= a_s) & (b_s[j] != PAD)
    return ok & (a_s != PAD)


def containing_mask(a_s, a_e, b_s, b_e):
    """mask[i]: A[i] ⊒ some B[j].  First B starting >= A.start must end <= A.end."""
    j = jnp.searchsorted(b_s, a_s, side="left")
    j = jnp.minimum(j, b_s.shape[0] - 1)
    ok = (b_s[j] >= a_s) & (b_e[j] <= a_e) & (b_s[j] != PAD)
    return ok & (a_s != PAD)


def _apply_mask(a_s, a_e, a_v, mask):
    s = jnp.where(mask, a_s, PAD)
    e = jnp.where(mask, a_e, PAD)
    v = jnp.where(mask, a_v, 0.0)
    order = jnp.argsort(s)
    return s[order], e[order], v[order]


def contained_in(a_s, a_e, a_v, b_s, b_e):
    return _apply_mask(a_s, a_e, a_v, contained_in_mask(a_s, a_e, b_s, b_e))


def containing(a_s, a_e, a_v, b_s, b_e):
    return _apply_mask(a_s, a_e, a_v, containing_mask(a_s, a_e, b_s, b_e))


def not_contained_in(a_s, a_e, a_v, b_s, b_e):
    m = (~contained_in_mask(a_s, a_e, b_s, b_e)) & (a_s != PAD)
    return _apply_mask(a_s, a_e, a_v, m)


def not_containing(a_s, a_e, a_v, b_s, b_e):
    m = (~containing_mask(a_s, a_e, b_s, b_e)) & (a_s != PAD)
    return _apply_mask(a_s, a_e, a_v, m)


# --------------------------------------------------------------------- #
# combination operators: candidates + parallel G-reduce
# --------------------------------------------------------------------- #
def _rho_b(b_s, b_e, k):
    """Backward ρ: last B with end <= k; PAD-aware (PAD entries sort high)."""
    j = jnp.searchsorted(b_e, k, side="right") - 1
    ok = j >= 0
    j = jnp.maximum(j, 0)
    s = jnp.where(ok, b_s[j], PAD)
    e = jnp.where(ok, b_e[j], PAD)
    return s, e


def both_of(a_s, a_e, b_s, b_e):
    """A △ B.  Candidates: for each a: (min(a.p, ρ'_B(a.q).p), a.q), plus the
    symmetric set anchored at B (DESIGN §2 / gcl.BothOf derivation)."""
    def anchored(x_s, x_e, y_s, y_e):
        ys, ye = _rho_b(y_s, y_e, x_e)
        ok = (x_s != PAD) & (ys != PAD)
        cs = jnp.minimum(x_s, ys)
        return jnp.where(ok, cs, PAD), jnp.where(ok, x_e, PAD)

    ca_s, ca_e = anchored(a_s, a_e, b_s, b_e)
    cb_s, cb_e = anchored(b_s, b_e, a_s, a_e)
    s = jnp.concatenate([ca_s, cb_s])
    e = jnp.concatenate([ca_e, cb_e])
    s, e, keep, _ = g_reduce_mask(s, e)
    return jnp.where(keep, s, PAD), jnp.where(keep, e, PAD)


def one_of(a_s, a_e, b_s, b_e):
    s = jnp.concatenate([a_s, b_s])
    e = jnp.concatenate([a_e, b_e])
    s, e, keep, _ = g_reduce_mask(s, e)
    return jnp.where(keep, s, PAD), jnp.where(keep, e, PAD)


def followed_by(a_s, a_e, b_s, b_e):
    """A ◇ B: for each b, pair with the last A ending < b.p."""
    as_, ae_ = _rho_b(a_s, a_e, b_s - 1)
    ok = (b_s != PAD) & (as_ != PAD)
    cs = jnp.where(ok, as_, PAD)
    ce = jnp.where(ok, b_e, PAD)
    s, e, keep, _ = g_reduce_mask(cs, ce)
    return jnp.where(keep, s, PAD), jnp.where(keep, e, PAD)


# --------------------------------------------------------------------- #
# batched BM25 scoring (dense scatter-add path; the Pallas kernel offers
# the block-max pruned variant)
# --------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("n_docs", "k"))
def bm25_topk(doc_idx, impacts, qmask, n_docs: int, k: int):
    """Batched exhaustive BM25: a float32 scatter-add of every impact into
    one ``[n_docs]`` accumulator per query, then ``top_k`` of each.  The
    rank of ``doc_idx`` selects the input form.

    Postings form, one padded row per (query, term):
      doc_idx  [Q, T, L] int32 padded with n_docs (scatter drop)
      impacts  [Q, T, L] f32, zero where padded
      qmask    [Q, T]    f32 per-query term weights (0 = absent term)

    Compact form, every posting of the batch in one flat block:
      doc_idx  [P] int32, ``q * n_docs + d`` for document d of query slot
               q; padded with ``Q * n_docs`` (scatter drop)
      impacts  [P] f32, already weighted
      qmask    [Q, 1] f32, each query slot's weight (0 = empty slot)

    Both lay out a query's accumulator the same way, so equal scores
    come out lower document index first in either.
    returns  (scores [Q, k], ids [Q, k])
    """
    if doc_idx.ndim == 1:
        qp = qmask.shape[0]
        acc = jnp.zeros((qp * n_docs,), jnp.float32)
        acc = acc.at[doc_idx].add(impacts, mode="drop")
        return jax.lax.top_k(acc.reshape(qp, n_docs) * qmask, k)

    def per_query(di, im, qm):
        acc = jnp.zeros((n_docs,), jnp.float32)
        contrib = (im * qm[:, None]).reshape(-1)
        acc = acc.at[di.reshape(-1)].add(contrib, mode="drop")
        return jax.lax.top_k(acc, k)

    return jax.vmap(per_query)(doc_idx, impacts, qmask)
