"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps.

On CPU arrays every kernel runs through the Pallas interpreter
(``repro.kernels.platform``); ``test_tpu_compile.py`` compiles the served
ones for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.annotation import AnnotationList, reduce_minimal
from repro.core.vectorized import PAD, pack
from repro.kernels import (bm25_blockmax_topk, bm25_topk_ref,
                           embedding_bag_padded, embedding_bag_ref,
                           gqa_decode, gqa_decode_ref, interval_join)
from repro.kernels.interval_join.ref import (contained_in_mask_ref,
                                             containing_mask_ref)


def random_gc_list(rng, n, span=10_000):
    starts = np.sort(rng.choice(span, size=n, replace=False)).astype(np.int64)
    ends = starts + rng.integers(0, 50, size=n)
    lst = reduce_minimal(starts, ends, np.zeros(n))
    return lst


# ------------------------------------------------------------------ #
@pytest.mark.parametrize("na,nb", [(16, 16), (100, 37), (513, 257), (1000, 3)])
@pytest.mark.parametrize("mode", ["contained_in", "containing"])
def test_interval_join_sweep(na, nb, mode):
    rng = np.random.default_rng(na * 1000 + nb + len(mode))
    A = random_gc_list(rng, na)
    B = random_gc_list(rng, nb)
    a_s, a_e, _ = pack(A.starts, A.ends)
    b_s, b_e, _ = pack(B.starts, B.ends)
    got = interval_join(a_s, a_e, b_s, b_e, mode=mode, use_pallas=True)
    ref_fn = contained_in_mask_ref if mode == "contained_in" else containing_mask_ref
    want = ref_fn(a_s, a_e, b_s, b_e)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_interval_join_matches_lazy_engine():
    from repro.core import gcl
    rng = np.random.default_rng(7)
    A = random_gc_list(rng, 200, span=2000)
    B = random_gc_list(rng, 50, span=2000)
    node = gcl.ContainedIn(gcl.Term(A), gcl.Term(B))
    lazy = {(p, q) for p, q, _ in node.solutions()}
    a_s, a_e, _ = pack(A.starts, A.ends)
    b_s, b_e, _ = pack(B.starts, B.ends)
    mask = np.asarray(interval_join(a_s, a_e, b_s, b_e, mode="contained_in"))
    got = {(int(A.starts[i]), int(A.ends[i])) for i in np.flatnonzero(mask[:len(A)])}
    assert got == lazy


# ------------------------------------------------------------------ #
@pytest.mark.parametrize("t,nb,bs,k", [(4, 8, 128, 10), (8, 32, 128, 25),
                                       (2, 4, 256, 5), (16, 16, 128, 100)])
def test_bm25_blockmax_sweep(t, nb, bs, k):
    rng = np.random.default_rng(t * 100 + nb)
    # sparse block-major impacts: ~10% fill
    impacts = rng.random((nb, t, bs), dtype=np.float32)
    impacts *= rng.random((nb, t, bs)) < 0.1
    block_max = impacts.max(axis=2)
    got_s, got_i = bm25_blockmax_topk(jnp.asarray(impacts),
                                      jnp.asarray(block_max), k=k)
    want_s, want_i = bm25_topk_ref(jnp.asarray(impacts), k)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-5, atol=1e-6)
    # ids may differ on exact ties; scores must match as multisets
    assert set(np.asarray(got_i)[np.asarray(got_s) > 0]) == \
           set(np.asarray(want_i)[np.asarray(want_s) > 0])


def test_bm25_blockmax_prunes():
    from repro.kernels import pruned_fraction
    rng = np.random.default_rng(0)
    t, nb, bs = 4, 64, 128
    impacts = rng.random((nb, t, bs), dtype=np.float32)
    impacts *= rng.random((nb, t, bs)) < 0.05
    # a few hot blocks
    impacts[:2] *= 10
    block_max = impacts.max(axis=2)
    s, _ = bm25_blockmax_topk(jnp.asarray(impacts), jnp.asarray(block_max), k=5)
    theta = float(s[-1])
    frac = float(pruned_fraction(jnp.asarray(block_max), theta))
    assert frac > 0.3, f"expected meaningful pruning, got {frac}"


# ------------------------------------------------------------------ #
# degenerate shapes: the failure modes happy-path sweeps never reach
# ------------------------------------------------------------------ #
def _bm25_parity(impacts, k):
    """Pallas vs oracle: exact positive scores, tie-tolerant ids."""
    impacts = jnp.asarray(impacts)
    got_s, got_i = bm25_blockmax_topk(impacts, impacts.max(axis=2), k=k)
    want_s, want_i = bm25_topk_ref(impacts, k)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-5, atol=1e-6)
    assert set(np.asarray(got_i)[np.asarray(got_s) > 0]) == \
           set(np.asarray(want_i)[np.asarray(want_s) > 0])


def test_bm25_blockmax_empty_posting_list():
    """All-zero impacts (no term hits anything): no -inf junk, all zeros."""
    _bm25_parity(np.zeros((2, 4, 128), np.float32), k=5)


def test_bm25_blockmax_single_element_block():
    """[1, 1, 1]: the θ pre-pass scores the only doc exactly, so the block
    sits at ub == θ — it must be swept, not pruned (regression: the strict
    ub > θ predicate dropped the true top-1 here)."""
    imp = np.zeros((1, 1, 1), np.float32)
    imp[0, 0, 0] = 2.5
    _bm25_parity(imp, k=1)


def test_bm25_blockmax_theta_tie_boundary():
    """Several blocks tied at exactly ub == θ: every tied block must be
    scored so the returned score multiset matches the oracle."""
    imp = np.zeros((4, 1, 8), np.float32)
    imp[:, 0, 3] = 1.0                   # one doc of score 1.0 per block
    _bm25_parity(imp, k=4)


@pytest.mark.parametrize("t,nb,bs,k", [(1, 1, 100, 3), (3, 5, 100, 7),
                                       (2, 3, 7, 4)])
def test_bm25_blockmax_block_length_not_tile_divisible(t, nb, bs, k):
    """BS not a multiple of the 128-lane tile: a (T, BS) block that spans
    the whole array's last two dims is still a legal TPU tile."""
    rng = np.random.default_rng(t * 31 + nb)
    imp = rng.random((nb, t, bs), dtype=np.float32)
    imp *= rng.random((nb, t, bs)) < 0.2
    _bm25_parity(imp.astype(np.float32), k=min(k, nb * bs))


def test_bm25_blockmax_k_exceeds_positive_docs():
    """Top-k spilling past the last positive doc pads with zeros, like the
    exhaustive oracle — never -inf."""
    imp = np.zeros((2, 2, 8), np.float32)
    imp[0, 0, 1] = 3.0                   # block 0, term 0
    imp[1, 1, 4] = 1.5                   # block 1, term 1
    impacts = jnp.asarray(imp)
    got_s, _ = bm25_blockmax_topk(impacts, impacts.max(axis=2), k=10)
    want_s, _ = bm25_topk_ref(impacts, 10)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(got_s)).all()


@pytest.mark.parametrize("mode", ["contained_in", "containing"])
def test_interval_join_empty_lists(mode):
    """pack() of an empty GC-list yields a single PAD entry; the join must
    return an all-zero mask on either (or both) sides."""
    empty = pack(np.array([], np.int64), np.array([], np.int64))
    one = pack(np.array([5], np.int64), np.array([9], np.int64))
    for a, b in [(empty, one), (one, empty), (empty, empty)]:
        got = interval_join(a[0], a[1], b[0], b[1], mode=mode,
                            use_pallas=True)
        ref_fn = (contained_in_mask_ref if mode == "contained_in"
                  else containing_mask_ref)
        want = ref_fn(a[0], a[1], b[0], b[1])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not np.asarray(got).any()


@pytest.mark.parametrize("a,b,contained,containing", [
    ((5, 9), (4, 10), 1, 0),      # A strictly inside B
    ((4, 10), (5, 9), 0, 1),      # A strictly contains B
    ((5, 9), (5, 9), 1, 1),       # identical intervals contain each other
    ((5, 9), (20, 30), 0, 0),     # disjoint
])
def test_interval_join_single_element(a, b, contained, containing):
    a_s, a_e, _ = pack(np.array([a[0]], np.int64), np.array([a[1]], np.int64))
    b_s, b_e, _ = pack(np.array([b[0]], np.int64), np.array([b[1]], np.int64))
    got_in = interval_join(a_s, a_e, b_s, b_e, mode="contained_in")
    got_on = interval_join(a_s, a_e, b_s, b_e, mode="containing")
    assert int(np.asarray(got_in)[0]) == contained
    assert int(np.asarray(got_on)[0]) == containing


@pytest.mark.parametrize("na,nb,tile", [(13, 5, 8), (20, 17, 8), (1, 9, 8),
                                        (257, 3, 128)])
@pytest.mark.parametrize("mode", ["contained_in", "containing"])
def test_interval_join_list_length_not_tile_divisible(na, nb, tile, mode):
    """Lengths that leave a partial final tile: the pad entries must never
    join, and multi-tile accumulation must match the oracle exactly."""
    from repro.kernels.interval_join.kernel import interval_join_pallas
    rng = np.random.default_rng(na * 100 + nb + tile)
    A = random_gc_list(rng, na, span=4000)
    B = random_gc_list(rng, nb, span=4000)
    a_s, a_e, _ = pack(A.starts, A.ends)
    b_s, b_e, _ = pack(B.starts, B.ends)
    got = interval_join_pallas(a_s, a_e, b_s, b_e, mode=mode,
                               tile_a=tile, tile_b=tile)
    ref_fn = (contained_in_mask_ref if mode == "contained_in"
              else containing_mask_ref)
    want = ref_fn(a_s, a_e, b_s, b_e)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------------ #
@pytest.mark.parametrize("b,hkv,g,d,s", [(2, 2, 4, 64, 256), (1, 4, 1, 128, 512),
                                         (2, 1, 8, 128, 300), (4, 2, 2, 64, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_decode_sweep(b, hkv, g, d, s, dtype):
    rng = np.random.default_rng(b * 100 + s)
    q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    length = jnp.asarray(rng.integers(1, s + 1, size=b), jnp.int32)
    got = gqa_decode(q, k, v, length, use_pallas=True, block_size=128)
    want = gqa_decode_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), length)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


# ------------------------------------------------------------------ #
@pytest.mark.parametrize("v,d,b,l", [(100, 32, 8, 5), (1000, 64, 16, 20),
                                     (64, 128, 4, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_embedding_bag_sweep(v, d, b, l, dtype):
    rng = np.random.default_rng(v + d)
    table = jnp.asarray(rng.standard_normal((v, d)), dtype)
    idx = jnp.asarray(rng.integers(0, v, size=(b, l)), jnp.int32)
    w = jnp.asarray((rng.random((b, l)) < 0.8).astype(np.float32))
    got_pallas = embedding_bag_padded(table, idx, w, use_pallas=True)
    got_jnp = embedding_bag_padded(table, idx, w, use_pallas=False)
    # oracle: flat segment-sum formulation
    seg = np.repeat(np.arange(b), l)
    want = embedding_bag_ref(table, idx.reshape(-1), jnp.asarray(seg), b,
                             weights=w.reshape(-1))
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_pallas), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
