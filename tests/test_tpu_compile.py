"""Compile the served scorers for a TPU v5e without the chip.

The TPU compiler is installed with jaxlib and compiles for a described,
unattached chip.  What it refuses here (tiling, VMEM, memory) it would
refuse on the chip, so these tests guard the device path on a CPU host.
The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports
every test file.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import aggregate
from repro.core.vectorized import bm25_topk
from repro.kernels import bm25_blockmax_topk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n_docs", [1 << 16, 1 << 23])
def test_bm25_topk_compiles_for_v5e(one_chip, n_docs):
    """The served scorer at a micro-batch's shape: 16 queries × 8 terms ×
    4096 postings, into a group accumulator of ``n_docs`` slots."""
    q, t, l = 16, 8, 4096
    fn = jax.jit(lambda d, i, m: bm25_topk(d, i, m, n_docs=n_docs, k=10))
    compiled = fn.lower(_spec((q, t, l), jnp.int32, one_chip),
                        _spec((q, t, l), jnp.float32, one_chip),
                        _spec((q, t), jnp.float32, one_chip)).compile()
    # about two [Q, n_docs] f32 arrays (accumulator and top-k working copy):
    # 1 GiB at 2^23, which leaves most of the chip's 16 GB of HBM free
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * q * n_docs * 4


@pytest.mark.parametrize("p, n_docs", [(1024, 1 << 23),
                                       (5_020_928, 1 << 14)])
def test_bm25_topk_compact_compiles_for_v5e(one_chip, p, n_docs):
    """The served scorer's compact form: 16 query slots, from the smallest
    posting bucket into a 2^23 accumulator to the largest bucket a batch of
    16 queries x 16 terms can fill at a 2^14 one."""
    q = 16
    fn = jax.jit(lambda d, i, m: bm25_topk(d, i, m, n_docs=n_docs, k=10))
    compiled = fn.lower(_spec((p,), jnp.int32, one_chip),
                        _spec((p,), jnp.float32, one_chip),
                        _spec((q, 1), jnp.float32, one_chip)).compile()
    # about three [Q, n_docs] f32 arrays (the flat accumulator, its
    # [Q, n_docs] layout and top-k's working copy): 1.5 GiB at 2^23
    assert compiled.memory_analysis().temp_size_in_bytes < \
        3.5 * q * n_docs * 4 + 8 * p


def test_bm25_blockmax_compiles_for_v5e(one_chip):
    """The block-max kernel at a real width: 8 terms over 2^15 blocks of
    128 documents (4M documents) compiles through Mosaic, not the
    interpreter."""
    nb, t, bs = 1 << 15, 8, 128
    fn = jax.jit(lambda i, m: bm25_blockmax_topk(i, m, k=10))
    compiled = fn.lower(_spec((nb, t, bs), jnp.float32, one_chip),
                        _spec((nb, t), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_rows", [1 << 15, 1 << 21])
def test_agg_scan_compiles_for_v5e(one_chip, n_rows):
    """The aggregate scan at a group's shape: 16 request slots over 8
    resident columns of 2^15 rows (a group at TPC-H SF 0.02) and 2^21
    (SF 1), 8 products into 8 group keys: the sums are one int8 matmul
    into int32 a block, and no int64 tensor is a block's size times the
    slots and sums."""
    c, q, p, g = aggregate.COLUMNS_STEP, 16, 8, 8
    blk = aggregate.ROW_BLOCK
    with jax.enable_x64(True):
        lowered = aggregate.agg_scan.lower(
            _spec((c, n_rows), jnp.int32, one_chip),
            _spec((c, n_rows), jnp.bool_, one_chip),
            _spec((q, aggregate._width(c)), jnp.int32, one_chip),
            _spec((p, aggregate.MAX_FACTORS, 3), jnp.int32, one_chip),
            n_keys=g)
        compiled = lowered.compile()
    text = lowered.as_text()
    n = p * aggregate.LIMBS + 1
    assert re.search(rf"dot_general .*\(tensor<{q * g}x{blk}xi8>, "
                     rf"tensor<{n}x{blk}xi8>\) -> tensor<{q * g}x{n}xi32>",
                     text)
    assert re.search(r"= s32\[\S+ convolution\(", compiled.as_text())
    for dims in re.findall(r"tensor<([0-9x]+)xi64>", text):
        assert math.prod(map(int, dims.split("x"))) < \
            q * aggregate.MAX_SUMS * blk
    # nothing beyond one block's int8 hit matrix and limbs, at any size
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        (q * g + n) * blk
