"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel subpackage ships kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd wrapper with a pure-jnp fallback), and ref.py (oracle).
``platform.py`` decides how a kernel runs: compiled when the program is
lowered for a TPU, interpreted when it is lowered for the CPU.  Model code
takes a `use_pallas` flag (default off so the multi-pod dry-run lowers the
pure-jnp path).
"""

from .bm25_blockmax import bm25_blockmax_topk, bm25_topk_ref, pruned_fraction
from .embedding_bag import embedding_bag_padded, embedding_bag_ref, pad_ragged
from .gqa_decode import gqa_decode, gqa_decode_ref
from .interval_join import (contained_in_mask_ref, containing_mask_ref,
                            interval_join)

__all__ = [
    "bm25_blockmax_topk", "bm25_topk_ref", "pruned_fraction",
    "embedding_bag_padded", "embedding_bag_ref", "pad_ragged",
    "gqa_decode", "gqa_decode_ref",
    "contained_in_mask_ref", "containing_mask_ref", "interval_join",
]
