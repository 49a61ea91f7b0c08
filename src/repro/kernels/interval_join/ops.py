"""Jit'd public wrapper for the containment-join kernel."""

import functools

import jax

from .kernel import interval_join_pallas
from .ref import contained_in_mask_ref, containing_mask_ref


@functools.partial(jax.jit, static_argnames=("mode", "use_pallas"))
def interval_join(a_s, a_e, b_s, b_e, mode: str = "contained_in",
                  use_pallas: bool = True):
    """Containment join over packed GC-lists; int32 mask over A."""
    if use_pallas:
        return interval_join_pallas(a_s, a_e, b_s, b_e, mode=mode)
    ref = contained_in_mask_ref if mode == "contained_in" else containing_mask_ref
    return ref(a_s, a_e, b_s, b_e)
