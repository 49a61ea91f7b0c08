"""Where a Pallas kernel runs: compiled on a TPU, interpreted on the CPU.

The choice is made when a program is lowered, from the platform it is
lowered for (``jax.lax.platform_dependent``), never from a flag.  So a
program lowered for a TPU always compiles its kernels through Mosaic, and
a kernel the compiler refuses fails loudly there instead of running
through the interpreter; the same call on CPU arrays (the test suite)
runs the interpreter.  Lowering for any other platform is an error.
"""

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` with ``interpret`` decided by
    the platform the enclosing program is lowered for: False for a TPU,
    True for the CPU."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          tpu=compiled)
    return call
