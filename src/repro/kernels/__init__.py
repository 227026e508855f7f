"""Pallas kernels of the paper's hot spots, plus their launch helper.

Every kernel in this package is launched through :func:`pallas_call`,
which is the ONE place the Pallas ``interpret`` flag is decided.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` compiled by Mosaic when lowered for a TPU and
    run by the Pallas interpreter on any other platform.

    The choice is made from the platform the program is *lowered* for
    (``jax.lax.platform_dependent``), not from a caller's flag, so a
    program running on a TPU can never reach the interpreter, and a
    compile for a described TPU from a CPU-only host lowers the real
    kernel.  Only the branch of the lowering platform is lowered."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)
    return call
