"""Pallas kernels for the compute hot-spots the paper itself optimizes.

Every kernel takes ``interpret=None`` by default, resolved by
:func:`lowered_for_platform`: the Pallas interpreter when the program is
lowered for the CPU, the compiled Mosaic kernel everywhere else.
"""
from __future__ import annotations

import jax


def lowered_for_platform(build, *args, interpret=None):
    """``build(interpret=...)(*args)`` with the mode chosen per platform.

    ``build`` is a ``pallas_call`` with everything bound but ``interpret``.
    ``interpret=None`` defers the choice to lowering: a CPU lowering runs
    the interpreter, any other lowering compiles the kernel.  So a TPU
    never falls back to the interpreter, and a TPU compile made on a CPU
    host (a described topology) contains the real ``tpu_custom_call``.
    An explicit bool forces the mode (the interpret-mode oracle tests).
    """
    if interpret is not None:
        return build(interpret=interpret)(*args)
    return jax.lax.platform_dependent(
        *args, cpu=build(interpret=True), default=build(interpret=False))
