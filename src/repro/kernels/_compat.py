"""The one spelling of Pallas TPU compiler params every kernel uses."""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu


def compiler_params(*, dimension_semantics=None, **kw):
    """``pltpu.CompilerParams`` with ``dimension_semantics`` as a tuple."""
    if dimension_semantics is not None:
        kw["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kw)
