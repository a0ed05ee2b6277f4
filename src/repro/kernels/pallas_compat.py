"""The one place the kernels name the Pallas TPU grid and compiler-params
API (``pltpu.CompilerParams``, ``GridDimensionSemantics``,
``PrefetchScalarGridSpec``).

Kernels express dimension semantics with ``PARALLEL`` / ``ARBITRARY`` and
build their params and scalar-prefetch grids through the two helpers
below, so a change of that API is a one-file edit (quakecheck QK103
enforces the rule).  The repo runs on one JAX release; see
``docs/compat.md``.
"""
from __future__ import annotations

from typing import Any, Sequence

from jax.experimental.pallas import tpu as pltpu

__all__ = ["PARALLEL", "ARBITRARY", "compiler_params",
           "prefetch_scalar_grid_spec"]

PARALLEL = pltpu.GridDimensionSemantics.PARALLEL
ARBITRARY = pltpu.GridDimensionSemantics.ARBITRARY


def compiler_params(*, dimension_semantics: Sequence[Any], **kwargs: Any):
    """Mosaic compiler params; extra kwargs pass through."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics), **kwargs)


def prefetch_scalar_grid_spec(*, num_scalar_prefetch: int, grid, in_specs,
                              out_specs, scratch_shapes):
    """Scalar-prefetch grid spec (index maps read the prefetched operands)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)
