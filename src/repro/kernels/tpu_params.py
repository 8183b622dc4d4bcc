"""Mosaic compiler parameters shared by every Pallas TPU kernel here.

One place builds them, so a rename in `jax.experimental.pallas.tpu` (it
was `TPUCompilerParams` before JAX 0.6) is a one-line repair.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu


def compiler_params(*semantics: str,
                    vmem_limit_bytes: int | None = None
                    ) -> pltpu.CompilerParams:
    """Grid `dimension_semantics`, one entry per grid axis: "parallel" for
    axes with no carried state, "arbitrary" for an accumulation axis.
    `vmem_limit_bytes` raises Mosaic's scoped-VMEM budget (16 MiB by
    default) for kernels whose in-VMEM decode temporaries need more."""
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=vmem_limit_bytes)
