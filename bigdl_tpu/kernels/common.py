"""Helpers shared by the kernel implementations (tiling + compiler
params) — one home so a tiling policy change is fixed in exactly one
place."""
from __future__ import annotations

__all__ = ["FLASH_VMEM_LIMIT_MB", "fit_block", "sublanes",
           "tpu_compiler_params"]

#: scoped VMEM (MiB) the full-row flash kernels ask the compiler for a
#: program, of a v5e's 128 (its default grants 16). The ONE number
#: for that limit: the dispatch's default working-set budget is this
#: less a margin (``KernelConfig.resolve_vmem_budget``)
FLASH_VMEM_LIMIT_MB = 32


def sublanes(dtype) -> int:
    """Rows of one TPU vector tile for ``dtype``: 8 for 32-bit, 16 for
    bf16, 32 for int8 (narrow types pack along sublanes). A row tile
    that is sliced or blocked at a dynamic offset must be a multiple
    of it."""
    import numpy as np

    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def fit_block(dim: int, preferred: int, align: int = 1) -> int:
    """The largest block size <= ``preferred`` that divides ``dim`` and
    is a multiple of ``align`` (pallas grids need exact tiling; ragged
    shapes shrink the tile instead of falling off the kernel path).
    Where no such divisor exists the block is the whole ``dim``: the
    TPU compiler takes a block whose trailing dims are tile-aligned
    *or equal to the array's*, and refuses anything between."""
    dim, align = int(dim), int(align)
    b = min(int(preferred), dim) // align * align
    while b > 0 and dim % b:
        b -= align
    return b if b > 0 else dim


def tpu_compiler_params(dimension_semantics, vmem_limit_bytes=None):
    """TPU compiler params for a kernel grid: the accumulator-carrying
    axis is "arbitrary" (sequential), everything else parallel.
    ``vmem_limit_bytes`` raises the scoped VMEM one program may take
    over the compiler's default (16 MiB on a v5e, of 128)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes)
