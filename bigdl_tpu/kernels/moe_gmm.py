"""Grouped matrix product for a routed expert layer — each row tile of
the sorted token-expert pairs is multiplied by ITS expert's matrix.

The expert layer (:mod:`bigdl_tpu.nn.moe`) sorts the pairs that fall on
the experts it holds and lays every expert's run out on whole row tiles
of ``tile_m`` rows (the tail of a run is padding), so a tile belongs to
exactly one expert and the kernel needs no masking inside a tile:

- ``x``            ``[M, K]`` the sorted, tile-aligned rows;
- ``w``            ``[E, K, N]`` the held experts' stacked matrices;
- ``tile_expert``  int32 ``[M / tile_m]``, the expert of each row tile;
- ``num_tiles``    int32 ``[1]``, how many leading tiles hold pairs.

Grid ``(M / tile_m, N / tn, K / tk)``; ``tile_expert`` and ``num_tiles``
are scalar-prefetched into SMEM and pick the weight block, so an expert
no pair fell on is never read, and one that holds a single tile is read
once. ``M`` is the static worst case (every pair local); tiles past
``num_tiles`` compute nothing, and their block indices stand still, so
nothing is fetched for them either; their output rows are written as
zeros (the layer never reads them, but an activation's gradient at
whatever the buffer held would be NaN times zero). A decode step of 48 tokens
is bound by streaming each touched expert's matrix once; a wide prefill
chunk by the products.

Used through :func:`bigdl_tpu.kernels.grouped_matmul`, which owns
eligibility, the jnp fallback and the backward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import fit_block, tpu_compiler_params

__all__ = ["grouped_matmul_pallas", "weight_blocks"]

_LANES = 128
#: bytes of one weight block: two of them (double buffering) and the
#: row tiles stay well inside the 16 MiB a kernel may use on a v5e
_WEIGHT_BLOCK_BYTES = 3 << 20


def weight_blocks(k: int, n: int, itemsize: int):
    """``(tk, tn)``: a lane-aligned column block of at most 512 and as
    much of ``K`` as keeps the block within its budget."""
    tn = fit_block(n, 512, align=_LANES)
    tk = fit_block(k, max(_LANES, _WEIGHT_BLOCK_BYTES // (tn * itemsize)),
                   align=_LANES)
    return tk, tn


def _gmm_kernel(te_ref, nt_ref, x_ref, w_ref, o_ref, acc_ref, *,
                k_tiles: int):
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(i < nt_ref[0])
    def _():
        part = jnp.dot(x_ref[...], w_ref[0],
                       preferred_element_type=jnp.float32)
        if k_tiles == 1:
            o_ref[...] = part.astype(o_ref.dtype)
        else:
            @pl.when(kk == 0)
            def _():
                acc_ref[...] = part

            @pl.when(kk > 0)
            def _():
                acc_ref[...] += part

            @pl.when(kk == k_tiles - 1)
            def _():
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    @pl.when(i >= nt_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def grouped_matmul_pallas(x, w, tile_expert, num_tiles, *, tile_m: int,
                          interpret: bool = False):
    """``out[t*tile_m:(t+1)*tile_m] = x[t*tile_m:(t+1)*tile_m] @
    w[tile_expert[t]]`` for the first ``num_tiles[0]`` row tiles."""
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    e, k2, n = w.shape
    if k2 != k or m % tile_m:
        raise ValueError(f"x {x.shape} / w {w.shape} / tile_m {tile_m}")
    tk, tn = weight_blocks(k, n, w.dtype.itemsize)
    k_tiles = k // tk

    # a tile past the last live one keeps the block indices of the last
    # step taken, so the pipeline fetches nothing new for it
    def live(i, nt, a, b):
        return jnp.where(i < nt[0], a, b)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // tile_m, n // tn, k_tiles),
        in_specs=[
            pl.BlockSpec((tile_m, tk), lambda i, j, kk, te, nt:
                         (live(i, nt, i, 0), live(i, nt, kk, 0))),
            pl.BlockSpec((1, tk, tn), lambda i, j, kk, te, nt:
                         (te[i], live(i, nt, kk, 0), live(i, nt, j, 0))),
        ],
        out_specs=pl.BlockSpec((tile_m, tn),
                               lambda i, j, kk, te, nt: (i, j)),
        scratch_shapes=[pltpu.VMEM((tile_m, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, k_tiles=k_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bigdl_moe_gmm",
    )(tile_expert.astype(jnp.int32), num_tiles.astype(jnp.int32), x, w)
