"""Ragged decode attention — fetch and walk only the K/V tiles that
hold valid columns.

The decode engine's per-step cost story: every slot's query attends a
*preallocated* cache row padded to the attend-length bucket, so the
einsum path pays O(slots × bucket) work and bytes no matter how short
the live sequences are. At high occupancy with mixed lengths that is
the decode tokens/sec ceiling. This kernel puts the cache's column
axis on the grid in tiles of :func:`kv_tile` columns — the classic
online-softmax rescaling form, its running max, sum and accumulator in
VMEM scratch across the tiles of one slot-head — and the host
``lengths`` vector (``KVCache.lengths``, the same array the engine
already threads as ``positions``) is scalar-prefetched into SMEM: a
tile wholly past ``lengths[i]`` keeps the block index of the slot's
last valid tile (an unchanged index is not fetched again) and skips
the body, so a slot 17 tokens into a 512 bucket reads one tile, not
512 columns. ``lengths`` is the ONLY ragged input: block shapes stay
static, so kernel variants never multiply the ≤ 2-programs-per-bucket
bound (:mod:`bigdl_tpu.generation.engine`).

The tile is the unit of both the fetch and the walk, and the kernel
sizes it itself from the shapes it is handed (:func:`kv_tile`): a turn
of the walk is a chain of two small products and two lane reductions
whose latency does not shrink with the tile, so a narrow tile is bound
by its turns and not by its bytes (128 columns read a quarter of the
bytes' roofline on a v5e), while a tile as wide as the block fetches
every row whole whatever its length.

K and V arrive as one layer's whole cache ``[slots, H, D, T]`` — time
on the lanes, exactly as ``KVCache`` stores it (``D`` = 64 on the 128
lanes would pad every tile 2x) — and the block is ``(1, 1, D, tile)``
over the first ``A`` columns, ``A`` the ladder rung rounded up to
whole lane tiles: nothing is sliced, transposed or copied between the
cache and the kernel. Scores are ``q[G, D] @ k[D, tile]``; the value
product contracts the lane axis of ``p[G, tile]`` with ``v[D, tile]``.

K/V heads may be fewer than query heads (grouped-query attention):
the grid runs over the K/V heads and a program's query block is the
``[G, D]`` group that shares its K/V head, so every cached column is
read once for its ``G`` queries. ``G`` = 1 is multi-head attention.

One token per slot (decode's shape), grid ``(slots, kv heads, A /
tile)``; used through :func:`bigdl_tpu.kernels.decode_attention`,
which owns eligibility and the jnp fallback.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bigdl_tpu.kernels.common import fit_block, tpu_compiler_params

# pallas (a second of import) is loaded where the kernel is traced: the
# decode engine imports kv_tile from here for its host-side accounting
# and must not pay for it in a process that runs no kernel

__all__ = ["ragged_decode_attention", "kv_tile", "block_columns"]

_NEG_INF = float("-inf")
_LANES = 128    # a TPU vector tile's last dim, whatever the dtype
#: VMEM one program may spend on its K and V tiles, double buffered,
#: and its float32 scores: a small part of the 16 MiB a kernel has on
#: a v5e, so the float32 copies the products make of a tile fit too
_TILE_BUDGET_BYTES = 3 << 20


def block_columns(t: int, attend_len: int) -> int:
    """``A``: the columns of a ``[.., T]`` cache the kernel's grid
    covers at ladder rung ``attend_len`` — the rung rounded up to whole
    lane tiles, or all of ``T`` (Mosaic takes a trailing block dim that
    is tile-aligned or the array's); the columns past the rung it
    brings along are masked by ``lengths``."""
    return min(int(t), -(-int(attend_len) // _LANES) * _LANES)


def kv_tile(a: int, d: int, g: int, itemsize: int) -> int:
    """Columns of one K/V tile for a block of ``a`` columns, head size
    ``d``, ``g`` queries a K/V head and a cache of ``itemsize`` bytes:
    the largest lane-aligned divisor of ``a`` whose K and V tiles,
    double buffered, and ``[g, tile]`` float32 scores stay inside the
    budget. A pure function of static shapes. A block with no
    lane-aligned divisor (a cache shorter than a lane tile, or not a
    whole number of them) is one tile."""
    per_column = 2 * 2 * d * itemsize + 4 * g
    return fit_block(a, max(_LANES, _TILE_BUDGET_BYTES // per_column),
                     align=_LANES)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *scratch,
                   tile: int, k_tiles: int, sm_scale: float):
    from jax.experimental import pallas as pl

    n = len_ref[pl.program_id(0)]                       # valid KV columns
    j = pl.program_id(2)

    def walk(m, l, acc):
        """One tile folded into the running (max, sum, accumulator)."""
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [G, D]
        s = jax.lax.dot_general(q, k_ref[0, 0].astype(jnp.float32),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)            # [D, tile]
        valid = j * tile + jax.lax.broadcasted_iota(
            jnp.int32, (1, tile), 1) < n
        # whatever the cache holds past n (a NaN too) is kept out of
        # both products: -inf scores become exact zeros in p
        s = jnp.where(valid, s, _NEG_INF)               # [G, tile]
        vb = jnp.where(valid, vb, 0.0)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # first tile: m = -inf, m_new finite (col 0 < n always) so
        # alpha underflows to an exact 0 and the zero-initialized
        # carry drops out; every later tile walked holds >= 1 valid
        # column (tiles past n are skipped), keeping m_new finite
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # both operands carry the tile's columns on the lanes
        acc = acc * alpha + jax.lax.dot_general(
            p, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    g, d = q_ref.shape[2:]
    if k_tiles == 1:
        # the block is one tile (1 <= n <= attend_len always): no carry
        _, l, acc = walk(jnp.full((g, 1), _NEG_INF, jnp.float32),
                         jnp.zeros((g, 1), jnp.float32),
                         jnp.zeros((g, d), jnp.float32))
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
        return
    m_ref, l_ref, acc_ref = scratch

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * tile < n)
    def _():
        m_ref[...], l_ref[...], acc_ref[...] = walk(
            m_ref[...], l_ref[...], acc_ref[...])

    @pl.when(j == k_tiles - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def ragged_decode_attention(q, k, v, lengths, *, attend_len: int = None,
                            sm_scale: float = None,
                            interpret: bool = False):
    """One decode step of attention over ragged KV: ``q`` is
    ``[slots, H, D]`` (the step's single token per slot), ``k``/``v``
    are one layer's WHOLE cache ``[slots, Hkv, D, T]`` (``H`` a multiple
    of ``Hkv``; query heads ``j G .. (j + 1) G - 1`` share K/V head
    ``j``) — time on the
    lanes, the form :class:`~bigdl_tpu.generation.kv_cache.KVCache`
    stores, so nothing is sliced or transposed on the way in —
    ``lengths`` the host int32 ``[slots]`` of valid columns per slot
    (clamped into ``[1, attend_len]`` — a free slot reads one garbage
    column whose output is never consumed, matching the engine's
    inactive-slot contract). ``attend_len`` (static, default ``T``) is
    the ladder rung: the grid covers its first columns only, rounded
    up to whole 128-lane tiles, in tiles of :func:`kv_tile` columns.
    Returns ``[slots, H, D]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, h, d, t = k.shape
    if (q.ndim != 3 or q.shape[0] != slots or q.shape[2] != d
            or q.shape[1] % h or v.shape != k.shape):
        raise ValueError(f"q {q.shape} / v {v.shape} do not match "
                         f"cache [{slots},{h},{d},{t}]")
    g = q.shape[1] // h
    al = t if attend_len is None else int(attend_len)
    if not 1 <= al <= t:
        raise ValueError(f"attend_len={al} outside [1, {t}]")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    a = block_columns(t, al)
    tile = kv_tile(a, d, g, k.dtype.itemsize)
    k_tiles = a // tile
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, al)
    kernel = functools.partial(_decode_kernel, tile=tile, k_tiles=k_tiles,
                               sm_scale=float(sm_scale))
    # q and the output travel as [slots, Hkv, G, D]: Mosaic wants the
    # last two dims of a block to be (8, 128)-aligned or the whole
    # array's, and a (G, D) tile of a [.., G, D] array is the latter
    row = pl.BlockSpec((1, 1, g, d), lambda s, h_, j, n: (s, h_, 0, 0))
    # a tile past the slot's last valid one keeps that one's index, so
    # the pipeline fetches nothing for it
    cache = pl.BlockSpec(
        (1, 1, d, tile),
        lambda s, h_, j, n: (s, h_, 0, jnp.minimum(j, (n[s] - 1) // tile)))
    scratch = [] if k_tiles == 1 else [
        pltpu.VMEM((g, 1), jnp.float32), pltpu.VMEM((g, 1), jnp.float32),
        pltpu.VMEM((g, d), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots, h, k_tiles),
            in_specs=[row, cache, cache], out_specs=row,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((slots, h, g, d), q.dtype),
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bigdl_ragged_decode",
    )(lengths, q.reshape(slots, h, g, d), k, v)
    return out.reshape(slots, h * g, d)
