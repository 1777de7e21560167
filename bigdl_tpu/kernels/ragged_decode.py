"""Ragged decode attention — read only ``lengths[i]`` valid KV per slot.

The decode engine's per-step cost story: every slot's query attends a
*preallocated* cache row padded to the attend-length bucket, so the
einsum path pays O(slots × bucket) work and bytes no matter how short
the live sequences are. At high occupancy with mixed lengths that is
the decode tokens/sec ceiling. This kernel walks each slot's KV in
``block_k`` tiles under a **dynamic** ``fori_loop`` bound
``cdiv(lengths[i], block_k)`` — the classic online-softmax rescaling
form — so a slot 17 tokens into a 512 bucket reads one tile, not 512
columns. The host ``lengths`` vector (``KVCache.lengths``, the same
array the engine already threads as ``positions``) rides into SMEM and
is the ONLY ragged input: block shapes stay static, so kernel variants
never multiply the ≤ 2-programs-per-bucket bound
(:mod:`bigdl_tpu.generation.engine`).

K and V arrive as one layer's whole cache ``[slots, H, D, T]`` — time
on the lanes, exactly as ``KVCache`` stores it (``D`` = 64 on the 128
lanes would pad every tile 2x) — and the block is ``(1, 1, D, A)``,
``A`` the ladder rung rounded up to whole lane tiles: nothing is
sliced, transposed or copied between the cache and the kernel. Scores
are ``q[1, D] @ k[D, block_k]``; the value product contracts the lane
axis of ``p[1, block_k]`` with ``v[D, block_k]``.

K/V heads may be fewer than query heads (grouped-query attention):
the grid runs over the K/V heads and a program's query block is the
``[G, D]`` group that shares its K/V head, so every cached column is
read once for its ``G`` queries. ``G`` = 1 is multi-head attention.

One token per slot (decode's shape), grid ``(slots, kv heads)``; used
through :func:`bigdl_tpu.kernels.decode_attention`, which owns
eligibility and the jnp fallback.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import fit_block

__all__ = ["ragged_decode_attention"]

_NEG_INF = float("-inf")
_LANES = 128    # a TPU vector tile's last dim, whatever the dtype


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *,
                   block_k: int, k_tiles: int, sm_scale: float):
    slot = pl.program_id(0)
    n = len_ref[slot]                                   # valid KV columns
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [G, D]

    def body(i, carry):
        m, l, acc = carry
        start = i * block_k
        if k_tiles > 1:
            start = pl.multiple_of(start, block_k)
        cols = pl.ds(start, block_k)
        kb = k_ref[0, 0, :, cols]                       # [D, block_k]
        s = jax.lax.dot_general(q, kb.astype(jnp.float32),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = start + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(col < n, s, _NEG_INF)             # [G, block_k]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # first tile: m = -inf, m_new finite (col 0 < n always) so
        # alpha underflows to an exact 0 and the zero-initialized
        # carry drops out; every later tile holds >= 1 valid column
        # (the loop bound is cdiv(n, block_k)), keeping m_new finite
        alpha = jnp.exp(m - m_new)
        p = jnp.where(col < n, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        vb = v_ref[0, 0, :, cols]                       # [D, block_k]
        # both operands carry the tile's columns on the lanes
        acc = acc * alpha + jax.lax.dot_general(
            p, vb.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    g, d = q.shape
    m0 = jnp.full((g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc0 = jnp.zeros((g, d), jnp.float32)
    if k_tiles == 1:
        # the block is one tile (1 <= n <= attend_len always): a static
        # slice — a block narrower than a vector tile's 128 lanes has
        # no aligned dynamic offset to prove
        _, l, acc = body(0, (m0, l0, acc0))
    else:
        _, l, acc = jax.lax.fori_loop(0, pl.cdiv(n, block_k), body,
                                      (m0, l0, acc0))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def ragged_decode_attention(q, k, v, lengths, *, attend_len: int = None,
                            sm_scale: float = None, block_k: int = 128,
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
    the ladder rung: the block covers its first columns only, rounded
    up to whole 128-lane tiles. Returns ``[slots, H, D]``."""
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
    # the block's last dim is whole lane tiles, or all of T (Mosaic
    # takes a trailing block dim that is tile-aligned or the array's);
    # the columns past attend_len it brings along are masked by lengths
    a = min(t, -(-al // _LANES) * _LANES)
    # K/V tiles are sliced at a dynamic lane offset inside the kernel,
    # so the tile is a whole number of lane tiles (or all of the block)
    block_k = fit_block(a, block_k, align=_LANES)
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, al)
    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               k_tiles=a // block_k,
                               sm_scale=float(sm_scale))
    # q and the output travel as [slots, Hkv, G, D]: Mosaic wants the
    # last two dims of a block to be (8, 128)-aligned or the whole
    # array's, and a (G, D) tile of a [.., G, D] array is the latter
    row = pl.BlockSpec((1, 1, g, d), lambda s, h_: (s, h_, 0, 0))
    cache = pl.BlockSpec((1, 1, d, a), lambda s, h_: (s, h_, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(slots, h),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row, cache,
                  cache],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((slots, h, g, d), q.dtype),
        interpret=interpret,
        name="bigdl_ragged_decode",
    )(lengths, q.reshape(slots, h, g, d), k, v)
    return out.reshape(slots, h * g, d)
