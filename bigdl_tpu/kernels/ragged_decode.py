"""Ragged decode attention — read only ``lengths[i]`` valid KV per slot.

The decode engine's per-step cost story: every slot's query attends a
*preallocated* cache row padded to the attend-length bucket, so the
einsum path pays O(slots × bucket) work and bytes no matter how short
the live sequences are. At high occupancy with mixed lengths that is
the decode tokens/sec ceiling. This kernel walks each slot's KV in
``block_k`` tiles under a **dynamic** ``fori_loop`` bound
``cdiv(lengths[i], block_k)`` — the classic online-softmax rescaling
form — so a slot 17 tokens into a 512 bucket reads one tile, not 512
rows. The host ``lengths`` vector (``KVCache.lengths``, the same array
the engine already threads as ``positions``) rides into SMEM and is
the ONLY ragged input: block shapes stay static, so kernel variants
never multiply the ≤ 2-programs-per-bucket bound
(:mod:`bigdl_tpu.generation.engine`).

One token per slot (decode's shape), grid ``(slots, heads)``; used
through :func:`bigdl_tpu.kernels.decode_attention`, which owns
eligibility and the jnp fallback.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import fit_block, sublanes

__all__ = ["ragged_decode_attention"]

_NEG_INF = float("-inf")


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *,
                   block_k: int, k_tiles: int, sm_scale: float):
    slot = pl.program_id(0)
    n = len_ref[slot]                                   # valid KV rows
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [1, D]

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, 0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(q, kb.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(col < n, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # first tile: m = -inf, m_new finite (col 0 < n always) so
        # alpha underflows to an exact 0 and the zero-initialized
        # carry drops out; every later tile holds >= 1 valid column
        # (the loop bound is cdiv(n, block_k)), keeping m_new finite
        alpha = jnp.exp(m - m_new)
        p = jnp.where(col < n, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        vb = v_ref[0, 0, pl.ds(i * block_k, block_k), :]
        acc = acc * alpha + jax.lax.dot_general(
            p, vb.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    d = q.shape[-1]
    m0 = jnp.full((1, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, 1), jnp.float32)
    acc0 = jnp.zeros((1, d), jnp.float32)
    if k_tiles == 1:
        # the bucket is one tile (1 <= n <= T always): a static slice —
        # the compiler cannot prove a dynamic row offset aligned when
        # T is below a vector tile
        _, l, acc = body(0, (m0, l0, acc0))
    else:
        _, l, acc = jax.lax.fori_loop(0, pl.cdiv(n, block_k), body,
                                      (m0, l0, acc0))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def ragged_decode_attention(q, k, v, lengths, *, sm_scale: float = None,
                            block_k: int = 128,
                            interpret: bool = False):
    """One decode step of attention over ragged KV: ``q`` is
    ``[slots, H, D]`` (the step's single token per slot), ``k``/``v``
    are ``[slots, H, T, D]`` cache slices, ``lengths`` the host int32
    ``[slots]`` of valid rows per slot (clamped into ``[1, T]`` — a
    free slot reads one garbage row whose output is never consumed,
    matching the engine's inactive-slot contract). Returns
    ``[slots, H, D]``."""
    from jax.experimental.pallas import tpu as pltpu

    slots, h, t, d = k.shape
    if q.shape != (slots, h, d):
        raise ValueError(f"q {q.shape} does not match cache "
                         f"[{slots},{h},{t},{d}]")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # K/V tiles are sliced at a dynamic row offset inside the kernel,
    # so the tile is a whole number of vector tiles (or all of T)
    block_k = fit_block(t, block_k, align=sublanes(k.dtype))
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, t)
    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               k_tiles=t // block_k,
                               sm_scale=float(sm_scale))
    # q and the output travel as [slots, H, 1, D]: Mosaic wants the
    # last two dims of a block to be (8, 128)-aligned or the whole
    # array's, and a (1, D) tile of a [.., 1, D] array is the latter
    row = pl.BlockSpec((1, 1, 1, d), lambda s, h_: (s, h_, 0, 0))
    full = pl.BlockSpec((1, 1, t, d), lambda s, h_: (s, h_, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(slots, h),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row, full, full],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((slots, h, 1, d), q.dtype),
        interpret=interpret,
        name="bigdl_ragged_decode",
    )(lengths, q[:, :, None, :], k, v)
    return out[:, :, 0, :]
