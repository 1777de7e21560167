"""Fused flash attention for training — pallas, segment-mask aware.

The [S, S] score matrix never exists in memory: the grid tiles the
query axis and each program holds one ``[block_q, S]`` score strip in
VMEM, computes a numerically-stable softmax over the full key axis and
contracts straight into the ``[block_q, D]`` output — O(S·block_q)
live bytes instead of O(S²) (the memory property that lets S=32K run
where the einsum path dies; nn/attention's ``_FLASH_SCORE_BYTES``
measurement note). The backward recomputes the strip from the saved
log-sum-exp and accumulates dK/dV across query tiles in VMEM scratch —
no residual score matrix either.

**Why full-row reductions instead of blockwise rescaling:** the
classic online-softmax rescales the running accumulator by
``exp(m_old - m_new)`` at every key block, which makes the result
depend on where block boundaries fall. Packed training slabs
(``bigdl_tpu.datapipe.packing``) put documents at arbitrary row
offsets, and the datapipe's contract is that a packed forward is
**bit-exact per token** against each document run alone — a guarantee
blockwise rescaling breaks (the rescale rounds differently per
offset). Reducing each query's full key row at once keeps masked
positions as *exact zeros* in the sum, which commutes with document
offset, so the packed-slab bitwise contract survives the kernel
(tests/test_kernels.py asserts it per token). The decode kernel
(:mod:`bigdl_tpu.kernels.decode_attention`), whose win is *skipping*
tail key blocks, uses the true online rescaling form — its contract is
tolerance, not bitwise.

Masking: ``causal`` and/or ``segment_ids`` (``[B, S]`` int32; queries
attend only same-segment keys — the packed-slab mask). Masked scores
are ``-inf`` so they vanish exactly from max/sum; a fully-masked query
row yields 0 output, not NaN.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import (fit_block, sublanes,
                                      tpu_compiler_params)

__all__ = ["flash_attention", "blockwise_flash_attention", "fit_block"]

_NEG_INF = float("-inf")


def _mask_for(i, block_q, s, causal, seg_q, seg_k):
    """The boolean keep-mask for query tile ``i``: ``[block_q, s]``,
    or None when nothing masks."""
    mask = None
    if causal:
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, s), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, s), 1)
        mask = cols <= rows
    if seg_q is not None:
        seg = seg_q == seg_k      # [bq, 1] column vs [1, S|bk] row
        mask = seg if mask is None else mask & seg
    return mask


def _segment_planes(segment_ids):
    """The ``[B, S]`` segment ids as the two operands the kernels
    read: a ``[B, S, 1]`` column plane (query side) and a ``[B, 1, S]``
    row plane (key side), so the same-segment mask is one 2-D
    broadcast compare — Mosaic takes no rank-1 vector, and a block's
    last two dims must be tile-aligned or the whole array's."""
    seg = segment_ids.astype(jnp.int32)
    return [seg[:, :, None], seg[:, None, :]]


def _segment_specs(block_q, block_k, q_tile, k_tile):
    """Block specs for :func:`_segment_planes`' two operands.
    ``q_tile`` / ``k_tile`` pick the query / key tile index out of the
    grid ids that follow ``(batch, head)``."""
    return [
        pl.BlockSpec((1, block_q, 1),
                     lambda b_, h_, *ids: (b_, q_tile(*ids), 0)),
        pl.BlockSpec((1, 1, block_k),
                     lambda b_, h_, *ids: (b_, 0, k_tile(*ids))),
    ]


def _fwd_kernel(*refs, causal: bool, block_q: int, sm_scale: float,
                segmented: bool):
    if segmented:
        q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref = refs
        seg_q, seg_k = sq_ref[0], sk_ref[0]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        seg_q = seg_k = None
    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # [bq, D]
    k = k_ref[0, 0]                                         # [S, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _mask_for(i, block_q, s.shape[-1], causal, seg_q, seg_k)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                  # [bq, 1]
    # exp(-inf - -inf) = nan on fully-masked rows; the where() zeroes
    # every masked lane EXACTLY, which is what keeps packed slabs
    # bit-faithful (module docstring)
    p = jnp.exp(s - m)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)                  # [bq, 1]
    acc = jax.lax.dot_general(p, v_ref[0, 0].astype(jnp.float32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0] = jnp.where(l > 0, acc / l, 0.0).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)


def _bwd_kernel(*refs, causal: bool, block_q: int, sm_scale: float,
                segmented: bool, q_tiles: int):
    if segmented:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sq_ref, sk_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
        seg_q, seg_k = sq_ref[0], sk_ref[0]
    else:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
        seg_q = seg_k = None
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)                     # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)                     # [S, D]
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)                   # [bq, D]
    o = o_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                     # [bq, 1]
    s = jax.lax.dot_general(q * sm_scale, k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _mask_for(i, block_q, s.shape[-1], causal, seg_q, seg_k)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    # softmax weights straight from the saved log-sum-exp; masked (and
    # fully-masked: -inf - -inf = nan) lanes zeroed exactly
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    p = jnp.where(jnp.isfinite(lse), p, 0.0)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)         # [bq, 1]
    ds = p * (dp - delta) * sm_scale                        # [bq, S]
    dq_ref[0, 0] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(i == q_tiles - 1)
    def _write():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _fwd_call(q, k, v, segment_ids, causal, sm_scale, block_q,
              interpret):
    b, h, s, d = q.shape
    grid = (b, h, s // block_q)
    segmented = segment_ids is not None
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
    ]
    args = [q, k, v]
    if segmented:
        # the key side is the whole row: one block, index 0
        in_specs += _segment_specs(block_q, s, lambda i: i, lambda i: 0)
        args += _segment_planes(segment_ids)
    kernel = functools.partial(_fwd_kernel, causal=causal,
                               block_q=block_q, sm_scale=sm_scale,
                               segmented=segmented)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="bigdl_flash_fwd",
    )(*args)


def _bwd_call(q, k, v, o, do, lse, segment_ids, causal, sm_scale,
              block_q, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    q_tiles = s // block_q
    grid = (b, h, q_tiles)
    segmented = segment_ids is not None
    tile = pl.BlockSpec((1, 1, block_q, d),
                        lambda b_, h_, i: (b_, h_, i, 0))
    full = pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0))
    in_specs = [tile, full, full, tile, tile,
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, i: (b_, h_, i, 0))]
    args = [q, k, v, o, do, lse]
    if segmented:
        # the key side is the whole row: one block, index 0
        in_specs += _segment_specs(block_q, s, lambda i: i, lambda i: 0)
        args += _segment_planes(segment_ids)
    kernel = functools.partial(_bwd_kernel, causal=causal,
                               block_q=block_q, sm_scale=sm_scale,
                               segmented=segmented, q_tiles=q_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[tile, full, full],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="bigdl_flash_bwd",
    )(*args)


def _compiler_params():
    """q tiles iterate innermost and carry the backward's dK/dV
    scratch, so that axis is "arbitrary" (sequential); batch and heads
    are parallel."""
    return tpu_compiler_params(("parallel", "parallel", "arbitrary"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, segment_ids, causal, sm_scale, block_q, interpret):
    out, _ = _fwd_call(q, k, v, segment_ids, causal, sm_scale, block_q,
                       interpret)
    return out


def _flash_fwd(q, k, v, segment_ids, causal, sm_scale, block_q,
               interpret):
    # the kernels read and write the log-sum-exp as a [B, H, S, 1]
    # column (a (block_q, 1) tile is a legal TPU block; a rank-1
    # (block_q,) strip of [B, H, S] is not), but a trailing dim of 1
    # pads to 128 lanes in HBM — so the saved residual is the compact
    # [B, H, S] and the backward re-expands it
    out, lse = _fwd_call(q, k, v, segment_ids, causal, sm_scale,
                         block_q, interpret)
    return out, (q, k, v, out, lse[..., 0], segment_ids)


def _flash_bwd(causal, sm_scale, block_q, interpret, res, g):
    q, k, v, out, lse, segment_ids = res
    dq, dk, dv = _bwd_call(q, k, v, out, g, lse[..., None],
                           segment_ids, causal, sm_scale, block_q,
                           interpret)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------
# Blockwise long-context path: key axis tiled through VMEM.
#
# The full-row kernels above hold one [block_q, S] strip plus the whole
# K/V in VMEM — past ~12 MiB of working set (S≈24K at D=64 f32) Mosaic
# would OOM, so dispatch historically DECLINED and S=32K fell back to
# the O(S²) einsum. These kernels are the classic online-softmax
# blockwise form instead: the grid also tiles the KEY axis, one
# [block_q, block_k] score tile lives at a time, and the running
# (m, l, acc) state is rescaled by exp(m_old - m_new) per key tile in
# VMEM scratch. Working set is O(block_q·block_k + (block_q+block_k)·D)
# — independent of S — so S=128K runs fused.
#
# The rescaling makes results depend on where key-block boundaries
# fall, which breaks the packed-slab BITWISE contract the full-row
# kernels keep (module docstring) — so this path is tolerance-
# contract, reserved by dispatch for shapes the full-row kernels
# cannot hold, and never silently substituted under the budget.
# Causal masking skips fully-masked key tiles outright (the FLOP win
# that makes causal blockwise ~2x the dense form).

#: lane width of the (m, l) running-statistics scratch rows — the f32
#: min-tile lane count, stored broadcast so no width-1 lane slicing
#: ever reaches Mosaic
_STAT_LANES = 128


def _tile_mask(i, j, block_q, block_k, causal, seg_q, seg_k):
    """Keep-mask for score tile (query tile ``i``, key tile ``j``):
    ``[block_q, block_k]``, or None when nothing masks."""
    mask = None
    if causal:
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = cols <= rows
    if seg_q is not None:
        seg = seg_q == seg_k      # [bq, 1] column vs [1, S|bk] row
        mask = seg if mask is None else mask & seg
    return mask


def _bw_fwd_kernel(*refs, causal: bool, block_q: int, block_k: int,
                   sm_scale: float, segmented: bool, k_tiles: int):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
         m_acc, l_acc, acc) = refs
        seg_q, seg_k = sq_ref[0], sk_ref[0]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs[:5]
        m_acc, l_acc, acc = refs[5:]
        seg_q = seg_k = None
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)

    # causal: a key tile strictly right of the query tile's last row is
    # fully masked — skip its FLOPs and leave the carry untouched
    live = (j * block_k <= i * block_q + block_q - 1) if causal \
        else (j >= 0)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [bq, D]
        k = k_ref[0, 0]                                     # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(i, j, block_q, block_k, causal, seg_q, seg_k)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        # scratch rows hold the stat broadcast across _STAT_LANES; a
        # lane-reduce recovers it without a width-1 lane slice
        m_old = jnp.max(m_acc[...], axis=-1, keepdims=True)  # [bq, 1]
        l_old = jnp.max(l_acc[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        # m_new = -inf only while EVERY lane so far is masked (any
        # unmasked lane is a finite dot product); exp guards below
        # keep those all-masked rows at exact (0, 0) carries, no NaN
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        p = jnp.where(jnp.isfinite(m_new), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_old),
                          jnp.exp(m_old - m_new), 0.0)     # [bq, 1]
        l_new = l_old * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
        l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)

    @pl.when(j == k_tiles - 1)
    def _finalize():
        m = jnp.max(m_acc[...], axis=-1, keepdims=True)
        l = jnp.max(l_acc[...], axis=-1, keepdims=True)
        o_ref[0, 0] = jnp.where(l > 0, acc[...] / l, 0.0) \
            .astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)


def _bw_fwd_call(q, k, v, segment_ids, causal, sm_scale, block_q,
                 block_k, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    k_tiles = s // block_k
    grid = (b, h, s // block_q, k_tiles)
    segmented = segment_ids is not None
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, h_, i, j: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h_, i, j: (b_, h_, j, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h_, i, j: (b_, h_, j, 0)),
    ]
    args = [q, k, v]
    if segmented:
        in_specs += _segment_specs(block_q, block_k,
                                   lambda i, j: i, lambda i, j: j)
        args += _segment_planes(segment_ids)
    kernel = functools.partial(_bw_fwd_kernel, causal=causal,
                               block_q=block_q, block_k=block_k,
                               sm_scale=sm_scale, segmented=segmented,
                               k_tiles=k_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_bw_compiler_params(),
        interpret=interpret,
        name="bigdl_flash_blockwise_fwd",
    )(*args)


def _bw_dq_kernel(*refs, causal: bool, block_q: int, block_k: int,
                  sm_scale: float, segmented: bool, k_tiles: int):
    if segmented:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sq_ref, sk_ref,
         dq_ref, dq_acc) = refs
        seg_q, seg_k = sq_ref[0], sk_ref[0]
    else:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dq_ref, dq_acc) = refs
        seg_q = seg_k = None
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (j * block_k <= i * block_q + block_q - 1) if causal \
        else (j >= 0)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                 # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                                 # [bq, 1]
        s = jax.lax.dot_general(q * sm_scale, k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(i, j, block_q, block_k, causal, seg_q, seg_k)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        # exact per-lane softmax weights from the saved log-sum-exp —
        # no rescaling in the backward, each tile's p is final
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        p = jnp.where(jnp.isfinite(lse), p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)     # [bq, 1]
        ds = p * (dp - delta) * sm_scale                    # [bq, bk]
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == k_tiles - 1)
    def _write():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bw_dkv_kernel(*refs, causal: bool, block_q: int, block_k: int,
                   sm_scale: float, segmented: bool, q_tiles: int):
    if segmented:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sq_ref, sk_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        seg_q, seg_k = sq_ref[0], sk_ref[0]
    else:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        seg_q = seg_k = None
    j, i = pl.program_id(2), pl.program_id(3)   # key tile outer here

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (j * block_k <= i * block_q + block_q - 1) if causal \
        else (i >= 0)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                 # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        s = jax.lax.dot_general(q * sm_scale, k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(i, j, block_q, block_k, causal, seg_q, seg_k)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        p = jnp.where(jnp.isfinite(lse), p, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == q_tiles - 1)
    def _write():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bw_bwd_call(q, k, v, o, do, lse, segment_ids, causal, sm_scale,
                 block_q, block_k, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    q_tiles, k_tiles = s // block_q, s // block_k
    segmented = segment_ids is not None
    q_tile = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h_, i, j: (b_, h_, i, 0))
    k_tile = pl.BlockSpec((1, 1, block_k, d),
                          lambda b_, h_, i, j: (b_, h_, j, 0))
    lse_tile = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b_, h_, i, j: (b_, h_, i, 0))
    seg = _segment_planes(segment_ids) if segmented else []

    # pass 1 — dq: query tile outer, key tiles stream innermost
    in_specs = [q_tile, k_tile, k_tile, q_tile, q_tile, lse_tile]
    if segmented:
        in_specs += _segment_specs(block_q, block_k,
                                   lambda i, j: i, lambda i, j: j)
    dq = pl.pallas_call(
        functools.partial(_bw_dq_kernel, causal=causal,
                          block_q=block_q, block_k=block_k,
                          sm_scale=sm_scale, segmented=segmented,
                          k_tiles=k_tiles),
        grid=(b, h, q_tiles, k_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_bw_compiler_params(),
        interpret=interpret,
        name="bigdl_flash_blockwise_dq",
    )(q, k, v, o, do, lse, *seg)

    # pass 2 — dk/dv: key tile outer, query tiles stream innermost
    # (grid ids arrive as (b, h, j, i) so the index maps swap)
    q_tile2 = pl.BlockSpec((1, 1, block_q, d),
                           lambda b_, h_, j, i: (b_, h_, i, 0))
    k_tile2 = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, h_, j, i: (b_, h_, j, 0))
    lse_tile2 = pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, j, i: (b_, h_, i, 0))
    in_specs = [q_tile2, k_tile2, k_tile2, q_tile2, q_tile2, lse_tile2]
    if segmented:
        in_specs += _segment_specs(block_q, block_k,
                                   lambda j, i: i, lambda j, i: j)
    dk, dv = pl.pallas_call(
        functools.partial(_bw_dkv_kernel, causal=causal,
                          block_q=block_q, block_k=block_k,
                          sm_scale=sm_scale, segmented=segmented,
                          q_tiles=q_tiles),
        grid=(b, h, k_tiles, q_tiles),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_bw_compiler_params(),
        interpret=interpret,
        name="bigdl_flash_blockwise_dkv",
    )(q, k, v, o, do, lse, *seg)
    return dq, dk, dv


def _bw_compiler_params():
    """Both inner grid axes carry VMEM scratch across iterations, so
    they are "arbitrary" (sequential); batch and heads stay
    parallel."""
    return tpu_compiler_params(
        ("parallel", "parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _blockwise(q, k, v, segment_ids, causal, sm_scale, block_q,
               block_k, interpret):
    out, _ = _bw_fwd_call(q, k, v, segment_ids, causal, sm_scale,
                          block_q, block_k, interpret)
    return out


def _blockwise_fwd(q, k, v, segment_ids, causal, sm_scale, block_q,
                   block_k, interpret):
    out, lse = _bw_fwd_call(q, k, v, segment_ids, causal, sm_scale,
                            block_q, block_k, interpret)
    return out, (q, k, v, out, lse[..., 0], segment_ids)


def _blockwise_bwd(causal, sm_scale, block_q, block_k, interpret, res,
                   g):
    q, k, v, out, lse, segment_ids = res
    dq, dk, dv = _bw_bwd_call(q, k, v, out, g, lse[..., None],
                              segment_ids, causal, sm_scale, block_q,
                              block_k, interpret)
    return dq, dk, dv, None


_blockwise.defvjp(_blockwise_fwd, _blockwise_bwd)


def blockwise_flash_attention(q, k, v, segment_ids=None, *,
                              causal: bool = False,
                              sm_scale: float = None,
                              block_q: int = 128, block_k: int = 128,
                              interpret: bool = False):
    """Blockwise (online-softmax) flash attention over ``[B, H, S, D]``
    q/k/v — the long-context form whose VMEM working set is
    independent of S (section comment above has the rescaling
    math and why its contract is tolerance, not bitwise).
    Differentiable via the two-pass tiled backward. Use through
    :func:`bigdl_tpu.kernels.attention`, which owns eligibility, the
    VMEM-budget routing and the jnp fallback."""
    if q.ndim != 4:
        raise ValueError(f"blockwise_flash_attention wants [B,H,S,D], "
                         f"got {q.shape}")
    s, d = q.shape[-2], q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # the query tile is a row block of q/o/lse, the key tile is both a
    # row block of k/v and the LANE extent of the score tile and of
    # the key-side segment row — hence the two alignments
    block_q = fit_block(s, block_q, align=sublanes(q.dtype))
    block_k = fit_block(s, block_k, align=128)
    return _blockwise(q, k, v, segment_ids, bool(causal),
                      float(sm_scale), int(block_q), int(block_k),
                      bool(interpret))


def flash_attention(q, k, v, segment_ids=None, *, causal: bool = False,
                    sm_scale: float = None, block_q: int = 128,
                    interpret: bool = False):
    """Flash attention over ``[B, H, S, D]`` q/k/v (module docstring
    has the memory/exactness contract). ``segment_ids`` is the packed
    slab's ``[B, S]`` int32 plane — queries attend same-segment keys
    only, ANDed with ``causal``. Differentiable via the fused backward
    kernel; ``interpret`` runs the pallas interpreter (the CPU tier-1
    path). Use through :func:`bigdl_tpu.kernels.attention`, which
    owns eligibility and the jnp fallback."""
    if q.ndim != 4:
        raise ValueError(f"flash_attention wants [B,H,S,D], got "
                         f"{q.shape}")
    s, d = q.shape[-2], q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_q = fit_block(s, block_q, align=sublanes(q.dtype))
    return _flash(q, k, v, segment_ids, bool(causal), float(sm_scale),
                  int(block_q), bool(interpret))
