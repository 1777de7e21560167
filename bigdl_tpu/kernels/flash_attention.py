"""Fused flash attention for training — pallas, segment-mask aware.

The [S, S] score matrix never exists in HBM: the grid tiles the query
axis, K and V of a (batch, head) stay whole in VMEM, and each program
walks the key axis in square chunks, holding one ``[S, chunk]`` score
strip, computing a numerically-stable softmax over the full key row
and contracting straight into its ``[chunk, D]`` output — O(S·chunk)
live bytes in VMEM instead of O(S²) through HBM. Under ``causal`` the
chunks right of the diagonal are never computed. The backward
recomputes each chunk's weights from the saved log-sum-exp and
accumulates dK/dV across query tiles in VMEM scratch — no residual
score matrix either. On a v5e a layer of ``[4, 16, 1024, 64]``
bfloat16, forward + backward, reads 0.68 ms where the einsum form
reads 2.89 (PERF.md section 6, PR 35).

**Why full-row reductions instead of blockwise rescaling:** the
classic online-softmax rescales the running accumulator by
``exp(m_old - m_new)`` at every key block, which makes the result
depend on where block boundaries fall. Packed training slabs
(``bigdl_tpu.datapipe.packing``) put documents at arbitrary row
offsets, and the datapipe's contract is that a packed forward is
**bit-exact per token** against its neighbours' content — a guarantee
blockwise rescaling would trade for a tolerance. Here the max is the
whole row's before any weight is formed (the strip is kept for it),
so masked positions are *exact zeros* in every sum and nothing is
rescaled: the packed-slab bitwise contract survives the kernel
(tests/test_kernels.py asserts it per token). The decode kernel
(:mod:`bigdl_tpu.kernels.ragged_decode`), whose win is *skipping*
tail key blocks, uses the true online rescaling form — its contract is
tolerance, not bitwise.

Precision: every product takes its operands in their own dtype with a
float32 accumulator; max, exp and sums are float32; the weights (and
the backward's ``ds``) are rounded to the operands' dtype before the
product that consumes them, as ``nn.attention.dot_product_attention``
rounds its soft-max weights. float32 inputs stay float32.

Masking: ``causal`` and/or ``segment_ids`` (``[B, S]`` int32; queries
attend only same-segment keys — the packed-slab mask). Masked scores
are ``-inf`` so they vanish exactly from max/sum; a fully-masked query
row yields 0 output, not NaN.

Each ``pallas_call`` wrapper is a ``jax.jit`` of its own, so that a
model's layers, which call it with the same shapes, share ONE trace of
the kernel and one lowering to Mosaic in the program that holds them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import (FLASH_VMEM_LIMIT_MB, fit_block,
                                      sublanes, tpu_compiler_params)

__all__ = ["flash_attention", "blockwise_flash_attention", "fit_block"]

_NEG_INF = float("-inf")


def _segment_planes(segment_ids):
    """The ``[B, S]`` segment ids as the two planes the kernels read: a
    ``[B, S, 1]`` column and a ``[B, 1, S]`` row, so the same-segment
    mask is one 2-D broadcast compare — Mosaic takes no rank-1 vector,
    and a block's last two dims must be tile-aligned or the whole
    array's. Returned ``(column, row)``."""
    seg = segment_ids.astype(jnp.int32)
    return seg[:, :, None], seg[:, None, :]


def _fold_scale(q, sm_scale: float):
    """``(q, what is left for the scores)``: the soft-max scale goes
    onto q before the product where that rounds nothing the plain form
    would not — float32 operands, or a power of two (``D`` = 64: 1/8)
    — and onto the float32 scores otherwise."""
    if q.dtype == jnp.float32 or math.frexp(sm_scale)[0] == 0.5:
        return q * sm_scale, 1.0
    return q, sm_scale


# The full-row kernels hold the scores TRANSPOSED: keys on the
# sublanes, queries on the lanes. A query's max, sum, log-sum-exp and
# the backward's ``delta`` are then ``[1, block]`` rows — reduced over
# sublanes, broadcast for free, and stored lane-dense as ``[B, H, 1,
# S]`` (a ``[B, H, S, 1]`` column pads 128 times in HBM) — and of the
# five products only two small ones (``v^T p^T``, ``k^T ds^T``) have a
# transposed left operand. K and V stay whole in VMEM for a (batch,
# head); the key axis is walked in square chunks of ``block`` rows,
# and under ``causal`` the chunks right of the diagonal are never
# computed and only the diagonal chunk builds a mask.

def _at(j, block):
    """Rows of chunk ``j``."""
    return pl.ds(pl.multiple_of(j * block, block), block)


def _keep(scores, j, diagonal, seg_q, sk_ref, block):
    """``scores`` ``[block keys, block queries]`` of key chunk ``j``
    with what the query may not see at ``-inf``."""
    keep = None
    if diagonal:
        keys = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        queries = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        keep = keys <= queries
    if seg_q is not None:
        seg_k = sk_ref[0, _at(j, block), :]
        same = seg_k == seg_q           # [block, 1] column vs [1, block] row
        keep = same if keep is None else keep & same
    return scores if keep is None else jnp.where(keep, scores, _NEG_INF)


def _walk(i, chunks, causal, body, carry):
    """``body(j, diagonal, carry)`` over the key chunks query tile
    ``i`` sees: all of them, or under ``causal`` those left of the
    diagonal in a loop and then the diagonal one."""
    if not causal:
        if chunks == 1:
            return body(0, False, carry)
        return jax.lax.fori_loop(
            0, chunks, lambda j, c: body(j, False, c), carry)
    if chunks == 1:
        return body(0, True, carry)
    carry = jax.lax.fori_loop(0, i, lambda j, c: body(j, False, c), carry)
    return body(i, True, carry)


def _fwd_kernel(*refs, causal: bool, block: int, chunks: int,
                sm_scale: float, segmented: bool):
    if segmented:
        q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, s_ref = refs
        seg_q = sq_ref[0]                                   # [1, block]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, s_ref = refs
        seg_q = sk_ref = None
    i = pl.program_id(2)
    q, on_s = _fold_scale(q_ref[0, 0], sm_scale)            # [block, D]

    def scores(j, diagonal, m):
        s = jax.lax.dot_general(k_ref[0, 0, _at(j, block), :], q,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if on_s != 1.0:
            s = s * on_s
        s = _keep(s, j, diagonal, seg_q, sk_ref, block)
        s_ref[_at(j, block), :] = s
        return jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))

    # the strip is kept so that the max is the WHOLE row's before any
    # weight is formed: no running rescale, masked lanes exact zeros
    # (module docstring)
    m = _walk(i, chunks, causal, scores,
              jnp.full((1, block), _NEG_INF, jnp.float32))
    m = jnp.where(m == _NEG_INF, 0.0, m)    # a row with nothing to see

    def weigh(j, _, carry):
        l, acc = carry
        p = jnp.exp(s_ref[_at(j, block), :] - m)
        l = l + jnp.sum(p, axis=0, keepdims=True)
        acc = acc + jax.lax.dot_general(
            v_ref[0, 0, _at(j, block), :], p.astype(v_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return l, acc

    d = q.shape[-1]
    l, acc = _walk(i, chunks, causal, weigh,
                   (jnp.zeros((1, block), jnp.float32),
                    jnp.zeros((d, block), jnp.float32)))
    out = jnp.where(l > 0, acc / l, 0.0)                    # [D, block]
    o_ref[0, 0] = out.T.astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)


def _bwd_kernel(*refs, causal: bool, block: int, chunks: int,
                sm_scale: float, segmented: bool):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
        seg_q = sq_ref[0]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
        seg_q = sk_ref = None
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0]                                         # [block, D]
    qs, on_s = _fold_scale(q, sm_scale)
    do = do_ref[0, 0]                                       # [block, D]
    lse = lse_ref[0, 0]                                     # [1, block]
    # a row with nothing to see has lse -inf: exp(s - inf) is 0 there
    lse = jnp.where(lse == _NEG_INF, jnp.inf, lse)
    delta = delta_ref[0, 0]                                 # [1, block]

    def chunk(j, diagonal, dq):
        at = _at(j, block)
        kj, vj = k_ref[0, 0, at, :], v_ref[0, 0, at, :]
        s = jax.lax.dot_general(kj, qs, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if on_s != 1.0:
            s = s * on_s
        s = _keep(s, j, diagonal, seg_q, sk_ref, block)
        # the weights straight from the saved log-sum-exp: each
        # chunk's are final, masked lanes exact zeros
        p = jnp.exp(s - lse)                                # [keys, queries]
        dv_acc[at, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(vj, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # the scale's factor goes onto dq and dk once, at the end
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[at, :] += jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(
            kj, ds, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [D, queries]

    dq = _walk(i, chunks, causal, chunk,
               jnp.zeros((q.shape[-1], block), jnp.float32))
    dq_ref[0, 0] = (dq * sm_scale).T.astype(dq_ref.dtype)

    @pl.when(i == chunks - 1)
    def _write():
        dk_ref[0, 0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _row_specs(b, h, s, d, block, segmented):
    """Block specs shared by the two full-row calls: a query tile, the
    whole K / V of a (batch, head), a ``[1, block]`` row of a
    ``[B, H, 1, S]`` statistic, and the segment planes' two."""
    tile = pl.BlockSpec((1, 1, block, d), lambda b_, h_, i: (b_, h_, i, 0))
    full = pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0))
    row = pl.BlockSpec((1, 1, 1, block), lambda b_, h_, i: (b_, h_, 0, i))
    seg = [pl.BlockSpec((1, 1, block), lambda b_, h_, i: (b_, 0, i)),
           pl.BlockSpec((1, s, 1), lambda b_, h_, i: (b_, 0, 0))] \
        if segmented else []
    return tile, full, row, seg


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block", "interpret"))
def _fwd_call(q, k, v, segment_ids, *, causal, sm_scale, block,
              interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    chunks = s // block
    segmented = segment_ids is not None
    tile, full, row, seg = _row_specs(b, h, s, d, block, segmented)
    args = [q, k, v]
    if segmented:
        column, row_plane = _segment_planes(segment_ids)
        args += [row_plane, column]
    kernel = functools.partial(_fwd_kernel, causal=causal, block=block,
                               chunks=chunks, sm_scale=sm_scale,
                               segmented=segmented)
    return pl.pallas_call(
        kernel,
        grid=(b, h, chunks),
        in_specs=[tile, full, full] + seg,
        out_specs=[tile, row],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((s, block), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="bigdl_flash_fwd",
    )(*args)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block", "interpret"))
def _bwd_call(q, k, v, o, do, lse, segment_ids, *, causal, sm_scale,
              block, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    chunks = s // block
    segmented = segment_ids is not None
    tile, full, row, seg = _row_specs(b, h, s, d, block, segmented)
    # delta = sum(do * o) a query, a lane-dense row like the
    # log-sum-exp: one fused pass of XLA over two [B, H, S, D] arrays
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    args = [q, k, v, do, lse, delta]
    if segmented:
        column, row_plane = _segment_planes(segment_ids)
        args += [row_plane, column]
    kernel = functools.partial(_bwd_kernel, causal=causal, block=block,
                               chunks=chunks, sm_scale=sm_scale,
                               segmented=segmented)
    return pl.pallas_call(
        kernel,
        grid=(b, h, chunks),
        in_specs=[tile, full, full, tile, row, row] + seg,
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
    are parallel. The dispatch hands these kernels what its estimate
    (``dispatch._flash_vmem_bytes``) puts 4 MiB or more inside the
    limit asked for here."""
    return tpu_compiler_params(
        ("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=FLASH_VMEM_LIMIT_MB << 20)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, segment_ids, causal, sm_scale, block, interpret):
    out, _ = _fwd_call(q, k, v, segment_ids, causal=causal,
                       sm_scale=sm_scale, block=block,
                       interpret=interpret)
    return out


def _flash_fwd(q, k, v, segment_ids, causal, sm_scale, block, interpret):
    out, lse = _fwd_call(q, k, v, segment_ids, causal=causal,
                         sm_scale=sm_scale, block=block,
                         interpret=interpret)
    return out, (q, k, v, out, lse, segment_ids)


def _flash_bwd(causal, sm_scale, block, interpret, res, g):
    q, k, v, out, lse, segment_ids = res
    dq, dk, dv = _bwd_call(q, k, v, out, g, lse, segment_ids,
                           causal=causal, sm_scale=sm_scale, block=block,
                           interpret=interpret)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------
# Blockwise long-context path: key axis tiled through VMEM.
#
# The full-row kernels above hold one [S, chunk] strip plus the whole
# K/V in VMEM — past the budget the dispatch prices them at
# (``dispatch._flash_vmem_bytes``; S of 4096 to 8192) Mosaic would
# OOM, so dispatch historically DECLINED and S=32K fell back to
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
# contract, reserved by dispatch for unpacked shapes the full-row
# kernels cannot hold, and never silently substituted for them.
# Causal masking skips fully-masked key tiles outright, body and
# fetch. They still keep the scores query-major and the log-sum-exp a
# [B, H, S, 1] column (compacted by XLA for the residual): where the
# full-row form fits it reads 1.6x faster (PERF.md section 6, PR 35).

#: lane width of the (m, l) running-statistics scratch rows — the f32
#: min-tile lane count, stored broadcast so no width-1 lane slicing
#: ever reaches Mosaic
_STAT_LANES = 128


def _segment_specs(block_q, block_k, q_tile, k_tile):
    """Block specs for :func:`_segment_planes`' column (query side) and
    row (key side). ``q_tile`` / ``k_tile`` pick the query / key tile
    index out of the grid ids that follow ``(batch, head)``."""
    return [
        pl.BlockSpec((1, block_q, 1),
                     lambda b_, h_, *ids: (b_, q_tile(*ids), 0)),
        pl.BlockSpec((1, 1, block_k),
                     lambda b_, h_, *ids: (b_, 0, k_tile(*ids))),
    ]


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


def _last_live(i, block_q, block_k):
    """The last key tile query tile ``i`` sees under ``causal``."""
    return (i * block_q + block_q - 1) // block_k


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
    # fully masked — skip its FLOPs and leave the carry untouched (its
    # block index is the last live tile's: nothing is fetched either)
    live = (j <= _last_live(i, block_q, block_k)) if causal else (j >= 0)

    @pl.when(live)
    def _tile():
        q, on_s = _fold_scale(q_ref[0, 0], sm_scale)        # [bq, D]
        s = jax.lax.dot_general(q, k_ref[0, 0],             # [bk, D]
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if on_s != 1.0:
            s = s * on_s
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
            p.astype(v_ref.dtype), v_ref[0, 0],
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


def _bw_specs(d, block_q, block_k, causal, segmented, key_outer):
    """The blockwise calls' block specs: a query-side tile, a key-side
    tile, the log-sum-exp column and the segment planes' two, for a
    grid of (batch, head, query tile, key tile) or, ``key_outer``,
    (batch, head, key tile, query tile). Under ``causal`` a dead
    tile's block index is clamped to the nearest live one, so the
    pipeline fetches nothing for a step whose body is skipped."""
    def ids(*g):
        j, i = g if key_outer else g[::-1]
        if causal and key_outer:
            i = jnp.maximum(i, j * block_k // block_q)
        elif causal:
            j = jnp.minimum(j, _last_live(i, block_q, block_k))
        return i, j

    q_tile = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h_, *g: (b_, h_, ids(*g)[0], 0))
    k_tile = pl.BlockSpec((1, 1, block_k, d),
                          lambda b_, h_, *g: (b_, h_, ids(*g)[1], 0))
    lse_tile = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b_, h_, *g: (b_, h_, ids(*g)[0], 0))
    seg = _segment_specs(block_q, block_k, lambda *g: ids(*g)[0],
                         lambda *g: ids(*g)[1]) if segmented else []
    return q_tile, k_tile, lse_tile, seg


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret"))
def _bw_fwd_call(q, k, v, segment_ids, *, causal, sm_scale, block_q,
                 block_k, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    k_tiles = s // block_k
    segmented = segment_ids is not None
    q_tile, k_tile, lse_tile, seg = _bw_specs(
        d, block_q, block_k, causal, segmented, key_outer=False)
    args = [q, k, v]
    if segmented:
        args += _segment_planes(segment_ids)
    kernel = functools.partial(_bw_fwd_kernel, causal=causal,
                               block_q=block_q, block_k=block_k,
                               sm_scale=sm_scale, segmented=segmented,
                               k_tiles=k_tiles)
    out_tile = pl.BlockSpec((1, 1, block_q, d),
                            lambda b_, h_, i, j: (b_, h_, i, 0))
    out_lse = pl.BlockSpec((1, 1, block_q, 1),
                           lambda b_, h_, i, j: (b_, h_, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, s // block_q, k_tiles),
        in_specs=[q_tile, k_tile, k_tile] + seg,
        out_specs=[out_tile, out_lse],
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


def _bw_grad_tile(refs, i, j, *, causal, block_q, block_k, sm_scale,
                  segmented):
    """What both backward passes form for score tile (query tile ``i``,
    key tile ``j``) from their first eight operands: the softmax
    weights ``p`` (float32, exact per lane from the saved log-sum-exp —
    no rescaling, each tile's are final) and ``ds`` WITHOUT the scale's
    factor, rounded to the operands' dtype; with the tile's q, k, do."""
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
    seg_q, seg_k = (refs[6][0], refs[7][0]) if segmented else (None, None)
    q, k, do = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]       # [bq|bk, D]
    qs, on_s = _fold_scale(q, sm_scale)
    s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if on_s != 1.0:
        s = s * on_s
    mask = _tile_mask(i, j, block_q, block_k, causal, seg_q, seg_k)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    lse = lse_ref[0, 0]                                     # [bq, 1]
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    p = jnp.where(jnp.isfinite(lse), p, 0.0)
    dp = jax.lax.dot_general(do, v_ref[0, 0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jnp.sum(do.astype(jnp.float32)
                    * o_ref[0, 0].astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [bq, 1]
    return p, (p * (dp - delta)).astype(q.dtype), q, k, do


def _bw_dq_kernel(*refs, causal: bool, block_q: int, block_k: int,
                  sm_scale: float, segmented: bool, k_tiles: int):
    dq_ref, dq_acc = refs[-2:]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (j <= _last_live(i, block_q, block_k)) if causal else (j >= 0)

    @pl.when(live)
    def _tile():
        _, ds, _, k, _ = _bw_grad_tile(
            refs, i, j, causal=causal, block_q=block_q, block_k=block_k,
            sm_scale=sm_scale, segmented=segmented)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == k_tiles - 1)
    def _write():
        dq_ref[0, 0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _bw_dkv_kernel(*refs, causal: bool, block_q: int, block_k: int,
                   sm_scale: float, segmented: bool, q_tiles: int):
    dk_ref, dv_ref, dk_acc, dv_acc = refs[-4:]
    j, i = pl.program_id(2), pl.program_id(3)   # key tile outer here

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (j <= _last_live(i, block_q, block_k)) if causal else (i >= 0)

    @pl.when(live)
    def _tile():
        p, ds, q, _, do = _bw_grad_tile(
            refs, i, j, causal=causal, block_q=block_q, block_k=block_k,
            sm_scale=sm_scale, segmented=segmented)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == q_tiles - 1)
    def _write():
        dk_ref[0, 0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret"))
def _bw_bwd_call(q, k, v, o, do, lse, segment_ids, *, causal, sm_scale,
                 block_q, block_k, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    q_tiles, k_tiles = s // block_q, s // block_k
    segmented = segment_ids is not None
    seg = list(_segment_planes(segment_ids)) if segmented else []
    static = dict(causal=causal, block_q=block_q, block_k=block_k,
                  sm_scale=sm_scale, segmented=segmented)

    # pass 1 — dq: query tile outer, key tiles stream innermost
    q_tile, k_tile, lse_tile, seg_specs = _bw_specs(
        d, block_q, block_k, causal, segmented, key_outer=False)
    dq = pl.pallas_call(
        functools.partial(_bw_dq_kernel, k_tiles=k_tiles, **static),
        grid=(b, h, q_tiles, k_tiles),
        in_specs=[q_tile, k_tile, k_tile, q_tile, q_tile, lse_tile]
        + seg_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_bw_compiler_params(),
        interpret=interpret,
        name="bigdl_flash_blockwise_dq",
    )(q, k, v, o, do, lse, *seg)

    # pass 2 — dk/dv: key tile outer, query tiles stream innermost
    q_tile, k_tile, lse_tile, seg_specs = _bw_specs(
        d, block_q, block_k, causal, segmented, key_outer=True)
    out_tile = pl.BlockSpec((1, 1, block_k, d),
                            lambda b_, h_, j, i: (b_, h_, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bw_dkv_kernel, q_tiles=q_tiles, **static),
        grid=(b, h, k_tiles, q_tiles),
        in_specs=[q_tile, k_tile, k_tile, q_tile, q_tile, lse_tile]
        + seg_specs,
        out_specs=[out_tile, out_tile],
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
    out, _ = _bw_fwd_call(q, k, v, segment_ids, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return out


def _blockwise_fwd(q, k, v, segment_ids, causal, sm_scale, block_q,
                   block_k, interpret):
    out, lse = _bw_fwd_call(q, k, v, segment_ids, causal=causal,
                            sm_scale=sm_scale, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    # a trailing dim of 1 pads to 128 lanes in HBM: the saved residual
    # is the compact [B, H, S] and the backward re-expands it
    return out, (q, k, v, out, lse[..., 0], segment_ids)


def _blockwise_bwd(causal, sm_scale, block_q, block_k, interpret, res,
                   g):
    q, k, v, out, lse, segment_ids = res
    dq, dk, dv = _bw_bwd_call(q, k, v, out, g, lse[..., None],
                              segment_ids, causal=causal,
                              sm_scale=sm_scale, block_q=block_q,
                              block_k=block_k, interpret=interpret)
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
                    sm_scale: float = None, block_q: int = 256,
                    interpret: bool = False):
    """Flash attention over ``[B, H, S, D]`` q/k/v (module docstring
    has the memory/exactness contract). ``segment_ids`` is the packed
    slab's ``[B, S]`` int32 plane — queries attend same-segment keys
    only, ANDed with ``causal``. ``block_q`` is the preferred side of
    the square score chunk (query tile and key chunk alike), shrunk to
    a divisor of ``S``. Differentiable via the fused backward
    kernel; ``interpret`` runs the pallas interpreter (the CPU tier-1
    path). Use through :func:`bigdl_tpu.kernels.attention`, which
    owns eligibility, the tile and the jnp fallback."""
    if q.ndim != 4:
        raise ValueError(f"flash_attention wants [B,H,S,D], got "
                         f"{q.shape}")
    s, d = q.shape[-2], q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # the chunk's side lies on the lanes of the score strip and of the
    # statistics' rows: whole lane tiles for the compiler, any divisor
    # for the interpreter (small test shapes still walk several chunks)
    block = fit_block(s, block_q, align=1 if interpret else 128)
    return _flash(q, k, v, segment_ids, bool(causal), float(sm_scale),
                  int(block), bool(interpret))
