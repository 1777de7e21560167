"""Ragged decode attention — fetch and walk only the K/V tiles that
hold valid columns, and write the step's new column on the way.

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
512 columns. ``lengths`` and ``write_at`` are the ONLY ragged inputs:
block shapes stay static, so kernel variants never multiply the ≤
2-programs-per-bucket bound (:mod:`bigdl_tpu.generation.engine`).

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

**The kernel writes.** The cache it is handed does not hold the step's
new token yet: its key and value come beside it (``k_new``, ``v_new``
``[slots, Hkv, D]``) with the column they go to (``write_at``, a second
scalar-prefetched vector: the offset, or ``offset mod window`` in a
ring, which in a full ring is not ``lengths - 1``). Written from XLA,
that column is a ``while`` over the slots, each turn a read-modify-
write of ``heads x D`` elements in as many different vector tiles of
an array whose lanes are time: more device time than the attention
(PERF.md, PR 31). Here K and V are aliased to two outputs of the
cache's shape under a ``(1, 1, D, 128)`` block at ``write_at // 128``,
constant over the tile axis: in the grid step whose tile holds the
column (always walked: ``write_at < lengths``) the body copies the 128
lanes around it out of the fetched tile, one lane replaced, and the
pipeline writes that lane tile back once a slot-head. The new token is
attended without patching a ``[D, tile]`` tile: it is the online soft-
max's FIRST term (``m = q . k_new``, ``l = 1``, ``acc = v_new`` where
the carry used to start at ``-inf, 0, 0``), and the walk masks column
``write_at`` with the columns past ``lengths`` — still unwritten, or
in a full ring the oldest token, which this step overwrites.

K/V heads may be fewer than query heads (grouped-query attention):
the grid runs over the K/V heads and a program's query block is the
``[G, D]`` group that shares its K/V head, so every cached column is
read once for its ``G`` queries. ``G`` = 1 is multi-head attention.

One token per slot (decode's shape), grid ``(slots, kv heads, A /
tile)``; used through :func:`bigdl_tpu.kernels.decode_attention`,
which owns eligibility; the caller owns the fallback.
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


def _decode_kernel(len_ref, at_ref, q_ref, k_ref, v_ref, krow_ref,
                   vrow_ref, kcol_ref, vcol_ref, o_ref, ko_ref, vo_ref,
                   *scratch, tile: int, k_tiles: int, sm_scale: float):
    from jax.experimental import pallas as pl

    head = pl.program_id(1)
    n = len_ref[pl.program_id(0)]                       # valid KV columns
    w = at_ref[pl.program_id(0)]                        # the new column
    j = pl.program_id(2)
    lanes = ko_ref.shape[3]                             # 128, or all of T

    def scaled_q():
        return q_ref[0, 0].astype(jnp.float32) * sm_scale   # [G, D]

    def first():
        """The carry before any tile: the new token alone, whose
        column the cache tiles do not hold yet. Its score is the max,
        its weight 1, its value the accumulator."""
        k_new = krow_ref[0, pl.ds(head, 1), :]              # [1, D] f32
        v_new = vrow_ref[0, pl.ds(head, 1), :]
        m = jnp.sum(scaled_q() * k_new, axis=-1, keepdims=True)
        g, d = q_ref.shape[2:]
        return (m, jnp.ones((g, 1), jnp.float32),
                jnp.broadcast_to(v_new, (g, d)))

    def walk(m, l, acc):
        """One tile folded into the running (max, sum, accumulator)."""
        s = jax.lax.dot_general(scaled_q(), k_ref[0, 0].astype(jnp.float32),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)            # [D, tile]
        col = j * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        # column w still holds what this step overwrites (nothing yet,
        # or a full ring's oldest token): the new token is in the carry
        valid = (col < n) & (col != w)
        # whatever the cache holds past n (a NaN too) is kept out of
        # both products: -inf scores become exact zeros in p
        s = jnp.where(valid, s, _NEG_INF)               # [G, tile]
        vb = jnp.where(valid, vb, 0.0)
        # m is finite from the first carry on, so a tile whose every
        # column is masked (lengths == 1) leaves the carry as it was
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # both operands carry the tile's columns on the lanes
        acc = acc * alpha + jax.lax.dot_general(
            p, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    def write(src_ref, col_ref, dst_ref):
        """The lane tile that holds column w, as fetched, with that
        one lane replaced by the new column."""
        if lanes == tile:
            chunk = src_ref[0, 0]
        else:
            at = pl.multiple_of(w % tile // lanes * lanes, lanes)
            chunk = src_ref[0, 0, :, pl.ds(at, lanes)]  # [D, 128]
        cols = col_ref[0].astype(jnp.float32)           # [D, Hkv]
        mine = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1) == head
        # a sum over one value and negative zeros is that value, bit
        # for bit
        new = jnp.sum(jnp.where(mine, cols, -0.0), axis=1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
        dst_ref[0, 0] = jnp.where(lane == w % lanes,
                                  new.astype(dst_ref.dtype), chunk)

    if k_tiles == 1:
        # the block is one tile (1 <= n <= attend_len always): no carry
        _, l, acc = walk(*first())
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
        write(k_ref, kcol_ref, ko_ref)
        write(v_ref, vcol_ref, vo_ref)
        return
    m_ref, l_ref, acc_ref = scratch

    @pl.when(j == 0)
    def _():
        m_ref[...], l_ref[...], acc_ref[...] = first()

    @pl.when(j * tile < n)
    def _():
        m_ref[...], l_ref[...], acc_ref[...] = walk(
            m_ref[...], l_ref[...], acc_ref[...])

    # w < n, so this tile is always walked and its block fetched
    @pl.when(j == w // tile)
    def _():
        write(k_ref, kcol_ref, ko_ref)
        write(v_ref, vcol_ref, vo_ref)

    @pl.when(j == k_tiles - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


# jitted so that a decoder's layers, which call it with the same shapes,
# share ONE trace of the kernel and one lowering to Mosaic in the
# program that holds them (the enclosing trace finds the cached one):
# a 12-layer decode step traces and lowers in 1.3 s where it took 2.0
@functools.partial(jax.jit, static_argnames=("attend_len", "sm_scale",
                                             "interpret"))
def ragged_decode_attention(q, k, v, lengths, write_at, k_new, v_new, *,
                            attend_len: int = None,
                            sm_scale: float = None,
                            interpret: bool = False):
    """One decode step of attention over ragged KV, the step's new
    column written on the way: ``q`` is ``[slots, H, D]`` (the step's
    single token per slot), ``k``/``v`` are one layer's WHOLE cache
    ``[slots, Hkv, D, T]`` (``H`` a multiple of ``Hkv``; query heads
    ``j G .. (j + 1) G - 1`` share K/V head ``j``) — time on the
    lanes, the form :class:`~bigdl_tpu.generation.kv_cache.KVCache`
    stores, so nothing is sliced or transposed on the way in — as they
    stand BEFORE the step; ``k_new``/``v_new`` ``[slots, Hkv, D]`` are
    the new token's key and value in the cache's dtype and ``write_at``
    (int32 ``[slots]``) the column they go to. ``lengths`` is the host
    int32 ``[slots]`` of valid columns per slot once the new one is
    written (clamped into ``[1, attend_len]`` — a free slot reads one
    garbage column whose output is never consumed, matching the
    engine's inactive-slot contract); ``write_at`` is clamped under it,
    which for a live slot changes nothing. The slot attends the new
    token and its columns ``< lengths`` other than ``write_at``, which
    holds nothing yet or, in a ring that is full, the oldest token that
    this step overwrites. ``attend_len`` (static, default ``T``) is
    the ladder rung: the grid covers its first columns only, rounded
    up to whole 128-lane tiles, in tiles of :func:`kv_tile` columns.
    Returns ``(out [slots, H, D], k, v)``, the cache aliased to its
    inputs and changed in column ``write_at`` alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, h, d, t = k.shape
    if (q.ndim != 3 or q.shape[0] != slots or q.shape[2] != d
            or q.shape[1] % h or v.shape != k.shape):
        raise ValueError(f"q {q.shape} / v {v.shape} do not match "
                         f"cache [{slots},{h},{d},{t}]")
    if not (k_new.shape == v_new.shape == (slots, h, d)
            and k_new.dtype == v_new.dtype == k.dtype == v.dtype):
        raise ValueError(
            f"new columns {k_new.shape} {k_new.dtype} / {v_new.shape} "
            f"{v_new.dtype} are not [{slots},{h},{d}] {k.dtype}")
    g = q.shape[1] // h
    al = t if attend_len is None else int(attend_len)
    if not 1 <= al <= t:
        raise ValueError(f"attend_len={al} outside [1, {t}]")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    a = block_columns(t, al)
    tile = kv_tile(a, d, g, k.dtype.itemsize)
    k_tiles = a // tile
    # the written lane tile: 128 columns, or the whole of a cache no
    # lane tile divides (the K/V tile is then the whole of it too)
    lanes = _LANES if tile % _LANES == 0 else tile
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, al)
    write_at = jnp.clip(write_at.astype(jnp.int32), 0, lengths - 1)
    kernel = functools.partial(_decode_kernel, tile=tile, k_tiles=k_tiles,
                               sm_scale=float(sm_scale))
    # q and the output travel as [slots, Hkv, G, D]: Mosaic wants the
    # last two dims of a block to be (8, 128)-aligned or the whole
    # array's, and a (G, D) tile of a [.., G, D] array is the latter
    row = pl.BlockSpec((1, 1, g, d), lambda s, h_, j, n, w: (s, h_, 0, 0))
    # a tile past the slot's last valid one keeps that one's index, so
    # the pipeline fetches nothing for it
    cache = pl.BlockSpec(
        (1, 1, d, tile),
        lambda s, h_, j, n, w: (s, h_, 0,
                                jnp.minimum(j, (n[s] - 1) // tile)))
    # the new columns twice, a slot's at a time: as float32 rows (D on
    # the lanes) for the carry's first term, and as columns (D on the
    # sublanes, as a cache tile has it) for the write
    rows = pl.BlockSpec((1, h, d), lambda s, h_, j, n, w: (s, 0, 0))
    cols = pl.BlockSpec((1, d, h), lambda s, h_, j, n, w: (s, 0, 0))
    # constant over j: written back once a slot-head
    written = pl.BlockSpec(
        (1, 1, d, lanes), lambda s, h_, j, n, w: (s, h_, 0, w[s] // lanes))
    scratch = [] if k_tiles == 1 else [
        pltpu.VMEM((g, 1), jnp.float32), pltpu.VMEM((g, 1), jnp.float32),
        pltpu.VMEM((g, d), jnp.float32)]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    out, k, v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots, h, k_tiles),
            in_specs=[row, cache, cache, rows, rows, cols, cols],
            out_specs=[row, written, written],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((slots, h, g, d), q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        # operands count the two prefetched vectors: k -> 1, v -> 2
        input_output_aliases={3: 1, 4: 2},
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bigdl_ragged_decode",
    )(lengths, write_at, q.reshape(slots, h, g, d), k, v,
      f32(k_new), f32(v_new),
      jnp.swapaxes(k_new, 1, 2), jnp.swapaxes(v_new, 1, 2))
    return out.reshape(slots, h * g, d), k, v
