"""Paged ragged decode — per-slot KV read through a page table.

:mod:`bigdl_tpu.kernels.ragged_decode` already bounds decode reads by
``lengths[i]``, but it still assumes each slot's KV rows live in ONE
contiguous ``[T, D]`` stripe of the preallocated cache. At long
context that contiguity is the allocator's enemy: a 128K ``max_len``
cache must reserve the full stripe per slot up front, so slot count —
the continuous-batching width — is priced at the worst case even when
most requests are short. The paged form breaks the stripe into fixed
``page_size`` **blocks** owned by a shared pool:

- ``k_pages``/``v_pages`` are ``[num_pages, H, page_size, D]`` pools;
- ``page_table [slots, pages_per_slot]`` holds each slot's physical
  page ids, in sequence order;
- ``lengths [slots]`` is the same host ragged bound the contiguous
  kernel reads.

The kernel walks grid ``(slots, heads, pages_per_slot)`` with the page
table **scalar-prefetched** (``PrefetchScalarGridSpec``): page ``j`` of
slot ``s`` is fetched by BlockSpec index map ``table[s, j]`` — the
indirection costs an SMEM read at grid-index time, not a gather — and
folded into the online-softmax carry exactly like one ``block_k`` tile
of the contiguous kernel. Pages past ``cdiv(lengths[s], page_size)``
are skipped (``pl.when``), so the per-step read volume stays
``O(lengths[s])`` regardless of how long the pool is.

Token identity: for any page table, the kernel computes the same
online-softmax reduction as the contiguous kernel over the rows the
table names, so decoding through a paged view of a contiguous cache is
**token-identical** to contiguous decode (asserted in
tests/test_longctx.py, shuffled tables included — bitwise vs the
ragged kernel when ``page == block_k``).

:func:`paged_view` builds the ``(pool, table)`` pair from a contiguous
``[slots, H, T, D]`` cache slice — the bridge the tests and the
dispatch escape hatch use; a production long-context allocator owns
its pool directly and hands the table over.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import tpu_compiler_params

__all__ = ["paged_decode_attention", "paged_view"]

_NEG_INF = float("-inf")


def paged_view(k, v, page_size: int):
    """Reshape one contiguous ``[slots, H, T, D]`` cache slice into a
    ``(k_pages, v_pages, page_table)`` paged triple: page ``j`` of
    slot ``s`` is rows ``[j*page_size, (j+1)*page_size)`` and the
    identity table maps it to pool id ``s * (T // page_size) + j``.
    ``page_size`` must divide ``T``. (Test/bridge utility — a real
    paged allocator owns the pool; the kernel only sees the table.)"""
    slots, h, t, d = k.shape
    if t % page_size:
        raise ValueError(f"page_size={page_size} must divide the "
                         f"cache time axis T={t}")
    pages_per_slot = t // page_size

    def pool(x):
        x = x.reshape(slots, h, pages_per_slot, page_size, d)
        return x.transpose(0, 2, 1, 3, 4).reshape(
            slots * pages_per_slot, h, page_size, d)

    table = jnp.arange(slots * pages_per_slot, dtype=jnp.int32).reshape(
        slots, pages_per_slot)
    return pool(k), pool(v), table


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int,
                  pages_per_slot: int, sm_scale: float):
    slot, j = pl.program_id(0), pl.program_id(2)
    n = len_ref[slot]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages wholly past the slot's valid rows contribute nothing —
    # skip the flops AND the rescale (the carry is already exact)
    @pl.when(j * page_size < n)
    def _tile():
        q = q_ref[0, 0].reshape(1, -1).astype(jnp.float32) * sm_scale
        kb = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        s = jnp.where(col < n, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # page 0 column 0 is always valid (lengths clamped >= 1), so
        # m_new is finite from the first live page on and alpha's
        # exp(-inf - finite) underflows to an exact 0 for the
        # zero-initialized carry — same first-tile story as the
        # contiguous kernel's fori_loop
        alpha = jnp.exp(m - m_new)
        p = jnp.where(col < n, jnp.exp(s - m_new), 0.0)
        vb = v_ref[0, 0].astype(jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pages_per_slot - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...])[0].astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           sm_scale: float = None,
                           interpret: bool = False):
    """One decode step of attention over PAGED KV: ``q`` is
    ``[slots, H, D]`` (one token per slot), ``k_pages``/``v_pages``
    ``[num_pages, H, page_size, D]`` pools, ``page_table`` the int32
    ``[slots, pages_per_slot]`` physical page ids in sequence order,
    ``lengths`` the host int32 ``[slots]`` ragged bound (clamped into
    ``[1, pages_per_slot * page_size]`` — a free slot reads one
    garbage page whose output is never consumed). Returns
    ``[slots, H, D]``. Table entries past a slot's valid pages are
    never fetched beyond block-index resolution — keep them in
    ``[0, num_pages)`` (the identity view does)."""
    from jax.experimental.pallas import tpu as pltpu

    slots, h, d = q.shape
    num_pages, hk, page_size, dk = k_pages.shape
    if (hk, dk) != (h, d) or v_pages.shape != k_pages.shape:
        raise ValueError(f"page pools {k_pages.shape}/{v_pages.shape} "
                         f"do not match q [{slots},{h},{d}]")
    pages_per_slot = int(page_table.shape[1])
    if page_table.shape[0] != slots:
        raise ValueError(f"page_table {page_table.shape} does not "
                         f"match {slots} slots")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    lengths = jnp.clip(lengths.astype(jnp.int32), 1,
                       pages_per_slot * page_size)
    kernel = functools.partial(
        _paged_kernel, page_size=page_size,
        pages_per_slot=pages_per_slot, sm_scale=float(sm_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, lengths
        grid=(slots, h, pages_per_slot),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda s, h_, j, tbl, ln: (s, h_, 0)),
            # the paged read: page j of slot s lives at pool id
            # table[s, j] — the indirection IS the index map
            pl.BlockSpec((1, 1, page_size, d),
                         lambda s, h_, j, tbl, ln: (tbl[s, j], h_, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda s, h_, j, tbl, ln: (tbl[s, j], h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d),
                               lambda s, h_, j, tbl, ln: (s, h_, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),   # running max
            pltpu.VMEM((1, 1), jnp.float32),   # running denominator
            pltpu.VMEM((1, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, h, d), q.dtype),
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bigdl_paged_decode",
    )(page_table.astype(jnp.int32), lengths, q, k_pages, v_pages)
