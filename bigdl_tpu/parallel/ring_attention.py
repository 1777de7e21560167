"""Ring attention — exact sequence/context-parallel attention over an ICI
ring (net-new vs the reference, which has no sequence parallelism:
SURVEY.md §2.3/§5. Design follows the blockwise/ring-attention pattern:
K/V blocks rotate around the mesh axis via ``ppermute`` while each shard
keeps a running online-softmax accumulator, so memory is linear in the
LOCAL sequence length and comms overlap compute around the ring).

Use inside ``shard_map`` with the sequence dim sharded over ``axis_name``
(per-shard shapes [B, H, S_local, D]), or call :func:`ring_attention_sharded`
on full arrays and let it wrap the shard_map.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_accum(q, k, v, m, l, o, qpos, kpos, *, causal, scale,
                 seg_q=None, seg_k=None):
    """One K/V block of online-softmax attention.

    q [B,H,Sq,D]; k,v [B,H,Sk,D]; m,l [B,H,Sq]; o [B,H,Sq,D];
    qpos [Sq], kpos [Sk] global positions for causal masking;
    seg_q [B,Sq] / seg_k [B,Sk] packed segment ids (None = no packing)
    — cross-segment scores mask out exactly like the dense path's
    same-segment mask, so packed slabs ride the ring bit-faithfully.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    neg = jnp.asarray(jnp.finfo(scores.dtype).min, scores.dtype)
    if causal:
        cmask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(cmask[None, None], scores, neg)
    if seg_q is not None:
        smask = seg_q[:, None, :, None] == seg_k[:, None, None, :]
        scores = jnp.where(smask, scores, neg)
    smax = jnp.max(scores, axis=-1)                      # [B,H,Sq]
    m_new = jnp.maximum(m, smax)
    # rows with everything masked keep m_new == neg; exp underflows to 0
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False,
                   segments=None):
    """Exact attention with sequence sharded over ``axis_name``.

    Per-shard q,k,v: [B, H, S_local, D]. Returns [B, H, S_local, D].
    ``segments`` [B, S_local] are per-shard packed segment ids — the
    key-side ids rotate around the ring WITH their K/V block, so every
    shard masks cross-segment scores against the block it currently
    holds (bit-faithful to the dense same-segment mask).
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    scale = 1.0 / math.sqrt(d)
    dtype = jnp.promote_types(q.dtype, jnp.float32)
    q32, k0, v0 = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    has_seg = segments is not None
    seg0 = (segments.astype(jnp.int32) if has_seg
            else jnp.zeros((b, s_loc), jnp.int32))

    qpos = my * s_loc + jnp.arange(s_loc)
    neg = jnp.asarray(jnp.finfo(dtype).min, dtype)
    m0 = jnp.full((b, h, s_loc), neg, dtype)
    l0 = jnp.zeros((b, h, s_loc), dtype)
    o0 = jnp.zeros((b, h, s_loc, d), dtype)
    # the accumulators (and the dummy all-zero segment carry when
    # packing is off) become shard-varying inside the scan; mark the
    # (constant) initial values as such for the vma type check
    varying = (m0, l0, o0) if has_seg else (m0, l0, o0, seg0)
    varying = jax.lax.pcast(varying, (axis_name,), to="varying")
    if has_seg:
        m0, l0, o0 = varying
    else:
        m0, l0, o0, seg0 = varying
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        k_blk, v_blk, seg_blk, m, l, o = carry
        src = (my - t) % n  # which shard's block we currently hold
        kpos = src * s_loc + jnp.arange(s_loc)
        m, l, o = _block_accum(
            q32, k_blk, v_blk, m, l, o, qpos, kpos,
            causal=causal, scale=scale,
            seg_q=segments if has_seg else None,
            seg_k=seg_blk if has_seg else None)
        # rotate AFTER consuming; skip the final (wasted) hop
        k_nxt, v_nxt, seg_nxt = jax.lax.cond(
            t < n - 1,
            lambda kv: jax.lax.ppermute(kv, axis_name, perm),
            lambda kv: kv,
            (k_blk, v_blk, seg_blk))
        return (k_nxt, v_nxt, seg_nxt, m, l, o), None

    (k_f, v_f, seg_f, m, l, o), _ = jax.lax.scan(
        step, (k0, v0, seg0, m0, l0, o0), jnp.arange(n))
    # fully-masked rows (l == 0) -> zeros, not NaN
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = o / safe_l[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                           *, causal: bool = False, segments=None):
    """Full-array convenience wrapper: shards S over ``seq_axis`` and runs
    ring attention under shard_map. q,k,v: [B, H, S, D] (global);
    ``segments`` [B, S] global packed ids, sharded alongside. Mesh
    axes other than ``seq_axis`` stay GSPMD-auto (composes with DP/TP);
    the wrapper is cached, so call it every forward."""
    from bigdl_tpu.parallel.mesh import seq_sharded_attention
    fn = seq_sharded_attention(ring_attention, mesh, seq_axis, causal,
                               segments is not None)
    return fn(q, k, v) if segments is None else fn(q, k, v, segments)
