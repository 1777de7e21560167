"""Attention layers — net-new TPU-first capability (the reference has no
attention/sequence-parallel machinery; SURVEY.md §2.3 "explicit parallelism
checklist": TP/SP/CP absent. Long-context is first-class here, so attention
ships with a ring/context-parallel path from the start).

Layout convention: [batch, seq, model] (B,S,E); heads split E. Matmuls are
einsums that XLA tiles onto the MXU; bf16-friendly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module
from bigdl_tpu.utils.engine import Engine


def _flash_attention_tpu(q, k, v, causal: bool):
    """jax's bundled pallas flash attention — O(S) memory, no
    materialized [S,S] score matrix. Which shapes it takes is
    ``_flash_eligible``'s decision, made before the call; whatever the
    kernel or the TPU compiler then raises reaches the caller.

    The forward's query and key tiles are 512 where the length allows,
    else the kernel's own 128: at 128 x 128 a layer of 48 x 4096 x 4096
    read 11 ms on a v5e, a tenth of its operations, against 1.9. The
    backward keeps the kernel's defaults."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention)

    b, h, s, d = q.shape
    tile = 512 if s % 512 == 0 else 128
    sizes = dataclasses.replace(
        BlockSizes.get_default(b, h, s, k.shape[2], d),
        block_q=tile, block_k_major=tile, block_k=tile)
    return flash_attention(q, k, v, causal=causal,
                           sm_scale=1.0 / math.sqrt(d),
                           block_sizes=sizes)


# What is left for the einsum form after the kernel dispatch declined
# (short sequences, free-form masks, dropout, kernels off) still goes
# to jax's bundled flash kernel when the materialized [S,S] score
# matrix would not comfortably fit HBM: a MEMORY escape hatch, keyed on
# bytes. Speed is the dispatch's business: on a v5e the fused kernel
# reads 0.68 ms a layer of [4,16,1024,64] against the einsum form's
# 2.89, and the einsum form wins under 512 keys (PERF.md section 6,
# PR 35: the measured table).
_FLASH_SCORE_BYTES = 2 << 30


def _flash_eligible(q, mask, dropout_rate, training) -> bool:
    if q.ndim != 4:  # the kernel needs [B,H,S,D]; other ranks use einsum
        return False
    b, h, seq, d = q.shape[-4], q.shape[-3], q.shape[-2], q.shape[-1]
    scores_bytes = b * h * seq * seq * q.dtype.itemsize
    return (mask is None
            and not (training and dropout_rate > 0.0)
            and seq % 128 == 0 and d % 128 == 0
            and scores_bytes > _FLASH_SCORE_BYTES)


def dot_product_attention(q, k, v, *, causal: bool = False, mask=None,
                          dropout_rate: float = 0.0, rng=None,
                          training: bool = False, use_flash: bool = True,
                          segments=None):
    """Scaled dot-product attention. q,k,v: [B, H, S, D].

    The first router is the kernel dispatch layer
    (``bigdl_tpu.kernels``): with the flash kernel enabled (the
    default on a TPU; ``KernelConfig``/``BIGDL_KERNELS``) the shapes
    its measured rule takes run the fused pallas flash attention, in
    a program of one device (one the partitioner splits over a mesh
    keeps the einsum form: docs/kernels.md). Masking is EITHER
    ``mask`` (an
    arbitrary boolean ``[B, 1, S, S]`` — never kernel-eligible, the
    kernel cannot honor a free-form mask) OR ``segments`` (the packed
    datapipe slab's ``[B, S]`` segment-id plane — the same-segment
    mask is derived HERE for the einsum fallback, and the raw plane
    rides into the kernel so packed slabs stay bit-faithful); passing
    both raises, because the kernel would silently drop whatever the
    mask adds beyond segment equality. A declined dispatch falls
    through unchanged, so kernels-off is byte-identical to the
    pre-kernel path.

    On TPU with kernels off, sequences whose score matrix would bust
    HBM still route to jax's bundled flash kernel (O(S) memory);
    everything else uses the einsum form, which XLA fuses onto the MXU
    (see _FLASH_SCORE_BYTES).
    """
    d = q.shape[-1]
    if mask is not None and segments is not None:
        raise ValueError(
            "pass mask= OR segments=, not both: the kernel path can "
            "only honor segment equality, so a mask carrying anything "
            "more would be silently dropped — derive from segments "
            "alone (the same-segment mask is built here) or keep a "
            "custom mask on the einsum path")
    if (use_flash and mask is None
            and not (training and dropout_rate > 0.0)):
        from bigdl_tpu import kernels as _kernels
        out = _kernels.attention(q, k, v, causal=causal,
                                 segment_ids=segments,
                                 sm_scale=1.0 / math.sqrt(d))
        if out is not None:
            return out
    if segments is not None:
        # the einsum fallback's same-segment mask — one derivation
        # site, bitwise the mask the packed model used to build itself
        seg = segments.astype(jnp.int32)
        mask = seg[:, None, :, None] == seg[:, None, None, :]
    if (use_flash and jax.default_backend() == "tpu"
            and _flash_eligible(q, mask, dropout_rate, training)):
        return _flash_attention_tpu(q, k, v, causal)
    # softmax is a sanctioned f32 island under every precision policy:
    # the QK contraction accumulates f32 on the MXU
    # (preferred_element_type costs nothing) and the exp/normalize run
    # in f32 — bf16 softmax saturates long-context score rows; the
    # weights return to v.dtype so the PV matmul stays in compute dtype
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(cmask, scores, jnp.finfo(scores.dtype).min)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    if training and dropout_rate > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, weights.shape)
        weights = weights * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


class MultiHeadAttention(Module):
    """Multi-head attention over [B, S, E] input.

    ``ring_axis`` names a mesh axis carrying the sequence dimension.
    When the module runs inside ``shard_map`` with that axis bound,
    attention runs directly as the chosen sequence-parallel kernel;
    when it runs under plain ``jit`` on a mesh that HAS the axis (the
    Optimizer product path), the kernel is auto-wrapped in
    ``jax.shard_map(axis_names={ring_axis})`` — the sequence dim goes
    manual over that axis while batch/model dims stay GSPMD-auto, so
    SP composes with DP/TP with no caller-side plumbing.

    ``sp_impl`` picks the kernel: "ring" (K/V blocks rotate via
    ppermute, parallel/ring_attention.py) or "ulysses" (all-to-all
    head re-sharding, parallel/ulysses.py).

    Modules built WITHOUT ``ring_axis`` adopt the train-step policy
    (``SeqParallelConfig``, installed for the duration of the trace by
    ``build_train_step(seq_parallel=...)``) — same kernels, chosen by
    the Optimizer instead of the model author; a custom mask or
    attention dropout keeps the dense path.
    """

    def __init__(self, hidden_size: int, num_heads: int,
                 dropout: float = 0.0, causal: bool = False,
                 with_bias: bool = True,
                 ring_axis: Optional[str] = None,
                 sp_impl: str = "ring", mesh=None):
        super().__init__()
        assert hidden_size % num_heads == 0
        if ring_axis is not None and dropout > 0.0:
            raise ValueError(
                "attention dropout is not supported on the ring-attention "
                "path (it would change the objective vs the unsharded "
                "model); use dropout=0.0 with ring_axis")
        if sp_impl not in ("ring", "ulysses"):
            raise ValueError(f"sp_impl must be ring|ulysses, got {sp_impl}")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.dropout = dropout
        self.causal = causal
        self.with_bias = with_bias
        self.ring_axis = ring_axis
        self.sp_impl = sp_impl
        self.mesh = mesh

    def init(self, rng):
        dtype = Engine.default_dtype()
        keys = jax.random.split(rng, 4)
        s = 1.0 / math.sqrt(self.hidden_size)
        p = {}
        for name, kk in zip(("q", "k", "v", "o"), keys):
            p[f"w{name}"] = jax.random.uniform(
                kk, (self.hidden_size, self.hidden_size), dtype, -s, s)
            if self.with_bias:
                p[f"b{name}"] = jnp.zeros((self.hidden_size,), dtype)
        return p

    def _proj(self, params, x, name):
        y = x @ params[f"w{name}"]
        if self.with_bias:
            y = y + params[f"b{name}"]
        return y

    def forward_fn(self, params, input, *, training=False, rng=None,
                   cache=None, positions=None, attend_len=None,
                   mask=None, segments=None, fresh=False):
        """Full-sequence attention, or — with ``cache=`` — one
        incremental (KV-cached) step.

        ``mask`` is an optional boolean ``[B, 1, S, S]`` (broadcastable)
        attention mask ANDed with the causal structure. ``segments``
        is the packed-sequence data path's ``[B, S]`` segment-id plane
        (``bigdl_tpu.datapipe.packing``): the same-segment mask is
        derived downstream for the einsum path, and the raw plane
        feeds the pallas flash kernel (``bigdl_tpu.kernels``) when
        enabled, so rows holding several documents never attend across
        document boundaries. Pass one or the other, never both (a
        custom mask cannot ride the kernel). ``segments`` also rides
        the sequence-parallel path (ring rotates the key-side ids with
        their K/V block; Ulysses all-gathers the full id row); a custom
        ``mask`` does not, and neither works on the cached path.

        ``cache`` is ``{"k": [B,H,D,T], "v": [B,H,D,T]}`` (T the
        cache's bucketed max length, on the lanes: the form the decode
        kernel reads, see ``generation/kv_cache.py``), ``positions`` an
        int32 ``[B]`` of per-row write offsets, ``attend_len`` and
        ``fresh`` static: the step itself is :func:`cached_attention`,
        shared with :class:`GroupedQueryAttention`, here with as many
        K/V heads as query heads and no window. Returns ``(out,
        new_cache)``.

        Without ``cache`` the weights are the same (generation adds no
        parameters)."""
        if cache is not None:
            if mask is not None or segments is not None:
                raise ValueError(
                    "segment masks are not supported on the KV-cached "
                    "decode path (pack training slabs, not decode steps)")
            if positions is None:
                raise ValueError("cache= needs positions= (per-row int32 "
                                 "write offsets into the KV cache)")
        if mask is not None and self.ring_axis is not None:
            raise ValueError(
                "custom masks are not supported on the sequence-parallel "
                "path (ring/ulysses kernels shard the key axis the mask "
                "indexes); packed segments= ride the SP path, or use "
                "ring_axis=None for arbitrary masks")
        x = input
        b, s, e = x.shape
        h, d = self.num_heads, self.head_dim

        def split(t):  # [B,S,E] -> [B,H,S,D]
            return t.reshape(b, s, h, d).transpose(0, 2, 1, 3)

        with jax.named_scope("attn/qkv"):
            q = split(self._proj(params, x, "q"))
            k = split(self._proj(params, x, "k"))
            v = split(self._proj(params, x, "v"))
        if cache is None:
            with jax.named_scope("attn/core"):
                out = self._core(q, k, v, mask, segments, training, rng)
        else:
            # the S new rows go to the cache's [H,D,T] form, never the
            # cache
            out, cache = cached_attention(
                q, jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3), cache,
                positions.astype(jnp.int32), attend_len=attend_len,
                fresh=fresh)
        with jax.named_scope("attn/out"):
            out = out.transpose(0, 2, 1, 3).reshape(b, s, e)
            out = self._proj(params, out, "o")
        return out if cache is None else (out, cache)

    def _core(self, q, k, v, mask, segments, training, rng):
        """``[B,H,S,D]`` attention by whichever path applies: a
        sequence-parallel kernel, the pallas flash kernel, or the
        einsum form."""
        # the module-level knob wins; without one, adopt the train-step
        # policy (build_train_step(seq_parallel=...) installs it for the
        # duration of the trace) — mask/dropout keep the dense path,
        # since neither survives a sharded key axis
        sp_axis, sp_impl, sp_mesh = self.ring_axis, self.sp_impl, self.mesh
        if sp_axis is None and mask is None and self.dropout == 0.0:
            from bigdl_tpu.parallel.sequence import active_sequence_parallel
            sp = active_sequence_parallel()
            if sp is not None:
                sp_axis, sp_impl, sp_mesh = sp.axis, sp.impl, sp.mesh

        out = None
        if sp_axis is not None:
            kern = self._sp_kernel(sp_impl)
            if _inside_axis(sp_axis):
                out = kern(q, k, v, axis_name=sp_axis,
                           causal=self.causal, segments=segments)
            else:
                from bigdl_tpu.parallel.mesh import (resolve_axis_mesh,
                                                     seq_sharded_attention)
                mesh = resolve_axis_mesh(sp_mesh, sp_axis)
                if mesh is not None:
                    wrapped = seq_sharded_attention(
                        kern, mesh, sp_axis, self.causal,
                        segments is not None)
                    out = (wrapped(q, k, v) if segments is None
                           else wrapped(q, k, v, segments))
        if out is None:
            out = dot_product_attention(
                q, k, v, causal=self.causal, mask=mask,
                dropout_rate=self.dropout, rng=rng, training=training,
                segments=segments)
        return out

    def _sp_kernel(self, impl: Optional[str] = None):
        if (impl or self.sp_impl) == "ulysses":
            from bigdl_tpu.parallel.ulysses import ulysses_attention
            return ulysses_attention
        from bigdl_tpu.parallel.ring_attention import ring_attention
        return ring_attention


def _inside_axis(axis_name: str) -> bool:
    """True when tracing under shard_map/pmap with this named axis bound.

    Only an unbound axis (NameError) falls back to local full attention —
    which also means a TYPO in ring_axis silently degrades to shard-local
    attention; use the same string for the mesh axis and ring_axis. Any
    other tracing failure propagates."""
    try:
        jax.lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def rotary(x, positions, theta: float):
    """Rotary position embedding, half-split form (``x cos +
    rotate_half(x) sin``): ``x [B, heads, S, D]``, ``positions [B, S]``
    absolute. Angles in float32, the result in ``x``'s dtype."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None, :, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos + rot * sin).astype(x.dtype)


def _head_norm(x, weight, eps):
    """RMSNorm over the head dim, mean square in float32."""
    x32 = x.astype(jnp.float32)  # bigdl: disable=implicit-upcast-in-trace
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * weight


def _grouped_scores(q, k_t):
    """``q [B, H, Sq, D]`` against keys ``k_t [B, Hkv, D, Sk]`` (the
    cache's form: time last): float32 scores ``[B, Hkv, G, Sq, Sk]``,
    query heads ``j G .. (j + 1) G - 1`` on K/V head ``j``."""
    b, h, sq, d = q.shape
    hkv = k_t.shape[1]
    qg = q.reshape(b, hkv, h // hkv, sq, d)
    return jnp.einsum("bngqd,bndk->bngqk", qg, k_t,
                      preferred_element_type=jnp.float32) / math.sqrt(d)


def _grouped_values(weights, v_t):
    """``weights [B, Hkv, G, Sq, Sk]`` over values ``v_t [B, Hkv, D,
    Sk]`` -> ``[B, H, Sq, D]``."""
    out = jnp.einsum("bngqk,bndk->bngqd", weights.astype(v_t.dtype), v_t)
    b, n, g, sq, d = out.shape
    return out.reshape(b, n * g, sq, d)


def _own_mask(s: int, window: Optional[int]):
    """``[1, 1, 1, S, S]``: new token i sees new token j."""
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    ok = j <= i
    if window is not None:
        ok = ok & (i - j < window)
    return ok[None, None, None]


def _attend(q, parts):
    """Soft-max attention of ``q`` over the concatenation of ``parts``
    = ``[(k_t, v_t, mask), ...]``, each ``[B, Hkv, D, Sk]`` with a mask
    broadcastable to ``[B, Hkv, G, Sq, Sk]``: the one length-masked
    form every cached step falls back to (operands, float32 scores and
    soft-max as ``dot_product_attention``'s, in the grouped spelling)."""
    scores = [jnp.where(m, _grouped_scores(q, kt),
                        jnp.finfo(jnp.float32).min)
              for kt, _, m in parts]
    w = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
    out, at = None, 0
    for kt, vt, _ in parts:
        n = kt.shape[-1]
        o = _grouped_values(w[..., at:at + n], vt)
        out, at = (o if out is None else out + o), at + n
    return out


def scoreless(length: int, head_dim: int,
              window: Optional[int] = None) -> bool:
    """Whether ``length`` fresh tokens can attend each other without
    materialised scores: the bundled flash kernel takes them (it knows
    the causal mask, not a window) and nothing interprets."""
    from bigdl_tpu import kernels as _kernels

    return (not _kernels.interpret_mode()
            and length % 128 == 0 and head_dim % 128 == 0
            and (window is None or length <= window))


def _write_columns(cache, k_t, v_t, offsets, qpos, valid, window):
    """The new columns ``k_t`` / ``v_t`` ``[B, Hkv, D, S]`` into the
    layer's entry: at each row's offset in a global layer; in a ring at
    ``p mod window``, one column in place for a decode step, else only
    the real tokens (``valid``) that stay inside the window once the
    call is over. Prefill chunks and ``verify`` steps (``S > 1``) write
    here; a decode step's one column is the ragged decode kernel's own
    (:func:`cached_attention`), so the ``S == 1`` arm is the fallback's
    alone: XLA makes a ``while`` over the rows of it, each turn a
    read-modify-write across ``Hkv x D / 8`` vector tiles.
    (XLA clamps an out-of-range start into the buffer;
    the engine passes in-range offsets for live rows, and a clamped
    write into a FREE slot is re-written by that slot's next prefill
    before any mask exposes it.)"""
    s = k_t.shape[3]
    if window is None or s == 1:
        at = offsets if window is None else offsets % window

        def put(cc, u, p):   # [Hkv,D,T], [Hkv,D,S], scalar offset
            return jax.lax.dynamic_update_slice(cc, u, (0, 0, p))
    else:
        cols = cache["k"].shape[3]
        n = (jnp.full_like(offsets, s) if valid is None
             else valid.astype(jnp.int32))
        i = jnp.arange(s, dtype=jnp.int32)[None, :]
        keep = (i < n[:, None]) & (i >= n[:, None] - window)
        at = jnp.where(keep, qpos % window, cols)        # cols: dropped

        def put(cc, u, a):   # [Hkv,D,cols], [Hkv,D,S], [S]
            return cc.at[:, :, a].set(u, mode="drop")

    return {"k": jax.vmap(put)(cache["k"], k_t, at),
            "v": jax.vmap(put)(cache["v"], v_t, at)}


def cached_attention(q, k_t, v_t, cache, offsets, *,
                     window: Optional[int] = None, attend_len=None,
                     valid=None, fresh: bool = False):
    """One KV-cached attention step, the only one: ``q [B, H, S, D]``
    and the S new keys and values ``k_t`` / ``v_t`` ``[B, Hkv, D, S]``
    (projected, normed and rotated by the caller) against the layer's
    entry ``cache = {"k", "v"}`` ``[B, Hkv, D, C]``, as
    :class:`~bigdl_tpu.generation.kv_cache.KVCache` keeps it. Returns
    ``(out [B, H, S, D], new_cache)``; pure.

    ``offsets`` (int32 ``[B]``): row ``b``'s new tokens sit at
    positions ``offsets[b] .. offsets[b] + S - 1``. Without a
    ``window`` position ``p`` lives at column ``p`` and a query attends
    the columns ``j <= p`` of the first ``attend_len`` (static: the
    engine's rung, so a short sequence never scans the whole entry).
    With one the entry is a RING of ``window`` columns, position ``p``
    at column ``p mod window``; a column needs no position of its own
    (keys are rotated before they are stored), so a decode step attends
    ``min(p + 1, window)`` columns in whatever order they lie.
    ``valid`` (int ``[B]``, prefill only) is how many of the S new
    tokens of each row are real: padding past it is never written into
    a ring, where it would overwrite positions still in the window.
    ``fresh`` (static): the rows' entries hold nothing yet and every
    offset is 0 - a prompt prefilled in one shot. The new tokens then
    attend only each other, through the bundled flash kernel where
    :func:`scoreless` says it fits.

    One new token a row (not ``fresh``) is offered to the ragged
    decode kernel FIRST (``kernels.decode_attention``), with the entry
    as it stands, the new column and where it goes: where the dispatch
    takes it, the kernel attends the new token with the ``lengths - 1``
    other columns and writes the column in place, and nothing else
    touches the entry. Everything else, and a declined dispatch (config
    off, a non-floating cache, a shape), writes through
    :func:`_write_columns` and attends through the length-masked
    :func:`_attend`. A ring's prefill chunk or verify step attends what
    the ring held BEFORE the call, told apart by the position each
    column must hold, together with the new tokens themselves."""
    s, d = q.shape[2], q.shape[3]
    qpos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    cols = cache["k"].shape[3]
    c = cols if attend_len is None else min(int(attend_len), cols)
    if s == 1 and not fresh:
        from bigdl_tpu import kernels as _kernels
        with jax.named_scope("attn/core"):
            taken = _kernels.decode_attention(
                q[:, :, 0, :], cache["k"], cache["v"],
                offsets + 1 if window is None
                else jnp.minimum(offsets + 1, window),
                new_k=k_t[..., 0], new_v=v_t[..., 0],
                write_at=offsets if window is None else offsets % window,
                attend_len=c)
        if taken is not None:
            out, k, v = taken
            return out[:, :, None, :], {"k": k, "v": v}
    with jax.named_scope("attn/kv_write"):
        new = _write_columns(cache, k_t, v_t, offsets, qpos, valid,
                             window)
    with jax.named_scope("attn/core"):
        if fresh and scoreless(s, d, window):
            g = q.shape[1] // k_t.shape[1]
            out = _flash_attention_tpu(
                q, jnp.repeat(jnp.swapaxes(k_t, 2, 3), g, axis=1),
                jnp.repeat(jnp.swapaxes(v_t, 2, 3), g, axis=1), True)
        elif fresh:
            out = _attend(q, [(k_t, v_t, _own_mask(s, window))])
        elif window is None:
            mask = (jnp.arange(c)[None, None, :]
                    <= qpos[:, :, None])[:, None, None]
            out = _attend(q, [(new["k"][..., :c], new["v"][..., :c],
                               mask)])
        else:
            # the position column j held before this call: the largest
            # p <= offset - 1 with p mod window == j (negative: none)
            j = jnp.arange(c, dtype=jnp.int32)[None, :]
            last = offsets[:, None] - 1
            held = last - (last - j) % window                 # [B, C]
            old = ((held[:, None, :] >= 0)
                   & (held[:, None, :] > qpos[:, :, None] - window))
            out = _attend(
                q, [(cache["k"][..., :c], cache["v"][..., :c],
                     old[:, None, None]),
                    (k_t, v_t, _own_mask(s, window))])
    return out, new


class GroupedQueryAttention(Module):
    """Causal attention whose K/V heads are fewer than its query heads,
    over [B, S, E] input, with the pieces today's decoders put around
    it — each one an argument, so a layer pattern can mix kinds:

    - ``num_kv_heads`` K/V heads of ``head_dim``, each shared by
      ``num_heads / num_kv_heads`` query heads;
    - ``qk_norm``: q and k through an RMSNorm over the head;
    - ``rope_theta``: rotary positions on q and k (``None``: the layer
      carries no positions);
    - ``window``: a query sees the last ``window`` positions, its own
      included (``None``: the whole prefix);
    - ``gate``: the output is multiplied by ``sigmoid(x Wg)`` before
      ``Wo``. No biases.

    **Cached.** ``cache`` is ``{"k", "v"}`` ``[B, Hkv, D, C]``, a ring
    of ``window`` columns for a window layer; the step, with
    ``positions``, ``attend_len``, ``valid`` and ``fresh``, is
    :func:`cached_attention`'s, shared with ``MultiHeadAttention``.
    """

    def __init__(self, hidden_size: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, *,
                 window: Optional[int] = None,
                 rope_theta: Optional[float] = None, qk_norm: bool = True,
                 gate: bool = True, norm_eps: float = 1e-5):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over "
                             f"{num_kv_heads} K/V heads")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.window = window
        self.rope_theta = rope_theta
        self.qk_norm = qk_norm
        self.gate = gate
        self.norm_eps = norm_eps

    def init(self, rng):
        dtype = Engine.default_dtype()
        h, d = self.hidden_size, self.head_dim
        nq, nkv = self.num_heads * d, self.num_kv_heads * d
        ks = jax.random.split(rng, 5)
        s_in, s_out = 1.0 / math.sqrt(h), 1.0 / math.sqrt(nq)
        p = {"wq": jax.random.uniform(ks[0], (h, nq), dtype, -s_in, s_in),
             "wk": jax.random.uniform(ks[1], (h, nkv), dtype, -s_in, s_in),
             "wv": jax.random.uniform(ks[2], (h, nkv), dtype, -s_in, s_in),
             "wo": jax.random.uniform(ks[3], (nq, h), dtype,
                                      -s_out, s_out)}
        if self.gate:
            p["wg"] = jax.random.uniform(ks[4], (h, nq), dtype,
                                         -s_in, s_in)
        if self.qk_norm:
            p["q_norm"] = jnp.ones((d,), dtype)
            p["k_norm"] = jnp.ones((d,), dtype)
        return p

    def cache_columns(self, max_len: int) -> int:
        """Columns this layer's cache entry keeps for ``max_len``
        positions: the ring, or every position."""
        return max_len if self.window is None else min(self.window,
                                                       max_len)

    def scoreless(self, length: int) -> bool:
        """:func:`scoreless` for this layer's head and window."""
        return scoreless(length, self.head_dim, self.window)

    def forward_fn(self, params, input, *, training=False, rng=None,
                   cache=None, positions=None, attend_len=None,
                   valid=None, fresh=False):
        x = input
        b, s, _ = x.shape
        d = self.head_dim

        def heads(t, n):  # [B,S,n*D] -> [B,n,S,D]
            return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)

        with jax.named_scope("attn/qkv"):
            q = heads(x @ params["wq"], self.num_heads)
            k = heads(x @ params["wk"], self.num_kv_heads)
            v = heads(x @ params["wv"], self.num_kv_heads)
            if self.qk_norm:
                q = _head_norm(q, params["q_norm"], self.norm_eps)
                k = _head_norm(k, params["k_norm"], self.norm_eps)
        if cache is None:
            offsets = jnp.zeros((b,), jnp.int32)
        elif positions is None:
            raise ValueError("cache= needs positions= (per-row int32 "
                             "write offsets into the KV cache)")
        else:
            offsets = positions.astype(jnp.int32)
        qpos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        if self.rope_theta is not None:
            with jax.named_scope("attn/rope"):
                q = rotary(q, qpos, self.rope_theta)
                k = rotary(k, qpos, self.rope_theta)
        k_t, v_t = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
        if cache is None:
            with jax.named_scope("attn/core"):
                out = _attend(q, [(k_t, v_t, _own_mask(s, self.window))])
        else:
            out, cache = cached_attention(
                q, k_t, v_t, cache, offsets, window=self.window,
                attend_len=attend_len, valid=valid, fresh=fresh)
        out = out.transpose(0, 2, 1, 3).reshape(b, s,
                                                self.num_heads * d)
        if self.gate:
            with jax.named_scope("attn/gate"):
                out = out * jax.nn.sigmoid(x @ params["wg"])
        with jax.named_scope("attn/out"):
            out = out @ params["wo"]
        return out if cache is None else (out, cache)
