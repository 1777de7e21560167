"""The kernel dispatch layer — every pallas kernel enters here.

Call sites (``nn.attention``, the generation decode path,
``nn.quantized``) never invoke ``pl.pallas_call`` directly — the
``raw-pallas-call`` lint rule enforces it — they ask this layer, which
checks the active :class:`~bigdl_tpu.kernels.config.KernelConfig` and
shape eligibility and returns either the kernel result or **None**,
meaning "run your existing pure-jnp path". Returning None (rather than
owning a second copy of the reference math) keeps exactly ONE
reference implementation per op — the einsum/`ops.quant` code the
equivalence tests compare against — and guarantees the kernels-off
configuration is byte-identical to the pre-kernel tree.

Dispatch decisions happen at TRACE time (config and shapes are
static), so the per-trace counters below count compiled-program
routing, not per-step calls: ``kernels/dispatch/pallas`` (label
``op=flash|decode|decode_write|int8|gmm``; ``decode_write`` is a second
label of the decode kernel, for the cache column it writes) vs
``kernels/dispatch/reference`` (labels ``op=...`` plus
``reason=config|shape|vmem`` so a `diagnose` dump attributes every
decline).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax.numpy as jnp

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.kernels import config as _config
from bigdl_tpu.kernels.common import fit_block, sublanes

__all__ = ["attention", "decode_attention", "int8_matmul",
           "grouped_matmul", "taken_in_thread"]

# module-level registration so `tools.check --telemetry-audit` sees the
# REAL instruments on import, not a hand-maintained name list
_C_PALLAS = telemetry.counter(
    "kernels/dispatch/pallas",
    "traces routed to a pallas kernel (label op=flash|decode|"
    "decode_write|int8|gmm)")
_C_REFERENCE = telemetry.counter(
    "kernels/dispatch/reference",
    "traces declined by the dispatch layer to the pure-jnp reference "
    "(labels op=flash|decode|int8, reason=config|shape|vmem)")


# trace-scoped routing evidence: tracing happens on the caller's
# thread, so a thread-local tick lets a compile site ask "did THIS
# trace route through a pallas kernel" — which is how program profiles
# earn their kernel=pallas label (telemetry.programs), instead of
# guessing from the global config
_TRACE = threading.local()


def taken_in_thread(op: Optional[str] = None) -> int:
    """Monotonic count of pallas dispatches taken on this thread —
    snapshot before and after a ``lower()``/trace to learn whether the
    traced program actually contains a kernel. With ``op``, the count
    under that one label (``decode_write``: the decode kernels that
    also wrote their step's cache column)."""
    if op is None:
        return getattr(_TRACE, "taken", 0)
    return getattr(_TRACE, "by_op", {}).get(op, 0)


def _declined(op: str, reason: str) -> None:
    # reason= makes declines attributable in `diagnose`: "config" (the
    # active KernelConfig disabled the op), "shape" (ineligible dtype/
    # rank/alignment), "vmem" (over the flash working-set budget with
    # the blockwise long-context path switched off)
    _C_REFERENCE.inc(op=op, reason=reason)


def _taken(*ops: str) -> None:
    """One kernel taken, counted under each of its labels."""
    _TRACE.taken = getattr(_TRACE, "taken", 0) + 1
    by_op = _TRACE.__dict__.setdefault("by_op", {})
    for op in ops:
        _C_PALLAS.inc(op=op)
        by_op[op] = by_op.get(op, 0) + 1


def _floating(*arrays) -> bool:
    return all(jnp.issubdtype(a.dtype, jnp.floating) for a in arrays)


def _flash_vmem_bytes(q, block_q: int) -> int:
    """Upper-bound VMEM working set of ONE flash grid program — the
    BACKWARD kernel's, which dominates: f32 casts of the full K and V
    blocks, the two [S, D] f32 dK/dV scratch accumulators, and four
    f32 [block_q, S] strips (scores, p, dp, ds). The forward (K+V at
    input dtype + three strips) is strictly smaller, so budgeting on
    the backward keeps jax.grad from OOMing at shapes the forward
    alone would have accepted."""
    s, d = q.shape[-2], q.shape[-1]
    bq = fit_block(s, block_q, align=sublanes(q.dtype))
    kv_inputs = 2 * s * d * q.dtype.itemsize
    kv_f32 = 2 * s * d * 4        # in-kernel f32 casts of K and V
    scratch = 2 * s * d * 4       # dK/dV accumulators
    strips = 4 * bq * s * 4       # scores / p / dp / ds
    tiles = 4 * bq * d * 4        # q, o, do, dq tiles
    return kv_inputs + kv_f32 + scratch + strips + tiles


def attention(q, k, v, *, causal: bool = False, segment_ids=None,
              sm_scale: Optional[float] = None):
    """Flash-attention dispatch for ``[B, H, S, D]`` q/k/v: the tiled
    pallas kernel (:mod:`bigdl_tpu.kernels.flash_attention`, segment-
    mask aware, differentiable) when the active config enables
    ``flash`` and the shapes qualify — else **None**, telling the
    caller to run its jnp path (``nn.attention.dot_product_attention``
    falls through to the einsum form, which itself still routes
    HBM-busting lengths to jax's bundled flash kernel)."""
    if not _config.enabled("flash"):
        _declined("flash", "config")
        return None
    if (q.ndim != 4 or k.shape != q.shape or v.shape != q.shape
            or not _floating(q, k, v)):
        _declined("flash", "shape")
        return None
    cfg = _config.get_config()
    interpret = cfg.resolve_interpret()
    if _flash_vmem_bytes(q, cfg.block_q) > cfg.resolve_vmem_budget():
        # past the working-set budget the full-K-row kernel would OOM
        # Mosaic (an error, not a fallback): route to the blockwise
        # long-context kernel — key axis tiled through VMEM with
        # online-softmax rescaling — unless it is switched off, in
        # which case decline so nn.attention's einsum/bundled-flash
        # routes keep the escape hatch. The budget gate applies in
        # interpret mode too, so CPU tier-1 exercises the same routing
        # a TPU would take (shrink vmem_budget_mb to steer small test
        # shapes down the blockwise path).
        if not cfg.long_context:
            _declined("flash", "vmem")
            return None
        from bigdl_tpu.kernels.flash_attention import (
            blockwise_flash_attention)

        _taken("flash")
        return blockwise_flash_attention(
            q, k, v, segment_ids, causal=causal, sm_scale=sm_scale,
            block_q=cfg.block_q, block_k=cfg.block_k,
            interpret=interpret)
    from bigdl_tpu.kernels.flash_attention import flash_attention

    _taken("flash")
    return flash_attention(q, k, v, segment_ids, causal=causal,
                           sm_scale=sm_scale, block_q=cfg.block_q,
                           interpret=interpret)


def decode_attention(q, k, v, lengths, *, new_k, new_v, write_at,
                     attend_len: int = None,
                     sm_scale: Optional[float] = None):
    """Ragged-decode dispatch, the step's cache write included: ``q
    [slots, H, D]`` (one token per slot), ``k``/``v`` one layer's whole
    ``[slots, Hkv, D, T]`` cache as it stands BEFORE the step (``H`` a
    multiple of ``Hkv``: grouped-query attention), ``new_k``/``new_v``
    ``[slots, Hkv, D]`` the new token's key and value in the cache's
    dtype, ``write_at`` (int32 ``[slots]``) the column they go to,
    ``lengths`` the host per-slot valid-KV vector with the new column
    counted, ``attend_len`` the (static) ladder rung. Returns ``(out,
    k, v)`` from the kernel (:mod:`bigdl_tpu.kernels.ragged_decode` —
    reads only ``lengths[i]`` columns per slot and writes the new one
    in place, the cache aliased through) when ``decode`` is enabled and
    the shapes qualify, else **None**: nothing is written, and the
    caller writes the column itself and runs its length-masked einsum
    path. A taken dispatch counts under ``op=decode`` and, for the
    column it wrote, ``op=decode_write``."""
    if not _config.enabled("decode"):
        _declined("decode", "config")
        return None
    if (k.ndim != 4 or v.shape != k.shape or q.ndim != 3
            or q.shape[0] != k.shape[0] or q.shape[2] != k.shape[2]
            or q.shape[1] % k.shape[1] or not _floating(q, k, v)
            or new_k.shape != k.shape[:3] or new_v.shape != k.shape[:3]
            or not new_k.dtype == new_v.dtype == k.dtype == v.dtype):
        _declined("decode", "shape")
        return None
    from bigdl_tpu.kernels.ragged_decode import ragged_decode_attention

    _taken("decode", "decode_write")
    return ragged_decode_attention(
        q, k, v, lengths, write_at, new_k, new_v, attend_len=attend_len,
        sm_scale=sm_scale,
        interpret=_config.get_config().resolve_interpret())


#: compiled (non-interpret) int8 tiles must fill the MXU: the same
#: alignment gate nn.quantized always applied before taking the kernel
_INT8_ALIGN = (256, 256, 512)


def int8_matmul(x_q, w_q, x_scale, w_scale, bias=None):
    """Fused dequant-int8-GEMM dispatch: ``x_q [M, K] i8 @ w_q [N, K]
    i8^T`` rescaled by ``x_scale`` (per row or scalar — the calibrated
    serving path) and per-channel ``w_scale``. Returns the pallas
    kernel result (bias added OUTSIDE the kernel so the path stays
    bit-identical to dequantize-then-matmul — see
    :mod:`bigdl_tpu.kernels.int8_gemm`) when ``int8`` is enabled and
    the shapes qualify, else **None** (the caller runs
    ``ops.quant.quantized_linear``)."""
    if not _config.enabled("int8"):
        _declined("int8", "config")
        return None
    m, k = x_q.shape
    n = w_q.shape[0]
    cfg = _config.get_config()
    interpret = cfg.resolve_interpret()
    if not interpret and not (m % _INT8_ALIGN[0] == 0
                              and n % _INT8_ALIGN[1] == 0
                              and k % _INT8_ALIGN[2] == 0):
        _declined("int8", "shape")
        return None
    from bigdl_tpu.kernels.int8_gemm import pallas_quantized_matmul

    _taken("int8")
    xs = jnp.broadcast_to(
        jnp.asarray(x_scale, jnp.float32).reshape(-1, 1), (m, 1))
    out = pallas_quantized_matmul(x_q, w_q, xs, w_scale,
                                  interpret=interpret)
    if bias is not None:
        # the ONE bias add both paths share (fusing it into the kernel
        # costs a one-ulp FMA drift vs the reference; int8_gemm.py)
        out = out + bias.reshape(1, -1).astype(jnp.float32)
    return out


def _grouped_reference(x, w, tile_expert, tile_m: int):
    """The grouped product in plain jnp: every row tile times its
    expert's matrix (gathered a tile at a time). Rows of tiles that hold
    no pair are computed like any other; the layer never reads them."""
    tiles = x.shape[0] // tile_m
    out = jnp.einsum("tmk,tkn->tmn", x.reshape(tiles, tile_m, x.shape[1]),
                     w[tile_expert])
    return out.reshape(x.shape[0], w.shape[2]).astype(x.dtype)


def grouped_matmul(x, w, tile_expert, num_tiles, *, tile_m: int):
    """The routed expert layer's grouped product: ``x [M, K]`` (sorted
    token-expert pairs, every expert's run on whole ``tile_m``-row
    tiles), ``w [E, K, N]``, ``tile_expert [M / tile_m]``, ``num_tiles
    [1]`` the live leading tiles. Always returns ``[M, N]`` (rows of
    dead tiles: zeros from the kernel, a product like any other from
    the plain form; nothing reads them): the pallas kernel
    (:mod:`bigdl_tpu.kernels.moe_gmm`) when ``gmm`` is enabled and the
    shapes tile, else the jnp form above. Unlike the other dispatchers
    it owns its fallback, because the kernel's backward pass IS the
    fallback's (a forward kernel, differentiated through the plain
    form), so the two must stay one definition."""
    import jax

    from bigdl_tpu.kernels.common import sublanes

    if not _config.enabled("gmm"):
        _declined("gmm", "config")
        return _grouped_reference(x, w, tile_expert, tile_m)
    interpret = _config.get_config().resolve_interpret()
    k, n = w.shape[1], w.shape[2]
    if (x.ndim != 2 or w.ndim != 3 or not _floating(x, w)
            or x.dtype != w.dtype
            or (not interpret and (tile_m % sublanes(x.dtype)
                                   or k % 128 or n % 128))):
        _declined("gmm", "shape")
        return _grouped_reference(x, w, tile_expert, tile_m)
    from bigdl_tpu.kernels.moe_gmm import grouped_matmul_pallas

    _taken("gmm")

    @jax.custom_vjp
    def gmm(x, w, te, nt):
        return grouped_matmul_pallas(x, w, te, nt, tile_m=tile_m,
                                     interpret=interpret)

    def fwd(x, w, te, nt):
        return gmm(x, w, te, nt), (x, w, te)

    def bwd(saved, g):
        # rows of dead tiles carry no gradient: the layer gathers only
        # live rows, so their cotangent is zero already
        x, w, te = saved
        _, pull = jax.vjp(
            lambda a, b: _grouped_reference(a, b, te, tile_m), x, w)
        return pull(g) + (None, None)

    gmm.defvjp(fwd, bwd)
    return gmm(x, w, tile_expert.astype(jnp.int32),
               num_tiles.astype(jnp.int32))
