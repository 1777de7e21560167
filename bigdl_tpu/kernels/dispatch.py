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
``op=flash|decode|decode_write|ssm_decode|int8|gmm``; ``decode_write``
is a second label of the decode kernel, for the cache column it writes)
vs
``kernels/dispatch/reference`` (labels ``op=...`` plus
``reason=config|shape|vmem|mesh`` so a `diagnose` dump attributes every
decline).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.kernels import config as _config
from bigdl_tpu.kernels.common import fit_block

__all__ = ["attention", "decode_attention", "ssm_decode_step", "int8_matmul",
           "grouped_matmul", "flash_route", "taken_in_thread",
           "declined_in_thread"]

# module-level registration so `tools.check --telemetry-audit` sees the
# REAL instruments on import, not a hand-maintained name list
_C_PALLAS = telemetry.counter(
    "kernels/dispatch/pallas",
    "traces routed to a pallas kernel (label op=flash|decode|"
    "decode_write|ssm_decode|int8|gmm)")
_C_REFERENCE = telemetry.counter(
    "kernels/dispatch/reference",
    "traces declined by the dispatch layer to the pure-jnp reference "
    "(labels op=flash|decode|ssm_decode|int8|gmm, "
    "reason=config|shape|vmem|mesh)")


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


def declined_in_thread(op: str) -> int:
    """:func:`taken_in_thread`'s twin: dispatches of ``op`` declined on
    this thread, whatever the reason. Taken over taken + declined
    across a trace is the share of a program's calls that run as the
    kernel (``train/optimizer/attn_in_kernel_share``)."""
    return getattr(_TRACE, "declined", {}).get(op, 0)


def _declined(op: str, reason: str) -> None:
    # reason= makes declines attributable in `diagnose`: "config" (the
    # active KernelConfig disabled the op), "shape" (ineligible dtype/
    # rank/alignment, or a length at which the plain form measured
    # faster), "vmem" (over the flash working-set budget with no
    # kernel left to take it), "mesh" (the partitioner may split the
    # program over devices, and it cannot split a Mosaic kernel)
    _C_REFERENCE.inc(op=op, reason=reason)
    by_op = _TRACE.__dict__.setdefault("declined", {})
    by_op[op] = by_op.get(op, 0) + 1


def _taken(*ops: str) -> None:
    """One kernel taken, counted under each of its labels."""
    _TRACE.taken = getattr(_TRACE, "taken", 0) + 1
    by_op = _TRACE.__dict__.setdefault("by_op", {})
    for op in ops:
        _C_PALLAS.inc(op=op)
        by_op[op] = by_op.get(op, 0) + 1


def _floating(*arrays) -> bool:
    return all(jnp.issubdtype(a.dtype, jnp.floating) for a in arrays)


# The flash selection rests on one table: one layer's causal attention
# alone on a v5e, forward + backward, ms by device time (PERF.md
# section 6, PR 35, calls t1 and t3; bf16 unless said):
#
#   [B,H,S,D]        einsum  full-row chunk 128/256/512  blockwise 256/512/1024
#   [4,16, 256,64]    0.035   0.216 / 0.118 /   -          0.134 /   -   /  -
#   [4,16, 512,64]    0.501   0.636 / 0.327 / 0.217        0.501 / 0.285 /  -
#   [4,16, 640,64]    0.935   0.941 /   -   /   -            -
#   [4,16, 896,64]    1.706   1.751 /   -   /   -            -
#   [4,16,1024,64]    2.891   2.173 / 1.017 / 0.678        1.768 / 1.075 / 0.999
#   [4,16,1152,64]    3.503   chunk 384: 0.965               -
#   [4,16,1280,64]    4.352     -   / 1.514 /   -            -
#   [8,12,1024,64]    4.265   3.324 / 1.585 / 1.066        2.667 / 1.627 / 1.502
#   [4,16,1024,64]f32 3.957   2.352 / 1.232 / 0.887        2.131 / 1.375 / 1.091
#   [2,16,2048,64]    5.490   3.817 / 1.636 / 1.004        2.985 / 1.690 / 1.716
#   [1,16,4096,64]   15.602   7.102 / 2.868 / 1.656        5.249 / 2.821 / 2.740
#   [1,16,8192,64]      -       -   /   -   / 6.038          -   /10.469 /  -
#   [1,8,10752,64]      -       -   /   -   / 5.046          -   / 8.562 /  -
#   [1,8,16384,64]      -     (past the kernel's VMEM)       -   /19.730 /  -
#
# so: under 512 keys the einsum form wins (3.4x at 256) and the
# dispatch declines; from 512 on the full-row kernel wins at every
# length it can hold (1.7x over blockwise at 8192 and 10752, the
# budget's edge), at the largest chunk that divides the length; past
# its VMEM the blockwise kernel takes over at 512 tiles (1024 reads
# the same and holds four times the scores). A side of 128 is on
# neither path: in the full-row form it reads what the einsums read
# at 640 and 896 (forward alone 2.7x and 1.3x theirs), in the
# blockwise form it was not measured. So a length whose largest
# lane-aligned divisor up to 512 is 128 (640, 896, 1408) declines,
# like one that is no multiple of 128 at all (600, 900), whose blocks
# no compile for a v5e covers.

#: side of the full-row kernels' square score chunk and of the
#: blockwise kernels' tile, where the length divides by it
_FLASH_BLOCK = 512
#: the smallest side the table supports
_FLASH_MIN_BLOCK = 256
#: compiled, a shorter sequence stays with the einsum form
_FLASH_MIN_SEQ = 512


def _flash_vmem_bytes(s: int, d: int, itemsize: int, chunk: int) -> int:
    """Estimated VMEM working set of ONE full-row grid program, the
    larger of the two kernels'. Backward: K and V whole and the dK / dV
    output blocks, each double-buffered at the input dtype, the two
    ``[S, D]`` float32 dK / dV accumulators, three float32 ``[chunk,
    chunk]`` score temporaries and the q / do / dq tiles. Forward: K
    and V, the ``[S, chunk]`` float32 score strip (what binds from
    S = 2048 on), one chunk of scores and the tiles. Budgeting on the
    larger keeps jax.grad from refusing what the forward alone would
    have taken. The estimate errs high: compiled for a v5e at the
    kernels' ``common.FLASH_VMEM_LIMIT_MB``, the first length the
    compiler refuses has an estimate of 37 MiB or more at every head
    size, dtype and chunk probed (PERF.md section 6, PR 35), and the
    default budget is that limit less 4 MiB
    (``KernelConfig.vmem_budget_mb``)."""
    kv = 2 * 2 * s * d * itemsize
    backward = (2 * kv + 2 * s * d * 4 + 3 * chunk * chunk * 4
                + 6 * chunk * d * 4)
    forward = kv + s * chunk * 4 + chunk * chunk * 4 + 4 * chunk * d * 4
    return max(backward, forward)


def flash_route(shape, itemsize: int, *, segmented: bool, interpret: bool,
                vmem_budget: int, long_context: bool = True):
    """Which form ``[B, H, S, D]`` attention takes, from what the
    dispatch can see: ``("full", chunk)``, ``("blockwise", tile)`` or
    ``("declined", reason)``. The rule and the measurements it rests
    on are in the comment above; chunk and tile follow from the shape.
    Packed slabs (``segmented``) keep the full-row kernel's
    bit-exact-per-token contract, so they take the full-row form or
    decline — never the blockwise one, whose rescaling rounds by where
    the tile boundaries fall. Under the interpreter nothing is timed
    and nothing is laid out in lanes, so no length is too short or
    too odd: tier-1 runs the kernel bodies at test sizes."""
    s, d = int(shape[-2]), int(shape[-1])
    if interpret:
        side = fit_block(s, _FLASH_BLOCK)
    else:
        if s < _FLASH_MIN_SEQ or s % 128:
            return "declined", "shape"
        side = fit_block(s, _FLASH_BLOCK, align=128)
        if side < _FLASH_MIN_BLOCK:
            return "declined", "shape"
    if _flash_vmem_bytes(s, d, itemsize, side) <= vmem_budget:
        return "full", side
    # past the budget the full-row kernel would OOM Mosaic (an error,
    # not a fallback): the blockwise kernel tiles the key axis through
    # VMEM too — unless it is switched off or the slab is packed; then
    # decline so nn.attention's einsum / bundled-flash routes keep the
    # escape hatch
    if segmented or not long_context:
        return "declined", "vmem"
    return "blockwise", side


def _partitioned() -> bool:
    """Whether the partitioner may split the program being traced over
    devices. It cannot split a Mosaic kernel: jax refuses to lower one
    ("Mosaic kernels cannot be automatically partitioned") unless the
    program runs on one device or every axis of its mesh is manual.
    Inside a ``shard_map`` the trace says which axes are manual. A
    plain ``jax.jit`` learns its devices from its arguments, after the
    trace: there only a process that sees one device is sure of one
    (``DistriOptimizer`` on a data mesh runs its step as such a jit)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return set(mesh.manual_axes) != set(mesh.axis_names)
    return jax.device_count() > 1


def attention(q, k, v, *, causal: bool = False, segment_ids=None,
              sm_scale: Optional[float] = None):
    """Flash-attention dispatch for ``[B, H, S, D]`` q/k/v: a fused
    pallas kernel (:mod:`bigdl_tpu.kernels.flash_attention`, segment-
    mask aware, differentiable) when the active config enables
    ``flash``, :func:`flash_route` finds a form for the shape and,
    compiled, the program is one device's (:func:`_partitioned`) —
    else **None**, telling the caller to run its jnp path
    (``nn.attention.dot_product_attention`` falls through to the
    einsum form, which itself still routes HBM-busting lengths to
    jax's bundled flash kernel)."""
    if not _config.enabled("flash"):
        _declined("flash", "config")
        return None
    if (q.ndim != 4 or k.shape != q.shape or v.shape != q.shape
            or not _floating(q, k, v)):
        _declined("flash", "shape")
        return None
    cfg = _config.get_config()
    interpret = cfg.resolve_interpret()
    form, how = flash_route(
        q.shape, q.dtype.itemsize, segmented=segment_ids is not None,
        interpret=interpret, vmem_budget=cfg.resolve_vmem_budget(),
        long_context=cfg.long_context)
    if form == "declined":
        _declined("flash", how)
        return None
    if not interpret and _partitioned():
        _declined("flash", "mesh")
        return None
    from bigdl_tpu.kernels import flash_attention as _flash

    _taken("flash")
    if form == "blockwise":
        return _flash.blockwise_flash_attention(
            q, k, v, segment_ids, causal=causal, sm_scale=sm_scale,
            block_q=how, block_k=how, interpret=interpret)
    return _flash.flash_attention(q, k, v, segment_ids, causal=causal,
                                  sm_scale=sm_scale, block_q=how,
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


def ssm_decode_step(state, dec, dtx, bc):
    """State-space decode dispatch: one token a slot through a Mamba-2
    layer's recurrent state ``[slots, Hq, N, L]`` float32, with ``dec``
    / ``dtx`` ``[slots, Hq, L]`` and ``bc [slots, N, 2 G]``
    (:func:`bigdl_tpu.nn.ssm.decode_operands`). Returns ``(y, state)``
    from the kernel (:mod:`bigdl_tpu.kernels.ssm_decode` - the state
    read once and written in place, aliased through) when ``decode`` is
    enabled (the decode kernels share the switch) and the shapes
    qualify - compiled: whole lane tiles, ``N`` on whole sublane tiles
    and a block of rows :func:`~bigdl_tpu.kernels.ssm_decode.state_rows`
    can cut - else **None**: the caller runs its plain form."""
    if not _config.enabled("decode"):
        _declined("ssm_decode", "config")
        return None
    from bigdl_tpu.kernels.ssm_decode import ssm_decode_pallas, state_rows

    interpret = _config.get_config().resolve_interpret()
    groups = bc.shape[-1] // 2
    ok = (state.ndim == 4 and groups > 0 and state.shape[1] % groups == 0
          and state.dtype == dec.dtype == dtx.dtype == bc.dtype
          == jnp.float32)
    if ok:
        hq, n, lanes = state.shape[1:]
        ok = (state_rows(hq, hq // groups, n, lanes) is not None
              and (interpret or (lanes % 128 == 0 and n % 8 == 0)))
    if not ok:
        _declined("ssm_decode", "shape")
        return None
    _taken("ssm_decode")
    return ssm_decode_pallas(state, dec, dtx, bc, interpret=interpret)


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
