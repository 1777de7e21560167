"""Fused dequant-int8 GEMM — the BigQuant story's serving kernel.

The reference's BigQuant ships hand-written SIMD int8 GEMM (C++ via
JNI — SURVEY.md §1 L0). The TPU analogue keeps the int8 multiply on
the MXU with int32 accumulation across K tiles in VMEM scratch and
fuses the fp32 dequant epilogue (``acc · x_scale · w_scale``) into the
same kernel — the int32 accumulator never round-trips HBM. Scales come
from the ONE max-abs rule (:func:`bigdl_tpu.ops.quant.scale_from_amax`):
dynamic per-row, or the calibrated per-tensor scales PR 9's
``precision/calibrate.py`` certifies.

**Bitwise contract:** integer accumulation is exact under K-splitting,
and the epilogue multiplies in the same order as the reference
(``ops.quant.quantized_linear``), so the kernel is *bit-identical* to
dequantize-then-matmul. The bias add deliberately lives in the
dispatch layer (one jnp add shared by both paths): fused into the
kernel, XLA contracts ``mul·mul + bias`` into an FMA and the result
drifts one ulp from the reference — measured, which is why the
kernel's ``with_bias`` epilogue exists for full-fusion callers but the
dispatched path adds bias outside (docs/kernels.md "Equivalence
contract").

Used through :func:`bigdl_tpu.kernels.int8_matmul`; the legacy import
site ``bigdl_tpu.ops.pallas_kernels`` re-exports from here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import fit_block, tpu_compiler_params

__all__ = ["pallas_quantized_matmul"]


def _qmm_kernel(x_ref, w_ref, xs_ref, ws_ref, b_ref, o_ref, acc_ref, *,
                k_steps: int, with_bias: bool):
    """One (bm, bn) output tile; K is the innermost ("arbitrary") grid
    dim.

    x_ref: (bm, bk) int8 activations | w_ref: (bn, bk) int8 weights
    xs_ref: (bm, 1) f32 row scales   | ws_ref: (1, bn) f32 channel scales
    acc_ref: (bm, bn) int32 scratch accumulator
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        if with_bias:
            out = out + b_ref[...]
        o_ref[...] = out


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "interpret"))
def pallas_quantized_matmul(x_q, w_q, x_scale, w_scale, bias=None, *,
                            bm: int = 256, bn: int = 256, bk: int = 512,
                            interpret: bool = False):
    """Fused int8 GEMM + dequant: ``(x_q [M,K] i8) @ (w_q [N,K] i8)^T``
    rescaled by per-row ``x_scale`` and per-channel ``w_scale``
    (module docstring has the memory story and bitwise contract).
    Block sizes shrink to the largest divisor of each dim, so any
    shape tiles exactly; ``bias=None`` is the bit-identical dispatched
    form (bias added by the caller), a non-None ``bias`` fuses the add
    at one-ulp FMA tolerance."""
    from jax.experimental.pallas import tpu as pltpu

    m, k = x_q.shape
    n = w_q.shape[0]
    bm, bn, bk = fit_block(m, bm), fit_block(n, bn), fit_block(k, bk)
    k_steps = k // bk
    with_bias = bias is not None
    xs = x_scale.reshape(m, 1).astype(jnp.float32)
    ws = w_scale.reshape(1, n).astype(jnp.float32)
    b = (bias.reshape(1, n).astype(jnp.float32) if with_bias
         else jnp.zeros((1, n), jnp.float32))

    grid = (m // bm, n // bn, k_steps)
    kernel = functools.partial(_qmm_kernel, k_steps=k_steps,
                               with_bias=with_bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bigdl_int8_gemm",
    )(x_q, w_q, xs, ws, b)
