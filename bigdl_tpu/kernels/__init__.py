"""Pallas kernel layer — the hand-tuned L0 the reference built in C++.

The source system's bottom layer was native kernels behind JNI (Intel
MKL + the BigQuant int8 GEMM, PAPER.md L0); the TPU-native analogue is
``jax.experimental.pallas``. This package holds the kernels and the
ONE gate in front of them:

- :mod:`~bigdl_tpu.kernels.flash_attention` — fused flash attention
  for training: q-tiled, causal chunks skipped, segment-mask aware
  (packed datapipe slabs run bit-faithfully), custom-VJP backward, no
  materialized [S, S];
- :mod:`~bigdl_tpu.kernels.ragged_decode` — ragged decode
  attention for the generation engine: reads only ``lengths[i]`` valid
  KV per slot instead of the bucket max, and writes the step's new K/V
  column into the cache itself;
- :mod:`~bigdl_tpu.kernels.ssm_decode` — one decode step of a
  state-space layer: every slot's recurrent state read once, updated
  and written back in place;
- :mod:`~bigdl_tpu.kernels.int8_gemm` — fused dequant-int8-GEMM
  completing the BigQuant serving story over the calibrated scales;
- :mod:`~bigdl_tpu.kernels.moe_gmm` — the routed expert layer's
  grouped product over sorted, tile-aligned token-expert pairs: an
  expert no pair fell on is never read;
- :mod:`~bigdl_tpu.kernels.dispatch` — :func:`attention` /
  :func:`decode_attention` / :func:`ssm_decode_step` / :func:`int8_matmul`
  / :func:`grouped_matmul`: config + shape
  eligibility in, kernel result or None (= run your jnp path) out;
- :mod:`~bigdl_tpu.kernels.config` — :class:`KernelConfig` and the
  ``BIGDL_KERNELS`` env toggle; every kernel default ON on real TPU
  (flash since PR 35: which shapes take it is the dispatch's measured
  rule), everything OFF on CPU, and kernels run under the pallas
  *interpreter* everywhere but real TPU so tier-1 on CPU executes the
  real kernel bodies.

Every kernel ships with an interpret-mode equivalence test against the
pure-jnp fallback (tests/test_kernels.py; bitwise for the int8 core
and the greedy decode token stream, tolerance-bounded for softmax
reductions) and registers its programs with a ``kernel=pallas|
reference`` label in :mod:`bigdl_tpu.telemetry.programs` so MFU/HBM
gauges compare the two paths side by side. See docs/kernels.md.
"""
from bigdl_tpu.kernels.config import (KernelConfig, active_label,
                                      configure, enabled, get_config,
                                      interpret_mode, use)
from bigdl_tpu.kernels.dispatch import (attention, decode_attention,
                                        grouped_matmul, int8_matmul,
                                        ssm_decode_step)

__all__ = ["KernelConfig", "configure", "get_config", "use", "enabled",
           "interpret_mode", "active_label", "attention",
           "decode_attention", "ssm_decode_step", "int8_matmul",
           "grouped_matmul"]
