"""On-chip int8-vs-bf16 shape sweep (the quantization verdict as a
MEASUREMENT, not an assertion — the reference's BigQuant was a
measured speed feature on Xeon, nn/quantized/Linear.scala:77-88; this
establishes where, if anywhere, the int8 path wins on this device).

Sweeps Linear (batch x in x out) over the pallas int8 fused matmul and
the plain jnp int8 path vs the bf16 MXU matmul, plus one conv case.
Each timing is a scanned chunk with a value fetch.

    python -m bigdl_tpu.tools.int8_sweep [iters]

.. deprecated:: PR 9
    Scale estimation moved to ``bigdl_tpu/precision/calibrate.py`` —
    the ONE int8 calibration path (weights via ``calibrate_weight``,
    activations via ``collect_activation_scales``; both derive from
    ``ops/quant.scale_from_amax``). This tool now delegates its weight
    scales there and remains CLI-compatible, but new code should
    calibrate through ``precision.calibrate`` / ``ModelRegistry.load(
    quantize=True, calibration=...)`` rather than calling
    ``quantize_symmetric`` directly. For choosing a precision policy
    from measurements, prefer the profile-guided autotuner:
    ``python -m bigdl_tpu.tools.autotune`` (docs/autotune.md).
"""
import json
import sys
import time



def _time_chunk(fn, args, scan: int, iters: int):
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnums=())
    def chunk(*a):
        def body(carry, _):
            # the carry perturbs the first operand so every scan step
            # DEPENDS on the previous one — a loop-invariant body gets
            # hoisted by XLA and the scan would time nothing but adds
            a0 = a[0] + jnp.asarray(carry, a[0].dtype)
            r = fn(a0, *a[1:])
            # the timing carry is a deliberate f32 scalar reduction —
            # it measures the kernel, it is not on a policy's hot path
            return r.astype(jnp.float32).sum() * 1e-30, None  # bigdl: disable=implicit-upcast-in-trace
        out, _ = lax.scan(body, jnp.float32(0.0), None, length=scan)
        return out

    r = chunk(*args)
    float(r)  # compile + warm
    t0 = time.time()
    for _ in range(iters):
        float(chunk(*args))
    return (time.time() - t0) / (iters * scan)


def main(argv=None):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.pallas_kernels import pallas_quantized_matmul
    from bigdl_tpu.ops.quant import quantized_linear
    # the one scale-estimation path (precision/calibrate.py delegates to
    # ops/quant's max-abs rule): weight AND activation scales below
    # come from here — this tool holds no quantization math of its own
    from bigdl_tpu.precision.calibrate import (calibrate_activation,
                                               calibrate_weight)

    import os
    args = argv if argv is not None else sys.argv[1:]
    iters = int(args[0]) if args else 3
    print("# int8_sweep measures kernels only; to pick a precision "
          "policy from measurements use: python -m "
          "bigdl_tpu.tools.autotune")
    # scan long enough that compute dominates the per-chunk dispatch
    # round trip
    scan = int(os.environ.get("BENCH_SCAN", 64))
    on_tpu = jax.devices()[0].platform == "tpu"

    shapes = [
        # (batch, in, out) — memory-bound tall/skinny through MXU-bound
        (256, 1024, 1024),
        (1024, 1024, 1024),
        (4096, 1024, 1024),
        (256, 4096, 4096),
        (1024, 4096, 4096),
        (4096, 4096, 4096),
        (16384, 2048, 2048),
        (256, 8192, 8192),
    ]
    from bigdl_tpu.tools.synthetic import gaussian_matrix

    rows = []
    for b, cin, cout in shapes:
        x = jnp.asarray(gaussian_matrix((b, cin)))
        w = jnp.asarray(gaussian_matrix((cout, cin), scale=0.05, seed=1))
        w_q, w_s = calibrate_weight(w, axis=0)  # per-out-channel
        x16 = x.astype(jnp.bfloat16)
        w16 = w.T.astype(jnp.bfloat16)

        def bf16_mm(x16, w16):
            return x16 @ w16

        t_bf16 = _time_chunk(bf16_mm, (x16, w16), scan, iters)

        def jnp_int8(x, w_q, w_s):
            return quantized_linear(x, w_q, w_s)

        t_jnp8 = _time_chunk(jnp_int8, (x, w_q, w_s), scan, iters)

        t_pl8 = None
        if on_tpu:
            x_q, x_s = calibrate_activation(x, axis=0)  # per-sample rows

            def pl8(x_q, w_q, x_s, w_s):
                return pallas_quantized_matmul(x_q, w_q, x_s, w_s)

            try:
                t_pl8 = _time_chunk(pl8, (x_q, w_q, x_s, w_s), scan,
                                    iters)
            except Exception as e:
                t_pl8 = f"failed: {type(e).__name__}"
        best8 = min([t for t in (t_jnp8, t_pl8)
                     if isinstance(t, float)])
        row = {"shape": [b, cin, cout],
               "bf16_ms": round(t_bf16 * 1e3, 3),
               "jnp_int8_ms": round(t_jnp8 * 1e3, 3),
               "pallas_int8_ms": (round(t_pl8 * 1e3, 3)
                                  if isinstance(t_pl8, float) else t_pl8),
               "int8_speedup_vs_bf16": round(t_bf16 / best8, 3)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # one conv case: ResNet-50's 3x3/256 block conv at eval batch
    from bigdl_tpu.ops.quant import quantized_conv2d
    x = jnp.asarray(gaussian_matrix((64, 256, 28, 28)))
    w = jnp.asarray(gaussian_matrix((256, 256, 3, 3), scale=0.05, seed=1))
    w_q, w_s = calibrate_weight(w, axis=0)  # per-out-channel

    def bf16_conv(x, w):
        from jax import lax
        return lax.conv_general_dilated(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (1, 1),
            ((1, 1), (1, 1)), dimension_numbers=("NCHW", "OIHW", "NCHW"))

    t_bc = _time_chunk(bf16_conv, (x, w), scan, iters)

    def int8_conv(x, w_q, w_s):
        return quantized_conv2d(x, w_q, w_s, stride=(1, 1),
                                padding=((1, 1), (1, 1)))

    t_ic = _time_chunk(int8_conv, (x, w_q, w_s), scan, iters)
    row = {"shape": "conv 64x256x28x28 3x3/256",
           "bf16_ms": round(t_bc * 1e3, 3),
           "jnp_int8_ms": round(t_ic * 1e3, 3),
           "int8_speedup_vs_bf16": round(t_bc / t_ic, 3)}
    rows.append(row)
    print(json.dumps(row), flush=True)
    wins = [r for r in rows
            if isinstance(r.get("int8_speedup_vs_bf16"), float)
            and r["int8_speedup_vs_bf16"] > 1.05]
    print(json.dumps({"verdict": (
        f"int8 wins at {len(wins)}/{len(rows)} shapes"
        if wins else "bf16 wins at every swept shape — int8 is a "
        "footprint feature on this device class")}))
    return rows


if __name__ == "__main__":
    main()
