"""Throughput harness for zoo models on synthetic data (reference:
models/utils/DistriOptimizerPerf.scala:38 / LocalOptimizerPerf.scala —
the de-facto benchmark tool; SURVEY.md §6).

Usage:
    python -m bigdl_tpu.tools.perf --model resnet50 --batch-size 64 \
        --iterations 20 [--mode train|inference] [--dtype bf16]
Prints per-iteration and summary images/sec.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import bigdl_tpu.telemetry as telemetry

# module-level registration so `tools.check --telemetry-audit` sees the
# REAL instruments on import, not a hand-maintained name list
_ITER_S = telemetry.histogram(
    "tools/perf/iteration_s", "seconds per timed perf iteration")
_WARMUP_S = telemetry.histogram(
    "tools/perf/warmup_s",
    "seconds per warmup iteration (includes the compile)")


def build_model(name: str, class_num: int = 1000):
    from bigdl_tpu import models
    name = name.lower()
    if name in ("lenet", "lenet5"):
        return models.LeNet5(10), (1, 28, 28), 10
    if name in ("vgg16", "vgg_16"):
        return models.Vgg_16(class_num), (3, 224, 224), class_num
    if name in ("vgg19", "vgg_19"):
        return models.Vgg_19(class_num), (3, 224, 224), class_num
    if name.startswith("resnet"):
        depth = int(name[len("resnet"):] or 50)
        return (models.ResNet(class_num, depth=depth, dataset="ImageNet"),
                (3, 224, 224), class_num)
    if name in ("alexnet", "alexnetowt", "alexnet_owt"):
        # DistriOptimizerPerf.scala:44 offers both forms
        builder = models.AlexNet if name == "alexnet" else models.AlexNet_OWT
        size = 227 if name == "alexnet" else 224
        return builder(class_num), (3, size, size), class_num
    if name in ("inception_v2", "inception-v2", "inceptionv2"):
        return (models.Inception_v2_NoAuxClassifier(class_num),
                (3, 224, 224), class_num)
    if name.startswith("inception"):
        return models.Inception_v1(class_num), (3, 224, 224), class_num
    if name.startswith("transformer"):
        return (models.TransformerLM(vocab_size=32000, hidden_size=512,
                                     num_layers=6, num_heads=8,
                                     max_len=512), (512,), 32000)
    raise ValueError(f"unknown model {name}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--mode", choices=["train", "inference"],
                    default="train")
    ap.add_argument("--dtype", choices=["f32", "bf16"], default="bf16",
                    help="legacy Engine compute-dtype knob; ignored "
                    "when --precision names a full policy")
    ap.add_argument("--precision", default=None,
                    choices=["f32", "bf16_mixed", "f16_mixed"],
                    metavar="POLICY",
                    help="explicit precision policy "
                    "(bigdl_tpu.precision.PrecisionPolicy preset): "
                    "param/compute/output/accum dtypes compiled into "
                    "the step, f32 master copy + dynamic loss scaling "
                    "for f16_mixed — the policy twin of "
                    "Optimizer.set_precision")
    ap.add_argument("--quantize", action="store_true",
                    help="int8 inference rewrite (inference mode only — "
                    "the reference's quantized serving story, "
                    "nn/quantized/Quantization.scala:168)")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="append a telemetry metrics snapshot (per-"
                    "iteration phase histograms + run meta) to PATH as "
                    "one JSONL line; default off (BIGDL_METRICS_JSONL "
                    "env var also enables it)")
    ap.add_argument("--steps-per-sync", type=int, default=1, metavar="K",
                    help="train mode: fuse K steps into one scanned "
                    "dispatch and sync the host once per window "
                    "(Optimizer.set_steps_per_sync's measurement twin); "
                    "1 = classic per-step dispatch")
    ap.add_argument("--sync-compare", action="store_true",
                    help="train mode: additionally measure steps/sec at "
                    "K=1 vs K=8 fused windows and report both in the "
                    "JSON tail line")
    ap.add_argument("--kernels", choices=["on", "off"], default=None,
                    metavar="{on,off}",
                    help="pallas kernel layer (bigdl_tpu.kernels): "
                    "'on' enables flash attention / ragged decode / "
                    "int8 GEMM dispatch (interpret mode off-TPU), "
                    "'off' forces the pure-jnp reference everywhere; "
                    "default: the backend/BIGDL_KERNELS policy. The "
                    "JSON tail carries kernels= and the program's "
                    "kernel label so a KERNELS on-vs-off pair is "
                    "attributable")
    ap.add_argument("--zero", type=int, choices=(0, 1, 2, 3), default=0,
                    metavar="STAGE",
                    help="train mode: ZeRO weight-update sharding stage "
                    "over a data-parallel mesh of ALL devices (parallel/"
                    "zero.py — 1: sharded opt state, 2: + gradient "
                    "reduce-scatter, 3: + params sharded at rest); the "
                    "JSON tail reports opt_state/params bytes per chip")
    ap.add_argument("--config", default=None, metavar="TUNED_JSON",
                    help="apply a tuned.json artifact from `python -m "
                    "bigdl_tpu.tools.autotune` — its train winner "
                    "overrides --steps-per-sync/--zero/--precision/"
                    "--batch-size/--kernels; refused (typed error) if "
                    "the artifact's environment fingerprint mismatches "
                    "this machine")
    args = ap.parse_args(argv)
    tuned_applied = []
    if args.config is not None:
        from bigdl_tpu.autotune.config import (apply_to_perf_args,
                                               load_tuned)
        tuned = load_tuned(args.config)
        tuned_applied = apply_to_perf_args(tuned, args)
        print(f"# tuned config {args.config}: applied "
              f"{','.join(tuned_applied) or 'nothing'}")
    if args.steps_per_sync < 1:
        raise SystemExit("--steps-per-sync must be >= 1")

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_eval_step, build_train_step
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    Engine.init()
    if args.kernels is not None:
        from bigdl_tpu import kernels as _kernels
        _kernels.configure(_kernels.KernelConfig.all_on()
                           if args.kernels == "on"
                           else _kernels.KernelConfig.off())
    if args.dtype == "bf16":
        Engine.set_compute_dtype(jnp.bfloat16)
    policy = None
    if args.precision is not None:
        from bigdl_tpu.precision import PrecisionPolicy
        policy = PrecisionPolicy.named(args.precision)
    RandomGenerator.set_seed(42)

    from bigdl_tpu.tools import synthetic

    model, in_shape, class_num = build_model(args.model)
    is_lm = len(in_shape) == 1
    if is_lm:
        xs, ys = synthetic.token_batch(args.batch_size, in_shape[0],
                                       class_num)
        criterion = nn.SequenceCrossEntropyCriterion()
    else:
        xs, ys = synthetic.image_batch(args.batch_size, in_shape,
                                       class_num)
        criterion = nn.CrossEntropyCriterion()
    x, y = jnp.asarray(xs), jnp.asarray(ys)

    model.training() if args.mode == "train" else model.evaluate()
    model.ensure_initialized()
    if args.quantize:
        if args.mode != "inference":
            raise SystemExit("--quantize is inference-only")
        model = model.quantize().evaluate()
        model.ensure_initialized()
    params = model.get_parameters()
    mstate = model.get_state()

    # ONE AOT compile serves both the timed loop and the MFU cost
    # analysis (a post-hoc step.lower().compile() would re-compile the
    # whole program a second time just to read the flop count)
    compiled_for_cost = None
    sync_k = args.steps_per_sync if args.mode == "train" else 1
    zero_meta = {}
    if args.mode == "train":
        import functools
        from jax import lax

        optim = SGD(learning_rate=0.01, momentum=0.9)
        opt_state = optim.init_state(params)
        if policy is not None:
            # seed the policy's opt-state keys the way
            # Optimizer.set_precision does (master copy, scaler state)
            from bigdl_tpu.precision import (MASTER_KEY, SCALER_KEY,
                                             DynamicLossScaler)
            if policy.needs_master:
                opt_state[MASTER_KEY] = params
                params = policy.cast_to_param(params)
            if policy.needs_loss_scaling:
                opt_state[SCALER_KEY] = DynamicLossScaler().init_state()
        zero_cfg, zero_mesh = None, None
        if args.zero:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from bigdl_tpu.parallel import (ZeroConfig,
                                            data_parallel_mesh,
                                            place_zero_state,
                                            record_memory_gauges)
            zero_mesh = data_parallel_mesh()
            ndev = zero_mesh.shape["data"]
            if args.batch_size % ndev:
                raise SystemExit(
                    f"--zero needs --batch-size divisible by the "
                    f"{ndev}-device data mesh, got {args.batch_size}")
            zero_cfg = ZeroConfig(stage=args.zero)
            repl = NamedSharding(zero_mesh, P())
            bsh = NamedSharding(zero_mesh, P("data"))
            params, opt_state = place_zero_state(params, opt_state,
                                                 zero_mesh, zero_cfg)
            mstate = jax.device_put(mstate, repl)
            x = jax.device_put(x, bsh)
            y = jax.device_put(y, bsh)
            zero_meta = dict(record_memory_gauges(params, opt_state),
                             zero_stage=args.zero, zero_devices=ndev)
        jit_step = build_train_step(model, criterion, optim,
                                    zero=zero_cfg, mesh=zero_mesh,
                                    precision=policy)
        key = jax.random.PRNGKey(0)

        def make_chunk(k):
            # k fused train steps over the SAME resident batch, per-step
            # keys threaded as scan xs — measures what bounded async
            # dispatch amortizes (per-dispatch + per-sync host cost),
            # with zero feed variance
            def body(carry, kk):
                p, o, m = carry
                p, o, m, loss = jit_step(p, o, m, kk, 0.01, x, y)
                return (p, o, m), loss

            @functools.partial(jax.jit, donate_argnums=(0,))
            def chunk(carry, keys):
                return lax.scan(body, carry, keys)
            return chunk

        if sync_k > 1:
            chunk = make_chunk(sync_k)
            keys0 = jax.random.split(key, sync_k)
            carry = (params, opt_state, mstate)
            try:
                chunk = chunk.lower(carry, keys0).compile()
                compiled_for_cost = chunk
            except Exception as e:
                print(f"# cost-analysis unavailable ({type(e).__name__})")

            def run():
                nonlocal carry
                carry, losses = chunk(carry, keys0)
                # close the window on the full carry, not the loss path
                jax.block_until_ready(carry[0])
                return losses
        else:
            step = jit_step
            try:
                step = step.lower(params, opt_state, mstate, key, 0.01,
                                  x, y).compile()
                compiled_for_cost = step
            except Exception as e:
                print(f"# cost-analysis unavailable ({type(e).__name__})")

            def run():
                nonlocal params, opt_state, mstate
                params, opt_state, mstate, loss = step(
                    params, opt_state, mstate, key, 0.01, x, y)
                # the loss fetch in sync() does not gate on the param
                # update branch of the program; block here so
                # per-iteration timings cover the WHOLE step, not just
                # the loss path
                jax.block_until_ready(params)
                return loss
    else:
        eval_step = build_eval_step(model, precision=policy)
        try:
            eval_step = eval_step.lower(params, mstate, x).compile()
            compiled_for_cost = eval_step
        except Exception as e:
            print(f"# cost-analysis unavailable ({type(e).__name__})")

        def run():
            return eval_step(params, mstate, x)

    def sync(out):
        # fetch a VALUE, not just block_until_ready: the value cannot
        # exist before the execution that computes it has completed
        leaf = jax.tree_util.tree_leaves(out)[0]
        return float(jnp.sum(jnp.asarray(leaf).astype(jnp.float32)))

    recs_per_iter = (args.batch_size * sync_k
                     * (in_shape[0] if is_lm else 1))
    prec_tag = args.precision if args.precision else args.dtype
    print(f"# {args.model} {args.mode} batch={args.batch_size} "
          f"dtype={prec_tag} steps_per_sync={sync_k} "
          f"backend={jax.default_backend()}")
    for i in range(args.warmup):
        t0 = time.perf_counter()
        sync(run())
        _WARMUP_S.observe(time.perf_counter() - t0, model=args.model,
                          mode=args.mode)
    times = []
    for i in range(args.iterations):
        t0 = time.perf_counter()
        with telemetry.span("tools/perf_iteration", i=i):
            sync(run())
        dt = time.perf_counter() - t0
        _ITER_S.observe(dt, model=args.model, mode=args.mode)
        times.append(dt)
        unit = "tok/s" if is_lm else "img/s"
        rate = recs_per_iter / dt
        print(f"iter {i}: {dt*1000:.1f} ms  {rate:.1f} {unit}")
    med = float(np.median(times))
    rate = recs_per_iter / med
    line = (f"median: {med*1000:.1f} ms  {rate:.1f} "
            f"{'tok/s' if is_lm else 'img/s'}")
    # analytic MFU vs an ASSUMED device peak (BIGDL_DEVICE_TFS, default
    # the v5e's, whatever the device — ROADMAP A1/C9) from the one compiled
    # program, through the shared telemetry.programs API — the same
    # math ceiling/bench consume, plus the HBM footprint the cost line
    # alone never showed
    import os
    program_fields = {}
    # one label serves the program profile AND the JSON tail, so the
    # two can never disagree: "pallas" only on trace EVIDENCE (a
    # dispatch actually taken while this process traced — a model
    # with no kernel-eligible ops stays honest), "reference" for the
    # forced-off leg, unset otherwise
    kern_label = None
    if args.kernels == "off":
        kern_label = "reference"
    elif args.kernels == "on":
        from bigdl_tpu.kernels.dispatch import taken_in_thread
        kern_label = "pallas" if taken_in_thread() > 0 else None
    if compiled_for_cost is not None:
        from bigdl_tpu.telemetry import programs
        prog_name = f"perf/{args.model}/{args.mode}"
        prof = programs.registry().register(
            prog_name, "train" if args.mode == "train" else "serving",
            compiled=compiled_for_cost, scan_length=sync_k,
            items_per_call=recs_per_iter, kernel=kern_label)
        rated = programs.registry().record_rate(prog_name,
                                                recs_per_iter / med)
        if rated is not None and rated.achieved_tfs is not None:
            line += (f"  |  {rated.achieved_tfs:.2f} TF/s analytic, "
                     f"MFU {100 * rated.mfu:.1f}% of "
                     f"{programs.DEVICE_TFS:.0f} TF/s peak")
            program_fields = {"achieved_tfs": rated.achieved_tfs,
                              "mfu_vs_peak": rated.mfu}
        else:
            line += "  |  cost-analysis unavailable on this backend"
        if prof.hbm_bytes:
            program_fields["program_hbm_bytes"] = int(prof.hbm_bytes)
            program_fields["program_flops_per_call"] = prof.flops
    print(line)

    # machine-readable JSON tail (the driver's scoreboard hook): the
    # run's steps/sec at its window size, plus the K=1-vs-K=8 dispatch
    # comparison when requested
    from bigdl_tpu import kernels as _kernels_tail
    tail = {"tool": "perf", "model": args.model, "mode": args.mode,
            "batch_size": args.batch_size, "dtype": prec_tag,
            "backend": jax.default_backend(), "median_s": med,
            "rate": rate, "steps_per_sync": sync_k,
            "kernels": ("on" if _kernels_tail.get_config().any_enabled
                        else "off"),
            "kernel_label": kern_label}
    if args.config is not None:
        tail["tuned_config"] = args.config
        tail["tuned_applied"] = tuned_applied
    tail.update(zero_meta)
    tail.update(program_fields)
    if args.mode == "train":
        tail["steps_per_sec"] = sync_k / med
        if args.sync_compare:
            from bigdl_tpu.tools.sync_compare import measure_sync_compare
            carry2 = carry if sync_k > 1 else (params, opt_state, mstate)

            def build(k):
                # the main loop's compiled window is the same program
                # when k matches — reuse it instead of recompiling
                # (sync_k == 1 ran the plain per-step path: no chunk)
                return chunk if sync_k > 1 and k == sync_k \
                    else make_chunk(k)

            rates, carry2 = measure_sync_compare(
                build, carry2,
                lambda k, i: jax.random.split(
                    jax.random.fold_in(key, 100 * k + i + 1), k),
                total=max(8, args.iterations))
            tail.update(rates)
    import json
    print(json.dumps(tail))

    jsonl = args.metrics_jsonl or os.environ.get("BIGDL_METRICS_JSONL")
    if jsonl:
        telemetry.snapshot_to_jsonl(jsonl, meta=tail)
        print(f"# metrics snapshot appended to {jsonl}")


if __name__ == "__main__":
    from bigdl_tpu.utils.engine import enable_compile_cache

    enable_compile_cache()
    main()
