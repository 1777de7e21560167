"""Benchmark harness (reference: models/utils/DistriOptimizerPerf.scala:38 —
synthetic-data throughput for the zoo models).

Runs ResNet-50 ImageNet *training* steps (fwd+bwd+SGD update, the BASELINE
north-star config) on the available accelerator with synthetic data and
prints ONE JSON line:

    {"metric": ..., "value": imgs/sec, "unit": "images/sec", "vs_baseline": r}

Each timed call scans BENCH_SCAN full training steps on-device (params,
optimizer state and BN statistics threaded step to step, a fresh random
batch generated per step) so the measurement is pure device throughput, not
per-dispatch host round-trips. Set BENCH_SCAN=1 for the old
one-step-per-dispatch behavior.

Baseline: the reference publishes no absolute numbers (BASELINE.md); the
working Xeon baseline recorded there is 56 img/s/node (BigDL-paper-era
dual-socket Xeon ResNet-50 estimate) until a measured value replaces it.
"""
import functools
import json
import os
import time

# BASELINE.md "working baseline" — see §North star.
REFERENCE_BASELINE_IMGS_PER_SEC = 56.0

# The JSON line's schema version, checked by the regression sentinel
# (python -m bigdl_tpu.tools.regress): bump it whenever a tracked key
# is RENAMED or changes meaning (adding keys is compatible — the
# sentinel reports unknown-to-it keys as "new" and ignores config
# echo). Version 2 = the documented stable key set: "metric"/"value"/
# "unit"/"vs_baseline" plus the optional per-row keys (steps_per_sync,
# *_per_sec*, *_ms_p*, PROGRAMS' programs_*_mfu/_hbm_bytes, ...).
BENCH_SCHEMA_VERSION = 2


def _maybe_metrics_snapshot(result):
    """One flag, default off (BIGDL_METRICS_JSONL=path): append a
    telemetry snapshot — any phase instruments the run populated plus
    this result as meta — so BENCH trajectories carry breakdowns, not
    just the headline number."""
    jsonl = os.environ.get("BIGDL_METRICS_JSONL")
    if jsonl:
        import bigdl_tpu.telemetry as telemetry
        telemetry.snapshot_to_jsonl(jsonl, meta=dict(result, tool="bench"))


def _build_decoded_pool(default_n: int = 256):
    """Synthesize ImageNet-shaped JPEGs (375x500 q90), decode + scale
    shorter side to 256 + center-crop — the decode-once cost real
    training pays on its first epoch. Returns (pool u8 [N,3,256,256],
    labels, decode_imgs_per_sec)."""
    import io

    import numpy as np
    from PIL import Image

    from bigdl_tpu.dataset.imagenet import decode_image

    pool_n = int(os.environ.get("BENCH_FED_POOL", default_n))
    rng = np.random.RandomState(0)
    t0 = time.time()
    pool = np.empty((pool_n, 3, 256, 256), np.uint8)
    for i in range(pool_n):
        arr = rng.randint(0, 255, (375, 500, 3), np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=90)
        img = decode_image(buf.getvalue(), scale=256)
        h, w = img.shape[:2]
        oy, ox = (h - 256) // 2, (w - 256) // 2
        pool[i] = img[oy:oy + 256, ox:ox + 256].transpose(2, 0, 1)
    decode_rate = pool_n / (time.time() - t0)
    labels = rng.randint(1, 1001, pool_n).astype(np.float32)
    return pool, labels, decode_rate


def _fed_minibatch_chunks(batch, scan):
    """Real-input feed: decode JPEGs once into a RAM cache (the reference
    caches *decoded* ImageNet in BlockManager memory across epochs —
    DataSet.scala CachedDistriDataSet:240), then augment per step with the
    native C++ loader (random crop+flip+normalize) and stage stacked
    scan-chunks to device while the previous chunk computes.

    Yields MiniBatch(xs[scan,B,3,224,224] uint8, ys[scan,B]) already on
    device; normalization runs on device where it fuses into the first
    conv (uint8 crosses the host->device link at 1/4 the float32 bytes).
    """
    from bigdl_tpu.dataset import native_available
    from bigdl_tpu.dataset.sample import MiniBatch

    if not native_available():
        raise RuntimeError("fed bench needs the native loader")
    from bigdl_tpu.native import NativeBatchLoaderU8

    pool, labels, decode_rate = _build_decoded_pool()

    loader = NativeBatchLoaderU8(
        pool, labels, batch, crop=(224, 224), pad=0, flip=True,
        num_threads=int(os.environ.get("BENCH_FED_THREADS",
                                       os.cpu_count() or 2)),
        prefetch=4)

    # Strictly serial, PIECEWISE staging:
    #  - transfer and compute alternate on one thread (where the link
    #    overlaps them, use dataset.prefetch.device_prefetch instead);
    #  - each batch is transferred separately and the scan chunk is
    #    stacked ON DEVICE, instead of one big device_put.
    # Whether either still pays on today's hosts is ROADMAP A10.
    import jax

    def chunks():
        while True:
            bs = [loader.next_batch() for _ in range(scan)]
            xs = [jax.device_put(b[0]) for b in bs]
            ys = [jax.device_put(b[1]) for b in bs]
            for a in xs:
                a.block_until_ready()
            for a in ys:
                a.block_until_ready()
            yield MiniBatch(xs, ys)

    return chunks(), loader, decode_rate


def _row_enabled(flag_name: str, platform: str) -> bool:
    """One gate for every optional bench row: the env flag "0" disables
    it everywhere, "1" forces it on, and otherwise it runs only off-CPU
    (on CPU smoke runs the extra compiles would dominate CI)."""
    flag = os.environ.get(flag_name, "")
    return flag != "0" and (platform != "cpu" or flag == "1")


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    batch = int(os.environ.get("BENCH_BATCH", 256))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    warmup = int(os.environ.get("BENCH_WARMUP", 1))
    scan = int(os.environ.get("BENCH_SCAN", 8))

    platform = jax.devices()[0].platform
    # every result line names the device it ran on
    device = {"platform": platform,
              "device_kind": jax.devices()[0].device_kind,
              "device_count": len(jax.devices())}
    # bf16 compute on accelerators (TPU-native analogue of the reference's
    # fp16 gradient compression); f32 master params.
    if platform != "cpu":
        Engine.set_compute_dtype(jnp.bfloat16)

    RandomGenerator.set_seed(1)
    model = ResNet(1000, depth=50, dataset="ImageNet").training()
    model.ensure_initialized()
    criterion = nn.CrossEntropyCriterion()
    optim = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4,
                nesterov=True, dampening=0.0)

    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    step = build_train_step(model, criterion, optim)

    mode = os.environ.get("BENCH_MODE", "synthetic")

    if mode == "cached":
        # Device-cached real-input variant: decoded images resident in
        # HBM as uint8, augmentation (random crop+flip+normalize) fused
        # into the jitted step — zero per-step host->device traffic (the
        # TPU-native form of the reference's decoded-image executor cache,
        # DataSet.scala CachedDistriDataSet:240).
        from bigdl_tpu.dataset.device_dataset import DeviceCachedArrayDataSet
        from bigdl_tpu.dataset.imagenet import IMAGENET_MEAN, IMAGENET_STD

        pool, labels, decode_rate = _build_decoded_pool()
        ds = DeviceCachedArrayDataSet(
            pool, labels, batch, crop=(224, 224), flip=True,
            mean=IMAGENET_MEAN, std=IMAGENET_STD)

        def scan_body_cached(carry, key_it):
            params, opt_state, mstate, ep, pos = carry
            kb, kr = jax.random.split(key_it)
            # epoch-exact permutation walk; the (epoch, pos) cursor stays
            # < 2n so it never overflows int32 however long the run
            x, y = ds.batch_fn(kb, epoch=ep, pos=pos)
            params, opt_state, mstate, loss = step(
                params, opt_state, mstate, kr, 0.1, x, y)
            pos = pos + batch
            ep = ep + pos // ds.n
            pos = pos % ds.n
            return (params, opt_state, mstate, ep, pos), loss

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_chunk_cached(carry, keys):
            return lax.scan(scan_body_cached, carry, keys)

        root = jax.random.PRNGKey(0)
        carry = (params, opt_state, mstate, jnp.int32(0), jnp.int32(0))
        for i in range(warmup):
            keys = jax.random.split(jax.random.fold_in(root, i), scan)
            carry, losses = run_chunk_cached(carry, keys)
        if warmup:
            float(losses.sum())
        t0 = time.time()
        for i in range(iters):
            keys = jax.random.split(jax.random.fold_in(root, 1000 + i),
                                    scan)
            carry, losses = run_chunk_cached(carry, keys)
        float(losses.sum())
        dt = time.time() - t0
        imgs_per_sec = batch * scan * iters / dt
        result = {
            "schema_version": BENCH_SCHEMA_VERSION,
            "device": device,
            "metric":
                "resnet50_imagenet_train_devcached_imgs_per_sec_per_chip",
            "value": round(imgs_per_sec, 2),
            "unit": "images/sec",
            "vs_baseline": round(
                imgs_per_sec / REFERENCE_BASELINE_IMGS_PER_SEC, 3),
            "first_epoch_decode_imgs_per_sec_per_core":
                round(decode_rate, 1),
        }
        print(json.dumps(result))
        _maybe_metrics_snapshot(result)
        return

    if mode == "rotate":
        # Shard-rotation variant: the decoded pool is >2x an artificial
        # HBM budget of two shard slots; training runs on the resident
        # shard while the next one streams host->device in cliff-safe
        # pieces between scan-chunks (the composition that makes real
        # ImageNet — ~250 GB decoded vs 128 GB pod HBM — train at
        # near-cached rates; DataSet.scala:470-552's cluster-rate IO).
        from bigdl_tpu.dataset.device_dataset import ShardRotator
        from bigdl_tpu.dataset.imagenet import IMAGENET_MEAN, IMAGENET_STD

        pool, labels, decode_rate = _build_decoded_pool(1024)
        n_shards = int(os.environ.get("BENCH_ROTATE_SHARDS", 4))
        shard = len(pool) // n_shards

        def provider(i):
            return (pool[i * shard:(i + 1) * shard],
                    labels[i * shard:(i + 1) * shard])

        rot = ShardRotator(provider, n_shards, batch, crop=(224, 224),
                           flip=True, mean=IMAGENET_MEAN,
                           std=IMAGENET_STD)
        tmpl = rot.template

        def scan_body_rot(carry, key_it, images, lbls):
            params, opt_state, mstate, ep, pos = carry
            kb, kr = jax.random.split(key_it)
            x, y = tmpl.batch_fn_on(images, lbls, kb, epoch=ep, pos=pos)
            params, opt_state, mstate, loss = step(
                params, opt_state, mstate, kr, 0.1, x, y)
            pos = pos + batch
            ep = ep + pos // tmpl.n
            pos = pos % tmpl.n
            return (params, opt_state, mstate, ep, pos), loss

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_chunk_rot(carry, keys, images, lbls):
            return lax.scan(
                lambda c, k: scan_body_rot(c, k, images, lbls),
                carry, keys)

        # chunks per shard ~= one shard-epoch (>=1)
        per_shard = max(1, shard // (batch * scan))
        root = jax.random.PRNGKey(0)
        carry = (params, opt_state, mstate, jnp.int32(0), jnp.int32(0))
        for i in range(max(warmup, 1)):
            keys = jax.random.split(jax.random.fold_in(root, i), scan)
            carry, losses = run_chunk_rot(carry, keys, rot.images,
                                          rot.labels)
        float(losses.sum())
        t0 = time.time()
        t_end = t0
        done = 0
        i = 0
        while done < iters * scan:
            for _ in range(per_shard):
                keys = jax.random.split(
                    jax.random.fold_in(root, 1000 + i), scan)
                carry, losses = run_chunk_rot(carry, keys, rot.images,
                                              rot.labels)
                float(losses.sum())   # complete compute, THEN transfer
                t_end = time.time()   # clock stops at counted work only
                rot.pump()            # (alternate, never overlap)
                done += scan
                i += 1
                if done >= iters * scan:
                    break
            if done >= iters * scan:
                break  # don't time staging a shard that never trains
            while not rot.staged:
                rot.pump()
            rot.rotate()
        dt = t_end - t0
        imgs_per_sec = batch * done / dt
        result = {
            "schema_version": BENCH_SCHEMA_VERSION,
            "device": device,
            "metric":
                "resnet50_imagenet_train_shardrotate_imgs_per_sec_per_chip",
            "value": round(imgs_per_sec, 2),
            "unit": "images/sec",
            "vs_baseline": round(
                imgs_per_sec / REFERENCE_BASELINE_IMGS_PER_SEC, 3),
            "pool_images": len(pool),
            "hbm_budget_images": 2 * shard,
            "chunk_bytes": rot.chunk_bytes,
            "first_epoch_decode_imgs_per_sec_per_core":
                round(decode_rate, 1),
        }
        print(json.dumps(result))
        _maybe_metrics_snapshot(result)
        return

    if mode == "fed":
        # Real-input variant: host-augmented batches (decoded-image RAM
        # cache + native C++ crop/flip/normalize) staged to device.
        from bigdl_tpu.dataset.imagenet import IMAGENET_MEAN, IMAGENET_STD
        mean = jnp.asarray(IMAGENET_MEAN, jnp.float32).reshape(1, 3, 1, 1)
        std = jnp.asarray(IMAGENET_STD, jnp.float32).reshape(1, 3, 1, 1)

        def scan_body_fed(carry, xy):
            params, opt_state, mstate = carry
            x, y = xy
            # on-device normalize: uint8 -> f32, fused into the first conv
            x = (x.astype(jnp.float32) - mean) / std
            kr = jax.random.PRNGKey(0)
            params, opt_state, mstate, loss = step(
                params, opt_state, mstate, kr, 0.1, x, y)
            return (params, opt_state, mstate), loss

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_chunk_fed(carry, xs, ys):
            # xs/ys arrive as lists of per-batch device arrays (see
            # _fed_minibatch_chunks) — stack on device, then scan
            return lax.scan(scan_body_fed, carry,
                            (jnp.stack(xs), jnp.stack(ys)))

        chunks, loader, decode_rate = _fed_minibatch_chunks(batch, scan)
        try:
            carry = (params, opt_state, mstate)
            for _ in range(warmup):
                b = next(chunks)
                carry, losses = run_chunk_fed(carry, b.input, b.target)
            if warmup:
                float(losses.sum())
            t0 = time.time()
            for _ in range(iters):
                b = next(chunks)
                carry, losses = run_chunk_fed(carry, b.input, b.target)
            float(losses.sum())
            dt = time.time() - t0
        finally:
            loader.close()
        imgs_per_sec = batch * scan * iters / dt
        result = {
            "schema_version": BENCH_SCHEMA_VERSION,
            "device": device,
            "metric": "resnet50_imagenet_train_fed_imgs_per_sec_per_chip",
            "value": round(imgs_per_sec, 2),
            "unit": "images/sec",
            "vs_baseline": round(
                imgs_per_sec / REFERENCE_BASELINE_IMGS_PER_SEC, 3),
            "first_epoch_decode_imgs_per_sec_per_core":
                round(decode_rate, 1),
        }
        print(json.dumps(result))
        _maybe_metrics_snapshot(result)
        return

    def scan_body(carry, key):
        params, opt_state, mstate = carry
        kx, ky, kr = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (batch, 3, 224, 224), jnp.float32)
        y = jax.random.randint(ky, (batch,), 1, 1001).astype(jnp.float32)
        params, opt_state, mstate, loss = step(params, opt_state, mstate,
                                               kr, 0.1, x, y)
        return (params, opt_state, mstate), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    root = jax.random.PRNGKey(0)
    carry = (params, opt_state, mstate)
    for i in range(warmup):
        keys = jax.random.split(jax.random.fold_in(root, i), scan)
        carry, losses = run_chunk(carry, keys)
    if warmup:
        float(losses.sum())  # sync: losses depend on every prior params

    t0 = time.time()
    for i in range(iters):
        keys = jax.random.split(jax.random.fold_in(root, 1000 + i), scan)
        carry, losses = run_chunk(carry, keys)
    float(losses.sum())  # data dependency forces completion of the chain
    dt = time.time() - t0

    imgs_per_sec = batch * scan * iters / dt
    result = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "device": device,
        "metric": "resnet50_imagenet_train_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(imgs_per_sec / REFERENCE_BASELINE_IMGS_PER_SEC,
                             3),
        "steps_per_sync": scan,
    }
    # steps/sec at K=1 vs K=8 fused windows: quantifies what bounded
    # async dispatch buys over per-step host sync (the Optimizer's
    # set_steps_per_sync knob). Skipped on CPU smoke runs unless forced
    # — two extra compiles would dominate CI.
    if _row_enabled("BENCH_SYNC_COMPARE", platform):
        from bigdl_tpu.tools.sync_compare import measure_sync_compare

        def build(k):
            if k == scan:
                return run_chunk  # identical program: reuse, no recompile

            @functools.partial(jax.jit, donate_argnums=(0,))
            def chunk_k(c, keys):
                return lax.scan(scan_body, c, keys)
            return chunk_k

        rates, carry = measure_sync_compare(
            build, carry,
            lambda k, i: jax.random.split(
                jax.random.fold_in(root, 7000 + 100 * k + i + 1), k),
            total=max(8, int(os.environ.get("BENCH_SYNC_STEPS", 16))))
        result.update({name: round(r, 3) for name, r in rates.items()})
    # second tracked metric: TransformerLM training tokens/s (the
    # net-new flagship family; a regression here must be visible to the
    # driver's scoreboard, not just ResNet-50). Skipped on CPU smoke
    # runs unless forced — the compile alone would dominate CI.
    if _row_enabled("BENCH_LM", platform):
        result["transformerlm_tokens_per_sec_per_chip"] = round(
            _bench_transformer_lm(), 1)
    # third tracked scalar: forward-only (serving) throughput — the
    # reference's Predictor half of the product (Predictor.scala:35)
    if _row_enabled("BENCH_INFER", platform):
        # the original params buffers were DONATED to the train chunk;
        # the live values ride the final carry
        result["resnet50_inference_imgs_per_sec_per_chip"] = round(
            _bench_inference(model, carry[0], carry[2], batch), 1)
    # fourth tracked row: GENERATION — TransformerLM autoregressive
    # serving through the KV-cache decode engine (tokens/sec plus
    # TTFT / per-token latency percentiles from the service's own
    # histograms). Skipped on CPU smoke runs unless forced — the 2K
    # program warmup would dominate CI.
    if _row_enabled("BENCH_GEN", platform):
        result.update(_bench_generation())
    # fifth tracked row: DATA — the streaming data plane
    # (bigdl_tpu.datapipe). Host-feed (reader -> shuffle -> staged
    # [K,B,...] windows) vs device-feed steps/sec at K=8 for LeNet — the
    # ROADMAP "within ~10% of device-feed" number — and TransformerLM
    # packed-vs-padded tokens/sec with the padding-efficiency gauge
    # values. Skipped on CPU smoke runs unless forced.
    if _row_enabled("BENCH_DATA", platform):
        result.update(_bench_data())
    # sixth tracked row: ZERO — weight-update sharding
    # (bigdl_tpu.parallel.zero). Stage 0 vs 2 vs 3 imgs/sec at K=8
    # scanned windows over a data mesh of all devices, plus
    # opt_state_bytes_per_chip per stage — the n-fold memory reduction
    # and its throughput cost/benefit as scoreboard numbers. Skipped on
    # CPU smoke runs unless forced.
    if _row_enabled("BENCH_ZERO", platform):
        result.update(_bench_zero())
    # seventh tracked row: PRECISION — mixed precision as a policy
    # (bigdl_tpu.precision). ResNet f32 vs bf16_mixed train imgs/sec at
    # K scanned steps, TransformerLM tokens/sec both regimes, and f32
    # vs calibrated-int8 serving imgs/sec with the accuracy delta the
    # serving gate would enforce. Skipped on CPU smoke runs unless
    # forced — bf16 emulates (slowly) on CPU, so the CPU number reports
    # the measured delta, not a win.
    if _row_enabled("BENCH_PRECISION", platform):
        result.update(_bench_precision())
    # eighth tracked row: PROGRAMS — per-model device-side program
    # profiles (bigdl_tpu.telemetry.programs): analytic MFU + HBM
    # bytes + compile time for the resnet50 train window and the
    # eval forward, from XLA's own cost/memory analysis combined with
    # the rates this run just measured. The regression sentinel
    # (tools/regress) tracks these keys. Skipped on CPU smoke runs
    # unless forced — each profile pays one extra AOT compile.
    if _row_enabled("BENCH_PROGRAMS", platform):
        result.update(_bench_programs(
            model, run_chunk, carry,
            jax.random.split(jax.random.fold_in(root, 999), scan),
            batch, scan, imgs_per_sec,
            result.get("resnet50_inference_imgs_per_sec_per_chip")))
    # ninth tracked row: KERNELS — the pallas kernel layer
    # (bigdl_tpu.kernels): attention-program MFU with the flash kernel
    # vs the einsum reference (both registered under kernel= labels in
    # telemetry.programs, the PR-10 gauges as the success metric) and
    # generation decode tokens/sec with the ragged kernel on vs off.
    # Skipped on CPU smoke runs unless forced — the on-leg runs the
    # pallas interpreter.
    if _row_enabled("BENCH_KERNELS", platform):
        result.update(_bench_kernels())
    # tenth tracked row: ELASTIC — preemption-tolerant checkpointing
    # (bigdl_tpu.elastic): the per-checkpoint step-loop stall with the
    # sync (gather + inline write) vs async (snapshot-only) writers,
    # the hidden async write tail, and resume-to-first-step seconds
    # from a committed format-3 checkpoint. Skipped on CPU smoke runs
    # unless forced.
    if _row_enabled("BENCH_ELASTIC", platform):
        result.update(_bench_elastic())
    # eleventh tracked row: FLEET — planet-scale generation serving
    # (bigdl_tpu.fleet): goodput-under-load (tokens/sec at a fixed p99
    # TTFT budget) for 1 vs N replicas behind the router, prefix-cache
    # full-hit TTFT p50 vs the cold prefill p50, and speculative
    # decoding accepted-token rate + tokens/sec on vs off. Skipped on
    # CPU smoke runs unless forced — per-replica warmup compiles
    # dominate CI.
    if _row_enabled("BENCH_FLEET", platform):
        result.update(_bench_fleet())
    # twelfth tracked row: TUNED — the profile-guided autotuner
    # (bigdl_tpu.autotune): one prune-then-measure sweep over the
    # bounded smoke spaces, reporting the tuned winner's steps/sec and
    # decode tokens/sec against the hand-picked default config measured
    # in the SAME sweep (same seed, same windows — the speedup is the
    # autotuner's earned win, not run-to-run noise). Skipped on CPU
    # smoke runs unless forced.
    if _row_enabled("BENCH_TUNED", platform):
        result.update(_bench_tuned())
    # thirteenth tracked row: SLO — the fleet observability plane end
    # to end (telemetry.agg + telemetry.slo): a fleet soak with
    # per-replica PRIVATE registries, merged through
    # aggregate_snapshots, goodput + p99 TTFT read from the MERGED
    # snapshot and judged by one declarative SloSpec. Tracked so a
    # regression in the merge/SLO path (or in fleet goodput itself)
    # trips tools/regress like any perf number. Skipped on CPU smoke
    # runs unless forced.
    if _row_enabled("BENCH_SLO", platform):
        result.update(_bench_slo())
    # fourteenth tracked row: LONGCTX — long-context attention and
    # serving (the blockwise flash kernel past the VMEM budget +
    # chunked prefill): per-S train-step tokens/sec and MFU with the
    # blockwise kernel vs the einsum/bundled-flash fallback, and
    # chunked-prefill TTFT both ways. The fallback legs stop at
    # BENCH_LONGCTX_EINSUM_MAX (default 32K) — past it the O(S^2)
    # reference cannot run at all, which is the row's point. Skipped
    # on CPU smoke runs unless forced.
    if _row_enabled("BENCH_LONGCTX", platform):
        result.update(_bench_longctx())
    # fifteenth tracked row: CONTROL — the SLO-driven control plane
    # under a load ramp (chaos --control leg, faults off): goodput and
    # p99 TTFT while replicas scale 1->N->1, scale-up reaction time,
    # and per-tenant shed fractions. Tracked so a regression in the
    # autoscaler/admission path trips tools/regress like any perf
    # number. Skipped on CPU smoke runs unless forced.
    if _row_enabled("BENCH_CONTROL", platform):
        result.update(_bench_control())
    print(json.dumps(result))
    _maybe_metrics_snapshot(result)


def _bench_inference(model, params, mstate, batch):
    """Eval-mode forward-only ResNet-50 throughput under one scanned
    dispatch (the device serving rate, with no per-batch host feed
    in it)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    scan = int(os.environ.get("BENCH_SCAN", 8))
    iters = int(os.environ.get("BENCH_ITERS", 6))

    def scan_body(carry, key):
        x = jax.random.uniform(key, (batch, 3, 224, 224), jnp.float32)
        out, _ = model.apply(params, mstate, x, training=False)
        # carry a scalar data dependency so the chain cannot be elided
        return carry + out[0, 0].astype(jnp.float32), None

    @jax.jit
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    root = jax.random.PRNGKey(7)
    carry = jnp.zeros((), jnp.float32)
    carry, _ = run_chunk(carry, jax.random.split(root, scan))
    float(carry)
    t0 = time.time()
    for i in range(iters):
        carry, _ = run_chunk(carry, jax.random.split(
            jax.random.fold_in(root, i), scan))
    float(carry)
    return batch * scan * iters / (time.time() - t0)


def _bench_generation():
    """TransformerLM generation serving: a burst of seeded ragged
    prompts through the bucketed KV-cache decode engine with
    continuous batching (``bigdl_tpu.generation``). Returns the
    GENERATION row: tokens/sec/chip plus p50/p99 time-to-first-token
    and p50/p99 per-token latency, read from the GenerationService's
    own telemetry histograms so the scoreboard and the service agree
    by construction."""
    import numpy as np

    from bigdl_tpu.generation import GenerationConfig, GenerationService
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.tools.synthetic import seeded_rng
    from bigdl_tpu.utils.random import RandomGenerator

    vocab = int(os.environ.get("BENCH_GEN_VOCAB", 8192))
    hidden = int(os.environ.get("BENCH_GEN_HIDDEN", 512))
    layers = int(os.environ.get("BENCH_GEN_LAYERS", 6))
    max_len = int(os.environ.get("BENCH_GEN_LEN", 512))
    slots = int(os.environ.get("BENCH_GEN_SLOTS", 16))
    n_reqs = int(os.environ.get("BENCH_GEN_REQS", 32))
    max_new = int(os.environ.get("BENCH_GEN_NEW", 32))

    RandomGenerator.set_seed(11)
    model = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                          num_layers=layers, num_heads=8,
                          max_len=max_len).evaluate()
    model.ensure_initialized()
    svc = GenerationService(config=GenerationConfig(
        slots=slots, max_len=max_len, prefill_rows=min(4, slots),
        max_queue=max(n_reqs, 256)))
    svc.load("lm", model)  # warmup: compiles stay out of the timing

    r = seeded_rng(12)
    prompts = [r.randint(1, vocab, r.randint(4, max_len - max_new))
               .astype(np.int32) for _ in range(n_reqs)]
    t0 = time.time()
    streams = [svc.generate("lm", p, max_new_tokens=max_new)
               for p in prompts]
    total = sum(len(s.result()) for s in streams)
    dt = time.time() - t0
    m = svc.metrics("lm")
    svc.shutdown()
    row = {
        "transformerlm_generation_tokens_per_sec_per_chip":
            round(total / dt, 1),
        "generation_requests": n_reqs,
        "generation_compiles": int(m["compile_count"]),
    }
    for key in ("ttft_ms_p50", "ttft_ms_p99",
                "token_ms_p50", "token_ms_p99"):
        if key in m:
            row[f"generation_{key}"] = round(float(m[key]), 3)
    return row


def _bench_fleet():
    """FLEET row: the planet-scale serving numbers (bigdl_tpu.fleet).

    Leg 1 — goodput under load: the same seeded burst through a
    1-replica and an N-replica router; goodput = tokens/sec times the
    fraction of ACCEPTED requests meeting the p99 TTFT budget (shed
    requests failed fast and typed — that is the router working).
    Leg 2 — prefix/KV reuse: one service with the prefix cache on,
    the same prompts twice; cold p50 TTFT pays the prefill, hit p50
    pays one seed-copy + decode step (the acceptance bound: hit p50
    within 2x the decode-step p50).  Leg 3 — speculative decoding:
    the same prompts through target-only generation vs the
    draft-propose/target-verify decoder; accepted-token rate decides
    whether the draft pays for itself."""
    import numpy as np

    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu.fleet import (FleetRouter, SpeculativeConfig,
                                 SpeculativeDecoder, build_replicas,
                                 run_fleet_soak)
    from bigdl_tpu.generation import GenerationConfig, GenerationService
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.tools.synthetic import seeded_rng
    from bigdl_tpu.utils.random import RandomGenerator

    vocab = int(os.environ.get("BENCH_FLEET_VOCAB", 1024))
    hidden = int(os.environ.get("BENCH_FLEET_HIDDEN", 128))
    layers = int(os.environ.get("BENCH_FLEET_LAYERS", 2))
    heads = int(os.environ.get("BENCH_FLEET_HEADS", 4))
    max_len = int(os.environ.get("BENCH_FLEET_LEN", 64))
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", 4))
    n_replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", 2))
    n_reqs = int(os.environ.get("BENCH_FLEET_REQS", 24))
    max_new = int(os.environ.get("BENCH_FLEET_NEW", 8))
    budget_ms = float(os.environ.get("BENCH_FLEET_TTFT_BUDGET_MS",
                                     2000.0))
    row = {"fleet_replicas": n_replicas,
           "fleet_ttft_budget_ms": budget_ms}

    # -- leg 1: goodput under load, 1 vs N replicas -------------------
    for tag, n in (("1r", 1), ("nr", n_replicas)):
        router = FleetRouter(build_replicas(
            n, seed=21, vocab=vocab, hidden=hidden, layers=layers,
            heads=heads, slots=slots, max_len=max_len, max_queue=8,
            metrics=telemetry.MetricsRegistry()))
        rep = run_fleet_soak(router=router, requests=n_reqs,
                             threads=4, max_new=max_new,
                             prompt_len=max_len // 4, seed=22,
                             open_breaker_on=None,
                             ttft_budget_ms=budget_ms,
                             token_budget_ms=budget_ms)
        router.shutdown()
        row[f"fleet_goodput_tokens_per_sec_{tag}"] = round(
            rep["tokens_per_sec"]
            * rep["ttft_within_budget_fraction"], 2)
        row[f"fleet_ttft_ms_p99_{tag}"] = rep.get("ttft_ms_p99", 0.0)
    if row["fleet_goodput_tokens_per_sec_1r"]:
        row["fleet_goodput_scaling"] = round(
            row["fleet_goodput_tokens_per_sec_nr"]
            / row["fleet_goodput_tokens_per_sec_1r"], 3)

    # -- leg 2: prefix-cache hit vs cold TTFT -------------------------
    RandomGenerator.set_seed(23)
    model = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                          num_layers=layers, num_heads=heads,
                          max_len=max_len).evaluate()
    model.ensure_initialized()
    svc = GenerationService(config=GenerationConfig(
        slots=slots, max_len=max_len, prefill_rows=min(2, slots),
        # this row MEASURES the prefix cache, so the cache size is part
        # of the experiment, not a tunable
        prefix_cache_bytes=256 << 20))  # bigdl: disable=hardcoded-tuned-constant
    svc.load("lm", model)
    r = seeded_rng(24)
    prompts = [r.randint(1, vocab, max_len - max_new - 1)
               .astype(np.int32) for _ in range(8)]
    cold_ttft, hit_ttft = [], []
    for leg in (cold_ttft, hit_ttft):
        for p in prompts:
            s = svc.generate("lm", p, max_new_tokens=max_new)
            s.result(120)
            leg.append(s.ttft_ms)
    m = svc.metrics("lm")
    assert m["prefix_hits"] >= len(prompts), m
    svc.shutdown()
    row.update({
        "fleet_prefix_cold_ttft_ms_p50": round(
            float(np.median(cold_ttft)), 3),
        "fleet_prefix_hit_ttft_ms_p50": round(
            float(np.median(hit_ttft)), 3),
        "fleet_token_ms_p50": round(float(m["token_ms_p50"]), 3),
        "fleet_prefix_ttft_speedup": round(
            float(np.median(cold_ttft) / max(np.median(hit_ttft),
                                             1e-9)), 2),
    })

    # -- leg 3: speculative decoding on vs off ------------------------
    RandomGenerator.set_seed(25)
    draft = TransformerLM(vocab_size=vocab, hidden_size=hidden // 2,
                          num_layers=1, num_heads=heads,
                          max_len=max_len).evaluate()
    draft.ensure_initialized()
    spec_prompts = [r.randint(1, vocab, max_len // 4).astype(np.int32)
                    for _ in range(slots)]
    spec_new = min(max_new, max_len // 2)
    svc_off = GenerationService(config=GenerationConfig(
        slots=slots, max_len=max_len, prefill_rows=min(2, slots)))
    svc_off.load("lm", model)
    t0 = time.time()
    streams = [svc_off.generate("lm", p, max_new_tokens=spec_new)
               for p in spec_prompts]
    off_tokens = sum(len(s.result(120)) for s in streams)
    off_dt = time.time() - t0
    svc_off.shutdown()
    dec = SpeculativeDecoder(model, draft, SpeculativeConfig(
        k=int(os.environ.get("BENCH_FLEET_SPEC_K", 4)), slots=slots,
        max_len=max_len))
    # full-depth warmup: compiles every verify/decode rung the timed
    # run will touch (attend buckets grow with the sequence)
    dec.generate(spec_prompts, spec_new)
    t0 = time.time()
    outs, stats = dec.generate(spec_prompts, spec_new)
    on_dt = time.time() - t0
    row.update({
        "fleet_spec_accept_rate": round(stats["accept_rate"], 4),
        "fleet_spec_tokens_per_sec_off": round(off_tokens / off_dt, 1),
        "fleet_spec_tokens_per_sec_on": round(
            stats["tokens"] / on_dt, 1),
        "fleet_spec_speedup": round(
            (stats["tokens"] / on_dt) / (off_tokens / off_dt), 3),
    })
    return row


def _bench_slo():
    """SLO row: fleet soak goodput + p99 TTFT **from the merged
    cross-process snapshot** (telemetry.agg), judged by one
    declarative SloSpec (telemetry.slo). Each replica serves from its
    own PRIVATE registry — the merge is load-bearing, not cosmetic:
    a broken aggregator shows up here as a zero/missing p99 and
    ``slo_passed`` drops to 0."""
    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu.fleet import (FleetRouter, build_replicas,
                                 run_fleet_soak)
    from bigdl_tpu.telemetry import agg
    from bigdl_tpu.telemetry import slo as slo_mod

    n_replicas = int(os.environ.get("BENCH_SLO_REPLICAS", 2))
    n_reqs = int(os.environ.get("BENCH_SLO_REQS", 24))
    max_new = int(os.environ.get("BENCH_SLO_NEW", 6))
    budget_ms = float(os.environ.get("BENCH_SLO_TTFT_BUDGET_MS",
                                     5000.0))

    # metrics=None -> every replica's GenerationService creates its
    # own registry; the router keeps a separate one of its own
    reps = build_replicas(n_replicas, seed=31, max_queue=8,
                          metrics=None)
    router = FleetRouter(reps, metrics=telemetry.MetricsRegistry())
    try:
        soak = run_fleet_soak(router=router, requests=n_reqs,
                              threads=4, max_new=max_new, seed=32,
                              open_breaker_on=None,
                              ttft_budget_ms=budget_ms)
    finally:
        router.shutdown(drain=True)

    sources = [({"replica": r.name},
                r.service.metrics_registry.snapshot(True))
               for r in reps]
    sources.append(({"replica": "router"},
                    router.metrics_registry.snapshot(True)))
    merged = agg.aggregate_snapshots(sources)
    bad = agg.check_merge_invariant(sources, merged)
    spec = slo_mod.SloSpec.parse(
        f"p99_ttft: serving/generation/ttft_ms.p99 <= {budget_ms};"
        "goodput: goodput_tokens_per_sec >= 0.001")
    rep = slo_mod.evaluate(
        spec, merged,
        {"goodput_tokens_per_sec": soak["goodput_tokens_per_sec"]})
    by = {v.objective.name: v.value for v in rep.verdicts}
    return {
        "slo_goodput_tokens_per_sec": round(
            soak["goodput_tokens_per_sec"], 2),
        "slo_ttft_ms_p99": round(by.get("p99_ttft") or 0.0, 3),
        "slo_passed": int(rep.passed and soak["passed"] and not bad),
    }


def _bench_control():
    """CONTROL row: the chaos ``--control`` load-ramp leg run
    fault-free — goodput and p99 TTFT while the autoscaler takes the
    fleet 1->N->1 under a two-tenant burst, the scale-up reaction
    time, and each tenant's shed fraction. ``control_passed`` drops
    to 0 when the leg's invariants (typed-only sheds, zero hangs,
    ramp reached N, drained back to 1) break.

    Key naming is deliberate for tools/regress's classifier:
    ``*_per_sec`` higher-is-better, ``*_ms`` lower-is-better, and the
    shed fractions use the unclassified ``_frac_`` spelling — a shed
    fraction moving is context, not a regression by itself."""
    from bigdl_tpu.tools.chaos import run_control

    max_replicas = int(os.environ.get("BENCH_CONTROL_REPLICAS", 3))
    leg = run_control(max_replicas=max_replicas, inject=False)
    tenants = leg.get("tenants") or {}
    return {
        "control_goodput_tokens_per_sec": round(
            leg["goodput_tokens_per_sec"], 2),
        "control_ttft_ms_p99": round(
            (leg.get("latency") or {}).get("ramp_ttft_ms_p99")
            or 0.0, 3),
        "control_scaleup_reaction_ms": round(
            leg.get("scaleup_reaction_ms") or 0.0, 1),
        "control_shed_frac_gold": (
            tenants.get("gold") or {}).get("shed_fraction", 0.0),
        "control_shed_frac_bronze": (
            tenants.get("bronze") or {}).get("shed_fraction", 0.0),
        "control_passed": int(leg["passed"]),
    }


def _bench_longctx():
    """LONGCTX row: what the long-context stack buys, as
    sentinel-tracked numbers at S in BENCH_LONGCTX_SEQS (default
    8K/32K/128K).

    Leg 1 — training attention: one fused fwd+bwd causal attention
    step (``jit(value_and_grad)``, so the custom-VJP backward is the
    program measured) per S, blockwise flash kernel on vs the
    einsum/bundled-flash reference, each registered in
    ``telemetry.programs`` with the kernel= label decided by trace
    EVIDENCE — tokens/sec + MFU both ways and the speedup. Past
    ``BENCH_LONGCTX_EINSUM_MAX`` the quadratic reference is not run
    (it cannot fit); the blockwise numbers stand alone, which is the
    row's point. Leg 2 — serving: TTFT of an ~S-token prompt through
    chunked prefill (fixed BENCH_LONGCTX_CHUNK-wide chunks through the
    existing bucket rungs), kernels on vs off under the same chunking,
    with the prefill chunk count and compile count carried so the
    <=2-programs-per-bucket bound stays checkable. On CPU the
    kernel-on legs run the pallas interpreter, so CPU numbers document
    equivalence overhead, not a win — shrink BIGDL_VMEM_BUDGET_MB to
    steer small smoke shapes down the blockwise route."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import kernels
    from bigdl_tpu.generation import GenerationConfig, GenerationService
    from bigdl_tpu.kernels.dispatch import taken_in_thread
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.nn.attention import dot_product_attention
    from bigdl_tpu.telemetry import programs
    from bigdl_tpu.tools.synthetic import seeded_rng
    from bigdl_tpu.utils.random import RandomGenerator

    seqs = [int(s) for s in os.environ.get(
        "BENCH_LONGCTX_SEQS", "8192,32768,131072").split(",")]
    b = int(os.environ.get("BENCH_LONGCTX_BATCH", 1))
    heads = int(os.environ.get("BENCH_LONGCTX_HEADS", 8))
    hd = int(os.environ.get("BENCH_LONGCTX_HEAD_DIM", 64))
    einsum_max = int(os.environ.get("BENCH_LONGCTX_EINSUM_MAX", 32768))
    chunk = int(os.environ.get("BENCH_LONGCTX_CHUNK", 2048))
    vocab = int(os.environ.get("BENCH_LONGCTX_VOCAB", 8192))
    hidden = int(os.environ.get("BENCH_LONGCTX_HIDDEN", 512))
    layers = int(os.environ.get("BENCH_LONGCTX_LAYERS", 2))
    max_new = int(os.environ.get("BENCH_LONGCTX_NEW", 8))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    reg = programs.registry()
    row = {"longctx_einsum_max": einsum_max,
           "longctx_prefill_chunk": chunk}

    def attn_leg(s, tag, cfg):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(41 + s % 97), 3)
        q = jax.random.normal(kq, (b, heads, s, hd), jnp.float32)
        k = jax.random.normal(kk, (b, heads, s, hd), jnp.float32)
        v = jax.random.normal(kv, (b, heads, s, hd), jnp.float32)
        with kernels.use(cfg):
            fn = jax.jit(jax.value_and_grad(
                lambda q_, k_, v_: dot_product_attention(
                    q_, k_, v_, causal=True).sum(), argnums=(0, 1, 2)))
            taken_before = taken_in_thread()
            t0 = time.perf_counter()
            compiled = fn.lower(q, k, v).compile()
            compile_s = time.perf_counter() - t0
            taken = int(taken_in_thread() > taken_before)
            name = f"bench/longctx/s{s}/{tag}"
            reg.register(name, "train", compiled=compiled,
                         compile_s=compile_s, items_per_call=b * s,
                         kernel="pallas" if taken else "reference")
            jax.block_until_ready(compiled(q, k, v))  # warm
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = compiled(q, k, v)
            jax.block_until_ready(out)
            rate = b * s * iters / (time.perf_counter() - t0)
            prof = reg.record_rate(name, rate)
            mfu = prof.mfu if prof is not None else None
            return rate, (mfu or 0.0), taken

    def ttft_leg(s, cfg):
        with kernels.use(cfg):
            RandomGenerator.set_seed(43)
            model = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                                  num_layers=layers, num_heads=heads,
                                  max_len=s).evaluate()
            model.ensure_initialized()
            svc = GenerationService(config=GenerationConfig(
                slots=2, max_len=s, prefill_rows=2,
                prefill_chunk=chunk))
            svc.load("longlm", model)  # warmup compiles off the clock
            r = seeded_rng(44)
            prompt = r.randint(1, vocab, s - max_new).astype(np.int32)
            stream = svc.generate("longlm", prompt,
                                  max_new_tokens=max_new)
            stream.result()
            ttft = stream.ttft_ms
            m = svc.metrics("longlm")
            svc.shutdown()
            return ttft, int(m.get("prefill_chunks", 0)), \
                int(m["compile_count"])

    for s in seqs:
        rate_on, mfu_on, taken = attn_leg(
            s, "blockwise", kernels.KernelConfig.all_on())
        row[f"longctx_s{s}_tokens_per_sec_blockwise"] = round(rate_on, 1)
        row[f"longctx_s{s}_mfu_blockwise"] = round(mfu_on, 4)
        row[f"longctx_s{s}_flash_taken"] = taken
        if s <= einsum_max:
            rate_off, mfu_off, _ = attn_leg(
                s, "einsum", kernels.KernelConfig.off())
            row[f"longctx_s{s}_tokens_per_sec_einsum"] = round(
                rate_off, 1)
            row[f"longctx_s{s}_mfu_einsum"] = round(mfu_off, 4)
            row[f"longctx_s{s}_blockwise_speedup"] = round(
                rate_on / rate_off, 3)
        ttft, chunks, compiles = ttft_leg(s, kernels.KernelConfig.all_on())
        row[f"longctx_s{s}_ttft_ms"] = round(ttft, 3)
        row[f"longctx_s{s}_prefill_chunks"] = chunks
        row[f"longctx_s{s}_generation_compiles"] = compiles
        if s <= einsum_max:
            ttft_ref, _, _ = ttft_leg(s, kernels.KernelConfig.off())
            row[f"longctx_s{s}_ttft_ms_einsum"] = round(ttft_ref, 3)
    return row


def _bench_data():
    """DATA row: how fast the streaming data plane feeds the chip.

    Leg 1 — LeNet at K=8: device-feed (HBM-cached ``batch_fn`` inside
    the scan, the feed ceiling) vs host-feed (datapipe reader ->
    seeded shuffle -> SampleToMiniBatch -> ``[K, B, ...]`` staged
    windows consumed by the same scanned step). Leg 2 — TransformerLM
    on ragged documents: packed slabs (segment masks) vs pad-to-max
    rows through the identical train step; tokens/sec counts REAL
    tokens, so the packed win is the padding it no longer computes.
    """
    import functools

    import jax
    import numpy as np
    from jax import lax

    import bigdl_tpu.nn as nn
    from bigdl_tpu import datapipe as dp
    from bigdl_tpu.dataset.device_dataset import DeviceCachedArrayDataSet
    from bigdl_tpu.models import LeNet5, TransformerLM
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.tools.synthetic import seeded_rng
    from bigdl_tpu.utils.random import RandomGenerator

    k = int(os.environ.get("BENCH_DATA_K", 8))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    batch = int(os.environ.get("BENCH_DATA_BATCH", 128))
    row = {}

    def window_runner(step):
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def run(p, o, m, keys, xs, ys):
            def body(carry, sl):
                p, o, m = carry
                key, x, y = sl
                p, o, m, loss = step(p, o, m, key, 0.05, x, y)
                return (p, o, m), loss
            (p, o, m), losses = lax.scan(body, (p, o, m), (keys, xs, ys))
            return p, o, m, losses
        return run

    rng = seeded_rng(6)
    n_pool = max(4 * batch, 512)
    imgs = rng.rand(n_pool, 1, 28, 28).astype(np.float32)
    labels = (rng.randint(0, 10, n_pool) + 1).astype(np.float32)

    def lenet_setup():
        RandomGenerator.set_seed(5)
        model = LeNet5(10).training()
        model.ensure_initialized()
        optim = SGD(learning_rate=0.05)
        step = build_train_step(model, nn.ClassNLLCriterion(), optim)
        return step, (model.get_parameters(),
                      optim.init_state(model.get_parameters()),
                      model.get_state())

    def lenet_host_leg() -> float:
        step, carry = lenet_setup()
        run = window_runner(step)
        root = jax.random.PRNGKey(2)
        pipe = (dp.Pipeline(dp.ArrayRecordReader(imgs, labels, seed=1))
                .shuffle(buffer_size=4 * batch, seed=2)
                .batch(batch, drop_remainder=True))
        staged = pipe.staged(k=k, loop=True)
        try:
            done = -1  # one warmup window, then `iters` timed ones
            t0 = None
            while done < iters:
                keys = jax.random.split(jax.random.fold_in(root, done + 1), k)
                b = next(staged)
                p, o, m, losses = run(*carry, keys, b.input, b.target)
                carry = (p, o, m)
                float(losses.sum())  # window boundary: the host sync
                done += 1
                if t0 is None:
                    t0 = time.time()
            dt = time.time() - t0
        finally:
            staged.close()
        return k * iters / dt

    def lenet_dev_leg() -> float:
        import jax.numpy as jnp
        step, carry = lenet_setup()
        ds = DeviceCachedArrayDataSet(
            (imgs * 255).astype(np.uint8), labels, batch,
            crop=(28, 28), flip=False, mean=(0.0,), std=(255.0,))
        root = jax.random.PRNGKey(2)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(carry, keys):
            def body(c, key):
                p, o, m, ep, pos = c
                kb, kr = jax.random.split(key)
                x, y = ds.batch_fn(kb, epoch=ep, pos=pos)
                p, o, m, loss = step(p, o, m, kr, 0.05, x, y)
                pos = pos + batch
                return (p, o, m, ep + pos // ds.n, pos % ds.n), loss
            return lax.scan(body, carry, keys)
        carry = carry + (jnp.int32(0), jnp.int32(0))
        done = -1
        t0 = None
        while done < iters:
            keys = jax.random.split(jax.random.fold_in(root, done + 1), k)
            carry, losses = run(carry, keys)
            float(losses.sum())
            done += 1
            if t0 is None:
                t0 = time.time()
        return k * iters / (time.time() - t0)

    dev = lenet_dev_leg()
    host = lenet_host_leg()
    row["data_window_k"] = k
    row["data_lenet_devfeed_steps_per_sec"] = round(dev, 2)
    row["data_lenet_hostfeed_steps_per_sec"] = round(host, 2)
    row["data_hostfeed_fraction_of_devfeed"] = round(host / dev, 3)

    # ---- TransformerLM: packed slabs vs pad-to-max rows ----------------
    vocab = int(os.environ.get("BENCH_DATA_VOCAB", 4096))
    seq = int(os.environ.get("BENCH_DATA_SEQ", 256))
    rows_b = int(os.environ.get("BENCH_DATA_ROWS", 8))
    r2 = seeded_rng(7)
    docs = [r2.randint(1, vocab, int(n)).astype(np.int32)
            for n in r2.randint(8, seq // 2, 256)]
    lengths = [len(d) - 1 for d in docs]
    packed_arrays = dp.pack_documents(docs, seq)  # packed once: the
    # timed leg and the efficiency number must describe the same slabs

    def tlm_leg(packed: bool) -> float:
        RandomGenerator.set_seed(9)
        model = TransformerLM(vocab_size=vocab, hidden_size=256,
                              num_layers=4, num_heads=8,
                              max_len=seq).training()
        model.ensure_initialized()
        optim = SGD(learning_rate=0.1)
        crit = nn.SequenceCrossEntropyCriterion(ignore_index=-1)
        step = build_train_step(model, crit, optim)
        params = model.get_parameters()
        mstate = model.get_state()
        opt_state = optim.init_state(params)
        if packed:
            toks, segs, pos, tgt = packed_arrays
        else:
            packer = dp.LengthBucketBatcher([seq], len(docs))
            (mb,) = list(packer(iter(docs), 0))
            toks, segs, pos = mb.input
            tgt = mb.target
        n_rows = (len(toks) // rows_b) * rows_b
        if n_rows == 0:
            raise ValueError(
                f"BENCH_DATA_ROWS={rows_b} exceeds the {len(toks)} "
                f"{'packed' if packed else 'padded'} rows the corpus "
                "yields; lower BENCH_DATA_ROWS")
        batches = [([toks[i:i + rows_b], segs[i:i + rows_b],
                     pos[i:i + rows_b]], tgt[i:i + rows_b],
                    int((segs[i:i + rows_b] > 0).sum()))
                   for i in range(0, n_rows, rows_b)]
        carry = (params, opt_state, mstate)
        real_tokens = 0
        t0 = None
        for it in range(iters + 1):
            for x, y, n_real in batches:
                p, o, m, loss = step(*carry, RandomGenerator.next_key(),
                                     0.1, x, y)
                carry = (p, o, m)
                if it > 0:
                    real_tokens += n_real
            float(loss)
            if t0 is None:
                t0 = time.time()  # first pass was compile+warmup
        return real_tokens / (time.time() - t0)

    row["data_tlm_packed_tokens_per_sec"] = round(tlm_leg(True), 1)
    row["data_tlm_padded_tokens_per_sec"] = round(tlm_leg(False), 1)
    row["data_padding_efficiency_padded"] = round(
        dp.padding_efficiency(lengths, seq), 4)
    packed_segs = packed_arrays[1]
    row["data_padding_efficiency_packed"] = round(
        float((packed_segs > 0).mean()), 4) if len(packed_segs) else 1.0
    return row


def _bench_zero():
    """ZERO row: ResNet training at ZeRO stage 0 vs 2 vs 3 over a
    data-parallel mesh of every available device, K scanned steps per
    dispatch (the windowed-driver regime where the collectives overlap
    the neighbouring steps' compute). Reports imgs/sec and the per-chip
    optimizer-state bytes each stage leaves resident — the measured
    form of the ZeRO memory math in docs/performance.md."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.parallel import (ZeroConfig, data_parallel_mesh,
                                    place_zero_state, tree_bytes_per_chip)
    from bigdl_tpu.utils.random import RandomGenerator

    scan = int(os.environ.get("BENCH_SCAN", 8))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    mesh = data_parallel_mesh()
    ndev = mesh.shape["data"]
    batch = int(os.environ.get("BENCH_ZERO_BATCH", 16 * ndev))
    batch = max(ndev, batch - batch % ndev)
    depth = int(os.environ.get("BENCH_ZERO_DEPTH", 20))
    repl = NamedSharding(mesh, P())
    bsh = NamedSharding(mesh, P("data"))
    row = {"zero_window_k": scan, "zero_devices": ndev,
           "zero_batch": batch}

    def leg(stage):
        RandomGenerator.set_seed(13)
        model = ResNet(10, depth=depth, dataset="CIFAR10").training()
        model.ensure_initialized()
        optim = SGD(learning_rate=0.1, momentum=0.9)
        cfg = ZeroConfig(stage=stage) if stage else None
        params = model.get_parameters()
        opt_state = optim.init_state(params)
        params, opt_state = place_zero_state(params, opt_state, mesh,
                                             cfg)
        mstate = jax.device_put(model.get_state(), repl)
        step = build_train_step(model, nn.CrossEntropyCriterion(), optim,
                                zero=cfg, mesh=mesh)

        def scan_body(carry, key):
            params, opt_state, mstate = carry
            kx, ky, kr = jax.random.split(key, 3)
            x = jax.lax.with_sharding_constraint(
                jax.random.uniform(kx, (batch, 3, 32, 32), jnp.float32),
                bsh)
            y = jax.lax.with_sharding_constraint(
                jax.random.randint(ky, (batch,), 1, 11)
                .astype(jnp.float32), bsh)
            params, opt_state, mstate, loss = step(
                params, opt_state, mstate, kr, 0.1, x, y)
            return (params, opt_state, mstate), loss

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_chunk(carry, keys):
            return lax.scan(scan_body, carry, keys)

        opt_bytes = tree_bytes_per_chip(opt_state)
        root = jax.random.PRNGKey(3)
        carry = (params, opt_state, mstate)
        carry, losses = run_chunk(carry, jax.random.split(root, scan))
        float(losses.sum())  # compile + warmup outside the clock
        t0 = time.time()
        for i in range(iters):
            carry, losses = run_chunk(
                carry, jax.random.split(jax.random.fold_in(root, i + 1),
                                        scan))
        float(losses.sum())
        return batch * scan * iters / (time.time() - t0), opt_bytes

    for stage in (0, 2, 3):
        rate, opt_bytes = leg(stage)
        row[f"zero_stage{stage}_imgs_per_sec"] = round(rate, 2)
        row[f"zero_stage{stage}_opt_state_bytes_per_chip"] = opt_bytes
    row["zero_opt_state_reduction_stage2"] = round(
        row["zero_stage0_opt_state_bytes_per_chip"]
        / max(1, row["zero_stage2_opt_state_bytes_per_chip"]), 2)
    return row


def _bench_precision():
    """PRECISION row: what the precision policy buys, as scoreboard
    numbers.

    Leg 1 — ResNet training (depth BENCH_PREC_DEPTH; 50 = the ImageNet
    north-star, smoke tests shrink it) under ``f32`` vs ``bf16_mixed``
    at K scanned steps per dispatch: identical program, identical data
    keys, only the policy differs — the ratio is the bf16 win. Leg 2 —
    TransformerLM train tokens/sec under both regimes. Leg 3 — serving:
    f32 forward vs CALIBRATED int8 (activation scales from
    ``precision.calibrate`` over real calibration batches), imgs/sec
    plus the top-1 agreement delta measured by the same ``AccuracyGate``
    the registry's quantized loads enforce — the delta in this row is
    the number the gate would compare against its bound."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import ResNet, TransformerLM
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.precision import AccuracyGate, PrecisionPolicy
    from bigdl_tpu.utils.random import RandomGenerator

    scan = int(os.environ.get("BENCH_SCAN", 8))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    depth = int(os.environ.get("BENCH_PREC_DEPTH", 50))
    batch = int(os.environ.get("BENCH_PREC_BATCH", 64))
    dataset = "ImageNet" if depth >= 50 else "CIFAR10"
    classes = 1000 if depth >= 50 else 10
    hw = 224 if depth >= 50 else 32
    row = {"precision_window_k": scan, "precision_resnet_depth": depth,
           "precision_batch": batch}

    def resnet_leg(policy) -> float:
        RandomGenerator.set_seed(17)
        model = ResNet(classes, depth=depth, dataset=dataset).training()
        model.ensure_initialized()
        optim = SGD(learning_rate=0.1, momentum=0.9)
        params = model.get_parameters()
        opt_state = optim.init_state(params)
        step = build_train_step(model, nn.CrossEntropyCriterion(), optim,
                                precision=policy)

        def scan_body(carry, key):
            params, opt_state, mstate = carry
            kx, ky, kr = jax.random.split(key, 3)
            x = jax.random.uniform(kx, (batch, 3, hw, hw), jnp.float32)
            y = jax.random.randint(ky, (batch,), 1, classes + 1) \
                .astype(jnp.float32)
            params, opt_state, mstate, loss = step(
                params, opt_state, mstate, kr, 0.1, x, y)
            return (params, opt_state, mstate), loss

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_chunk(carry, keys):
            return lax.scan(scan_body, carry, keys)

        root = jax.random.PRNGKey(4)
        carry = (params, opt_state, model.get_state())
        carry, losses = run_chunk(carry, jax.random.split(root, scan))
        float(losses.sum())  # compile + warmup outside the clock
        t0 = time.time()
        for i in range(iters):
            carry, losses = run_chunk(
                carry, jax.random.split(jax.random.fold_in(root, i + 1),
                                        scan))
        float(losses.sum())
        return batch * scan * iters / (time.time() - t0)

    f32 = resnet_leg(PrecisionPolicy.f32())
    bf16 = resnet_leg(PrecisionPolicy.bf16_mixed())
    row["precision_resnet_f32_imgs_per_sec"] = round(f32, 2)
    row["precision_resnet_bf16_imgs_per_sec"] = round(bf16, 2)
    row["precision_resnet_bf16_speedup"] = round(bf16 / f32, 3)

    # ---- TransformerLM tokens/sec, both regimes ------------------------
    vocab = int(os.environ.get("BENCH_PREC_VOCAB", 4096))
    hidden = int(os.environ.get("BENCH_PREC_HIDDEN", 256))
    layers = int(os.environ.get("BENCH_PREC_LAYERS", 4))
    seq = int(os.environ.get("BENCH_PREC_SEQ", 256))
    lm_batch = int(os.environ.get("BENCH_PREC_LM_BATCH", 8))

    def tlm_leg(policy) -> float:
        RandomGenerator.set_seed(19)
        model = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                              num_layers=layers, num_heads=8,
                              max_len=seq).training()
        model.ensure_initialized()
        optim = SGD(learning_rate=0.1)
        crit = nn.SequenceCrossEntropyCriterion(ignore_index=-1)
        step = build_train_step(model, crit, optim, precision=policy)
        params = model.get_parameters()
        opt_state = optim.init_state(params)

        def scan_body(carry, key):
            params, opt_state, mstate = carry
            kx, kr = jax.random.split(key)
            toks = jax.random.randint(kx, (lm_batch, seq), 1, vocab)
            tgt = jnp.roll(toks, -1, axis=1)
            params, opt_state, mstate, loss = step(
                params, opt_state, mstate, kr, 0.1, toks, tgt)
            return (params, opt_state, mstate), loss

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_chunk(carry, keys):
            return lax.scan(scan_body, carry, keys)

        root = jax.random.PRNGKey(5)
        carry = (params, opt_state, model.get_state())
        carry, losses = run_chunk(carry, jax.random.split(root, scan))
        float(losses.sum())
        t0 = time.time()
        for i in range(iters):
            carry, losses = run_chunk(
                carry, jax.random.split(jax.random.fold_in(root, i + 1),
                                        scan))
        float(losses.sum())
        return lm_batch * seq * scan * iters / (time.time() - t0)

    tf32 = tlm_leg(PrecisionPolicy.f32())
    tbf16 = tlm_leg(PrecisionPolicy.bf16_mixed())
    row["precision_tlm_f32_tokens_per_sec"] = round(tf32, 1)
    row["precision_tlm_bf16_tokens_per_sec"] = round(tbf16, 1)
    row["precision_tlm_bf16_speedup"] = round(tbf16 / tf32, 3)

    # ---- serving: f32 vs calibrated int8 -------------------------------
    from bigdl_tpu.nn.quantized import quantize
    from bigdl_tpu.precision.calibrate import collect_activation_scales
    from bigdl_tpu.tools.synthetic import seeded_rng

    RandomGenerator.set_seed(23)
    fmodel = ResNet(classes, depth=depth, dataset=dataset).evaluate()
    fmodel.ensure_initialized()
    r = seeded_rng(24)
    calib = [r.rand(min(batch, 16), 3, hw, hw).astype(np.float32)
             for _ in range(2)]
    scales = collect_activation_scales(fmodel, calib)
    qmodel = quantize(fmodel, act_scales=scales)

    def serve_leg(model) -> float:
        params, mstate = model.get_parameters(), model.get_state()

        def scan_body(carry, key):
            x = jax.random.uniform(key, (batch, 3, hw, hw), jnp.float32)
            out, _ = model.apply(params, mstate, x, training=False)
            return carry + out[0, 0].astype(jnp.float32), None

        @jax.jit
        def run_chunk(carry, keys):
            return lax.scan(scan_body, carry, keys)

        root = jax.random.PRNGKey(6)
        carry = jnp.zeros((), jnp.float32)
        carry, _ = run_chunk(carry, jax.random.split(root, scan))
        float(carry)
        t0 = time.time()
        for i in range(iters):
            carry, _ = run_chunk(carry, jax.random.split(
                jax.random.fold_in(root, i + 1), scan))
        float(carry)
        return batch * scan * iters / (time.time() - t0)

    sf32 = serve_leg(fmodel)
    sint8 = serve_leg(qmodel)
    # the SAME gate the registry's quantized loads enforce; agreement
    # mode (no labels) — delta is the top-1 disagreement rate
    gate = AccuracyGate(
        inputs=r.rand(int(os.environ.get("BENCH_PREC_GATE_N", 64)),
                      3, hw, hw).astype(np.float32),
        max_delta=float(os.environ.get("BENCH_PREC_GATE", 0.02)))
    delta = gate.evaluate(fmodel, qmodel)
    row["precision_serving_f32_imgs_per_sec"] = round(sf32, 2)
    row["precision_serving_int8_imgs_per_sec"] = round(sint8, 2)
    row["precision_serving_int8_speedup"] = round(sint8 / sf32, 3)
    row["precision_int8_accuracy_delta"] = round(delta, 4)
    row["precision_int8_gate_max_delta"] = gate.max_delta
    return row


def _bench_programs(model, run_chunk, carry, keys, batch, scan,
                    train_rate, infer_rate):
    """PROGRAMS row: register the resnet50 train window (and eval
    forward) in the program-profile registry and combine the analytic
    FLOPs/HBM numbers with the rates the earlier rows measured —
    per-model MFU + HBM bytes as sentinel-tracked scoreboard keys."""
    import time as _time

    import jax

    from bigdl_tpu.optim.optimizer import build_eval_step
    from bigdl_tpu.telemetry import programs

    reg = programs.registry()
    row = {}

    t0 = _time.perf_counter()
    compiled = run_chunk.lower(carry, keys).compile()
    compile_s = _time.perf_counter() - t0
    reg.register("bench/resnet50/train_window", "train",
                 compiled=compiled, compile_s=compile_s,
                 scan_length=scan, items_per_call=batch * scan,
                 donation="carry")
    prof = reg.record_rate("bench/resnet50/train_window", train_rate)
    row["programs_resnet50_train_hbm_bytes"] = int(prof.hbm_bytes)
    row["programs_resnet50_train_flops_per_img"] = round(
        prof.flops / (batch * scan), 1)
    row["programs_resnet50_train_compile_s"] = round(compile_s, 3)
    if prof.mfu is not None:
        row["programs_resnet50_train_mfu"] = round(prof.mfu, 4)
        row["programs_resnet50_train_achieved_tfs"] = round(
            prof.achieved_tfs, 3)

    # eval forward at the same batch (params/state ride the final carry
    # — the originals were donated into the train chunk)
    eval_step = build_eval_step(model)
    x = jax.numpy.zeros((batch, 3, 224, 224), jax.numpy.float32)
    t0 = _time.perf_counter()
    compiled = eval_step.lower(carry[0], carry[2], x).compile()
    compile_s = _time.perf_counter() - t0
    reg.register("bench/resnet50/eval", "train", compiled=compiled,
                 compile_s=compile_s, items_per_call=batch)
    row["programs_resnet50_eval_hbm_bytes"] = int(
        reg.get("bench/resnet50/eval").hbm_bytes)
    if infer_rate:
        prof = reg.record_rate("bench/resnet50/eval", infer_rate)
        if prof is not None and prof.mfu is not None:
            row["programs_resnet50_eval_mfu"] = round(prof.mfu, 4)
    return row


def _bench_elastic():
    """ELASTIC row: what async per-shard checkpointing buys, as
    sentinel-tracked numbers. Leg 1 trains the seeded chaos workload
    with SYNC (gather + inline write) checkpoints and reads the mean
    ``train/checkpoint/save_s`` stall; leg 2 repeats it with the
    ASYNC format-3 writer — the stall shrinks to the snapshot copy
    and the hidden tail lands in ``train/checkpoint/async_write_s``;
    leg 3 times a fresh Optimizer resuming from the committed elastic
    checkpoint to its first completed step (load + cross-layout
    reshard + compile + one step: the number a preempted pod pays
    before training again)."""
    import shutil
    import tempfile
    import time

    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.tools.chaos import _build_workload

    steps = int(os.environ.get("BENCH_ELASTIC_STEPS", 8))
    every = int(os.environ.get("BENCH_ELASTIC_EVERY", 2))
    save_h = telemetry.histogram("train/checkpoint/save_s")
    async_h = telemetry.histogram("train/checkpoint/async_write_s")
    workdir = tempfile.mkdtemp(prefix="bench-elastic-")

    def leg(ckpt, async_write, extra_steps=0):
        model, ds, crit = _build_workload("tiny", 42, 8)
        opt = Optimizer(model, ds, crit, batch_size=8)
        opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9))
        opt.set_end_when(max_iteration(steps + extra_steps))
        opt.set_checkpoint(ckpt, several_iteration(every),
                           async_write=async_write)
        opt.optimize()

    row = {}
    try:
        c0, s0 = save_h.count(), save_h.sum()
        leg(os.path.join(workdir, "sync"), False)
        c1, s1 = save_h.count(), save_h.sum()
        row["elastic_ckpt_stall_ms_sync"] = round(
            (s1 - s0) / max(1, c1 - c0) * 1000.0, 3)

        a0, t0 = async_h.count(), async_h.sum()
        leg(os.path.join(workdir, "async"), True)
        c2, s2 = save_h.count(), save_h.sum()
        a1, t1 = async_h.count(), async_h.sum()
        row["elastic_ckpt_stall_ms_async"] = round(
            (s2 - s1) / max(1, c2 - c1) * 1000.0, 3)
        row["elastic_ckpt_async_write_ms"] = round(
            (t1 - t0) / max(1, a1 - a0) * 1000.0, 3)

        w0 = time.time()
        leg(os.path.join(workdir, "async"), True, extra_steps=1)
        row["elastic_resume_to_first_step_s"] = round(time.time() - w0,
                                                      3)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return row


def _bench_kernels():
    """KERNELS row: what the pallas kernel layer buys, as
    sentinel-tracked numbers. Leg 1 registers the SAME causal
    attention forward twice in ``telemetry.programs`` — flash kernel
    on (``kernel=pallas``) vs einsum reference (``kernel=reference``)
    — and reports each program's measured rate and MFU, so the gauges
    and the scoreboard agree by construction. Leg 2 runs the same
    seeded generation burst through two fresh GenerationServices,
    ragged decode kernel on vs off, and reports decode tokens/sec both
    ways plus the speedup. (On CPU the on-legs run the pallas
    interpreter, so the CPU numbers document equivalence overhead, not
    a win — the TPU trajectory is the one the sentinel gates.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import kernels
    from bigdl_tpu.generation import GenerationConfig, GenerationService
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.nn.attention import dot_product_attention
    from bigdl_tpu.telemetry import programs
    from bigdl_tpu.tools.synthetic import seeded_rng
    from bigdl_tpu.utils.random import RandomGenerator

    b = int(os.environ.get("BENCH_KERNELS_BATCH", 4))
    heads = int(os.environ.get("BENCH_KERNELS_HEADS", 8))
    seq = int(os.environ.get("BENCH_KERNELS_SEQ", 512))
    hd = int(os.environ.get("BENCH_KERNELS_HEAD_DIM", 64))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    row = {}
    reg = programs.registry()

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(kq, (b, heads, seq, hd), jnp.float32)
    k = jax.random.normal(kk, (b, heads, seq, hd), jnp.float32)
    v = jax.random.normal(kv, (b, heads, seq, hd), jnp.float32)

    def attn_leg(tag, cfg):
        from bigdl_tpu.kernels.dispatch import taken_in_thread

        with kernels.use(cfg):
            fn = jax.jit(lambda q_, k_, v_: dot_product_attention(
                q_, k_, v_, causal=True))
            t0 = time.perf_counter()
            # label by trace EVIDENCE, like every other register site:
            # a declined dispatch (shape over the VMEM budget) must
            # report its leg as reference, not fake a pallas number
            taken_before = taken_in_thread()
            compiled = fn.lower(q, k, v).compile()
            compile_s = time.perf_counter() - t0
            name = f"bench/attention/{tag}"
            reg.register(name, "serving", compiled=compiled,
                         compile_s=compile_s, items_per_call=b * seq,
                         kernel=("pallas"
                                 if taken_in_thread() > taken_before
                                 else "reference"))
            jax.block_until_ready(compiled(q, k, v))  # warm
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = compiled(q, k, v)
            jax.block_until_ready(out)  # sync once per timed window
            dt = time.perf_counter() - t0
            return reg.record_rate(name, b * seq * iters / dt), dt

    p_on, dt_on = attn_leg("pallas", kernels.KernelConfig.all_on())
    p_off, dt_off = attn_leg("reference", kernels.KernelConfig.off())
    row["kernels_attention_tokens_per_sec_on"] = round(
        b * seq * iters / dt_on, 1)
    row["kernels_attention_tokens_per_sec_off"] = round(
        b * seq * iters / dt_off, 1)
    row["kernels_attention_mfu_on"] = round(p_on.mfu or 0.0, 4) \
        if p_on is not None else 0.0
    row["kernels_attention_mfu_off"] = round(p_off.mfu or 0.0, 4) \
        if p_off is not None else 0.0

    vocab = int(os.environ.get("BENCH_KERNELS_VOCAB", 8192))
    hidden = int(os.environ.get("BENCH_KERNELS_HIDDEN", 512))
    layers = int(os.environ.get("BENCH_KERNELS_LAYERS", 4))
    max_len = int(os.environ.get("BENCH_KERNELS_LEN", 512))
    slots = int(os.environ.get("BENCH_KERNELS_SLOTS", 16))
    n_reqs = int(os.environ.get("BENCH_KERNELS_REQS", 24))
    max_new = int(os.environ.get("BENCH_KERNELS_NEW", 32))

    def decode_leg(cfg) -> float:
        with kernels.use(cfg):
            RandomGenerator.set_seed(13)
            model = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                                  num_layers=layers, num_heads=8,
                                  max_len=max_len).evaluate()
            model.ensure_initialized()
            svc = GenerationService(config=GenerationConfig(
                slots=slots, max_len=max_len,
                prefill_rows=min(4, slots),
                max_queue=max(n_reqs, 256)))
            svc.load("klm", model)  # warmup compiles outside the timing
            r = seeded_rng(14)
            prompts = [r.randint(1, vocab,
                                 r.randint(4, max_len - max_new))
                       .astype(np.int32) for _ in range(n_reqs)]
            t0 = time.time()
            streams = [svc.generate("klm", p, max_new_tokens=max_new)
                       for p in prompts]
            total = sum(len(s.result()) for s in streams)
            dt = time.time() - t0
            svc.shutdown()
            return total / dt

    tps_on = decode_leg(kernels.KernelConfig.all_on())
    tps_off = decode_leg(kernels.KernelConfig.off())
    row["kernels_decode_tokens_per_sec_on"] = round(tps_on, 1)
    row["kernels_decode_tokens_per_sec_off"] = round(tps_off, 1)
    row["kernels_decode_speedup"] = round(tps_on / tps_off, 3)
    return row


def _bench_tuned():
    """TUNED row: what the autotuner's winner buys over the hand-picked
    defaults. Runs ONE prune-then-measure sweep over the bounded smoke
    spaces (``bigdl_tpu.autotune.defaults``) — the default config is a
    point IN those spaces, so winner and baseline come from the same
    seeded windows and the speedup is attributable to configuration,
    not noise. ``BENCH_TUNED_OUT`` additionally saves the tuned.json
    artifact the sweep produced."""
    from bigdl_tpu.autotune import defaults as dflt
    from bigdl_tpu.autotune import save_tuned
    from bigdl_tpu.tools.autotune import run_autotune

    seed = int(os.environ.get("BENCH_TUNED_SEED", 0))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    cfg = run_autotune(("train", "serving"), seed=seed, iters=iters,
                       smoke=True, log=lambda *_a, **_k: None)
    out = os.environ.get("BENCH_TUNED_OUT")
    if out:
        save_tuned(cfg, out)

    def entry_for(regime, want):
        want = {k: (list(v) if isinstance(v, tuple) else v)
                for k, v in want.items()}
        for e in cfg.leaderboard:
            if e.get("ok") and e["regime"] == regime and all(
                    e["config"].get(k) == v for k, v in want.items()):
                return e
        return None

    row = {}
    legs = (("train", "train_steps_per_sec",
             dflt.DEFAULT_TRAIN_CONFIG),
            ("serving", "decode_tokens_per_sec",
             dflt.DEFAULT_SERVING_CONFIG))
    for regime, metric, default_cfg in legs:
        winner = entry_for(regime, cfg.winners.get(regime, {}))
        default = entry_for(regime, default_cfg)
        if winner is None or default is None:
            continue
        row[f"tuned_{metric}"] = round(winner["objective"], 1)
        row[f"default_{metric}"] = round(default["objective"], 1)
        if default["objective"] > 0:
            row[f"tuned_vs_default_{regime}_speedup"] = round(
                winner["objective"] / default["objective"], 3)
    return row


def _bench_transformer_lm():
    """TransformerLM 6L/512d/8H seq 512, batch 16: full train steps
    (fwd+bwd+SGD) under one scanned dispatch; returns tokens/sec.

    ONE implementation serves the scoreboard metric and the ceiling
    ablation (tools/ceiling.framework_tlm) — they must measure the same
    program, so this only parameterizes that harness."""
    from bigdl_tpu.tools import ceiling as C

    C.BATCH = int(os.environ.get("BENCH_LM_BATCH", 16))
    C.SCAN = int(os.environ.get("BENCH_SCAN", 8))
    C.TLM["seq"] = int(os.environ.get("BENCH_LM_SEQ", 512))
    iters = int(os.environ.get("BENCH_ITERS", 6))
    seqs_per_sec = C.framework_tlm(iters)
    return seqs_per_sec * C.TLM["seq"]


if __name__ == "__main__":
    from bigdl_tpu.utils.engine import enable_compile_cache

    enable_compile_cache()
    main()
