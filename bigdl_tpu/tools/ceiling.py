"""On-chip ceiling ablation: framework steps vs hand-rolled raw-JAX
steps of identical semantics (the reference's counterpart is
models/utils/DistriOptimizerPerf.scala:38 leaving nothing on the table).

ResNet-50 modes:
  fw                framework step as shipped pre-r3 (conv biases, no donation)
  fw_donate         + donated scan carry
  fw_nobias         + pre-BN conv biases dropped (models/resnet default now)
  fw_nobias_donate  + both (= bench.py configuration)
  hand              hand-rolled full-semantics step (raw lax convs, one-pass
                    BN with running stats, CE loss, SGD momentum+wd+nesterov)
  hand_fwd          hand-rolled forward only

Zoo-wide modes (same methodology — the framework must meet its own
hand-rolled same-semantics ceiling on every flagship family):
  fw_vgg16 / hand_vgg16   VGG-16 ImageNet (batch BENCH_BATCH, default 128)
  fw_tlm / hand_tlm       TransformerLM 6L/512d/8H seq 512 (batch 16)

Every mode also reports analytic TF/s (XLA's compiled cost analysis)
and MFU against the device peak (BIGDL_DEVICE_TFS, default 197 TF/s —
the v5e bf16 peak, assumed whatever the device is: ROADMAP A1/C9).

Usage: python -m bigdl_tpu.tools.ceiling <mode> [iters]
"""
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import bigdl_tpu.telemetry as telemetry

# module-level registration so `tools.check --telemetry-audit` sees the
# REAL instrument on import
_ITEMS_PER_S = telemetry.histogram(
    "tools/ceiling/items_per_s", "measured throughput per ceiling run")

BATCH = int(os.environ.get("BENCH_BATCH", 256))
SCAN = int(os.environ.get("BENCH_SCAN", 8))
WARMUP = 1
# MFU denominator: v5e peak bf16 (197 TF/s), assumed on any device —
# the peaks table keyed by device_kind comes with the benchmark PR.
DEVICE_TFS = float(os.environ.get("BIGDL_DEVICE_TFS", 197.0))

_FLOPS = {"per_chunk": None}


def timed(run_chunk, carry, iters):
    root = jax.random.PRNGKey(0)
    keys0 = jax.random.split(root, SCAN)
    # ONE AOT compile serves both the cost analysis and the timed loop
    # (lower().compile() does not populate the jit dispatch cache, so
    # executing the compiled object avoids paying the compile twice)
    _FLOPS["per_chunk"] = None
    try:
        compiled = run_chunk.lower(carry, keys0).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        _FLOPS["per_chunk"] = float(cost["flops"])
        run_chunk = compiled
    except Exception:
        pass  # backend without AOT cost analysis: plain jit path
    # timing audit: chunks chain through `carry`, so one sync before t0
    # and one at the end bound ALL the dispatched work. The loss fetch
    # alone would not gate the LAST chunk's param-update branch — block
    # on the carry too, or the final update rides outside the window.
    for i in range(WARMUP):
        keys = jax.random.split(jax.random.fold_in(root, i), SCAN)
        carry, losses = run_chunk(carry, keys)
    jax.block_until_ready(carry)
    float(losses.sum())
    t0 = time.time()
    for i in range(iters):
        keys = jax.random.split(jax.random.fold_in(root, 1000 + i), SCAN)
        carry, losses = run_chunk(carry, keys)
    jax.block_until_ready(carry)
    float(losses.sum())
    dt = time.time() - t0
    return BATCH * SCAN * iters / dt


def mfu_fields(rate_per_sec, per_item_flops=None):
    """{achieved_tfs, mfu} from the measured rate and the compiled
    chunk's analytic flops (fallback: caller-supplied per-item flops).

    Thin shim over :func:`bigdl_tpu.telemetry.programs.mfu_fields` —
    the cost-analysis → MFU math (including the scan-body-counted-once
    disambiguation, ``resolve_per_item_flops``) lives in ONE place
    there; this keeps the ceiling CLI's JSON fields byte-compatible."""
    from bigdl_tpu.telemetry import programs

    return programs.mfu_fields(
        rate_per_sec, flops_per_call=_FLOPS["per_chunk"],
        items_per_call=BATCH, scan_length=SCAN,
        per_item_estimate=per_item_flops, peak_tfs=DEVICE_TFS)


def framework(mode, iters):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import resnet as R
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    Engine.set_compute_dtype(jnp.bfloat16)
    RandomGenerator.set_seed(1)
    # fw/fw_donate reproduce the r2 form (reference parameter set with
    # conv biases); the nobias modes are models/resnet's r3 default
    model = R.ResNet(1000, depth=50, dataset="ImageNet",
                     conv_bias="nobias" not in mode).training()
    model.ensure_initialized()
    criterion = nn.CrossEntropyCriterion()
    optim = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4,
                nesterov=True, dampening=0.0)
    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    step = build_train_step(model, criterion, optim)

    def scan_body(carry, key):
        params, opt_state, mstate = carry
        kx, ky, kr = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (BATCH, 3, 224, 224), jnp.float32)
        y = jax.random.randint(ky, (BATCH,), 1, 1001).astype(jnp.float32)
        params, opt_state, mstate, loss = step(params, opt_state, mstate,
                                               kr, 0.1, x, y)
        return (params, opt_state, mstate), loss

    kw = {"donate_argnums": (0,)} if "donate" in mode else {}

    @functools.partial(jax.jit, **kw)
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, opt_state, mstate), iters)


# ------------------------------------------------------- hand-rolled RN50

CFG50 = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]


def hand_init(key):
    params, state = [], []

    def conv_p(k, cin, cout, kh, kw_):
        fan_in = cin * kh * kw_
        w = jax.random.normal(k, (cout, cin, kh, kw_), jnp.float32) \
            * np.sqrt(2.0 / fan_in)
        return w

    def bn_p(c):
        return {"g": jnp.ones((c,), jnp.float32),
                "b": jnp.zeros((c,), jnp.float32)}

    def bn_s(c):
        return {"m": jnp.zeros((c,), jnp.float32),
                "v": jnp.ones((c,), jnp.float32)}

    ks = iter(jax.random.split(key, 256))
    params.append(conv_p(next(ks), 3, 64, 7, 7))     # stem
    params.append(bn_p(64))
    state.append(bn_s(64))
    cin = 64
    for feats, count, stride in CFG50:
        for i in range(count):
            s = stride if i == 0 else 1
            blk = {"c1": conv_p(next(ks), cin, feats, 1, 1),
                   "bn1": bn_p(feats),
                   "c2": conv_p(next(ks), feats, feats, 3, 3),
                   "bn2": bn_p(feats),
                   "c3": conv_p(next(ks), feats, feats * 4, 1, 1),
                   "bn3": bn_p(feats * 4)}
            st = {"bn1": bn_s(feats), "bn2": bn_s(feats),
                  "bn3": bn_s(feats * 4)}
            if i == 0:
                blk["cs"] = conv_p(next(ks), cin, feats * 4, 1, 1)
                blk["bns"] = bn_p(feats * 4)
                st["bns"] = bn_s(feats * 4)
            params.append(blk)
            state.append(st)
            cin = feats * 4
    wfc = jax.random.normal(next(ks), (2048, 1000), jnp.float32) * 0.01
    params.append({"w": wfc, "b": jnp.zeros((1000,), jnp.float32)})
    return params, state


def conv(x, w, stride=1, pad=0):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def bn(x, p, s, mom=0.1):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 2, 3))
    ex2 = jnp.mean(jnp.square(x32), axis=(0, 2, 3))
    var = jnp.maximum(ex2 - jnp.square(mean), 0.0)
    n = x.size // x.shape[1]
    new_s = {"m": (1 - mom) * s["m"] + mom * mean,
             "v": (1 - mom) * s["v"] + mom * var * n / (n - 1)}
    inv = lax.rsqrt(var + 1e-5).astype(x.dtype)
    mean = mean.astype(x.dtype)
    y = (x - mean[None, :, None, None]) * inv[None, :, None, None]
    y = y * p["g"].astype(x.dtype)[None, :, None, None] \
        + p["b"].astype(x.dtype)[None, :, None, None]
    return y, new_s


def hand_forward(params, state, x):
    new_state = []
    x = conv(lax.stop_gradient(x), params[0], 2, 3)
    x, s = bn(x, params[1], state[0])
    new_state.append(s)
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                          (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    i = 2
    si = 1
    for feats, count, stride in CFG50:
        for j in range(count):
            blk, st = params[i], state[si]
            s0 = stride if j == 0 else 1
            ns = {}
            h = conv(x, blk["c1"])
            h, ns["bn1"] = bn(h, blk["bn1"], st["bn1"])
            h = jax.nn.relu(h)
            h = conv(h, blk["c2"], s0, 1)
            h, ns["bn2"] = bn(h, blk["bn2"], st["bn2"])
            h = jax.nn.relu(h)
            h = conv(h, blk["c3"])
            h, ns["bn3"] = bn(h, blk["bn3"], st["bn3"])
            if "cs" in blk:
                sc = conv(x, blk["cs"], s0)
                sc, ns["bns"] = bn(sc, blk["bns"], st["bns"])
            else:
                sc = x
            x = jax.nn.relu(h + sc)
            new_state.append(ns)
            i += 1
            si += 1
    x = jnp.mean(x, axis=(2, 3))
    fc = params[i]
    logits = x @ fc["w"].astype(x.dtype) + fc["b"].astype(x.dtype)
    return logits.astype(jnp.float32), new_state


def hand(mode, iters):
    key = jax.random.PRNGKey(1)
    params, state = hand_init(key)
    mom_buf = jax.tree.map(jnp.zeros_like, params)

    def loss_fn(p, s, x, y):
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        logits, ns = hand_forward(p16, s, x.astype(jnp.bfloat16))
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - ll), ns

    fwd_only = mode == "hand_fwd"

    def scan_body(carry, key):
        params, mom, state = carry
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (BATCH, 3, 224, 224), jnp.float32)
        y = jax.random.randint(ky, (BATCH,), 0, 1000)
        if fwd_only:
            loss, ns = loss_fn(params, state, x, y)
            return (params, mom, ns), loss
        (loss, ns), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, x, y)
        grads = jax.tree.map(
            lambda g, p: g.astype(jnp.float32) + 1e-4 * p, grads, params)
        mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom_buf if mom is None
                           else mom, grads)
        upd = jax.tree.map(lambda g, m: g + 0.9 * m, grads, mom)  # nesterov
        params = jax.tree.map(lambda p, u: p - 0.1 * u, params, upd)
        return (params, mom, ns), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, mom_buf, state), iters)


# ----------------------------------------------------------- VGG-16 pair

def _sgd_momentum_tree(params, grads, mom, lr=0.01):
    mom = jax.tree.map(lambda m, g: 0.9 * m + g.astype(jnp.float32),
                       mom, grads)
    params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
    return params, mom


def framework_vgg16(iters):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import Vgg_16
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    Engine.set_compute_dtype(jnp.bfloat16)
    RandomGenerator.set_seed(1)
    model = Vgg_16(1000).training()
    model.ensure_initialized()
    optim = SGD(learning_rate=0.01, momentum=0.9)
    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    step = build_train_step(model, nn.ClassNLLCriterion(), optim)

    def scan_body(carry, key):
        params, opt_state, mstate = carry
        kx, ky, kr = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (BATCH, 3, 224, 224), jnp.float32)
        y = jax.random.randint(ky, (BATCH,), 1, 1001).astype(jnp.float32)
        params, opt_state, mstate, loss = step(params, opt_state, mstate,
                                               kr, 0.01, x, y)
        return (params, opt_state, mstate), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, opt_state, mstate), iters)


VGG_CFG = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
           512, 512, 512, "M", 512, 512, 512, "M"]


def hand_vgg16(iters):
    """Raw-JAX VGG-16 with the framework model's exact semantics: biased
    3x3 convs + ReLU + maxpools, FC 25088-4096-4096-1000 with
    Threshold(0,1e-6) and Dropout(0.5), LogSoftMax + NLL, SGD momentum,
    bf16 compute / f32 master."""
    key = jax.random.PRNGKey(1)
    ks = iter(jax.random.split(key, 64))
    params = []
    cin = 3
    for v in VGG_CFG:
        if v == "M":
            continue
        fan = cin * 9
        params.append({
            "w": jax.random.normal(next(ks), (v, cin, 3, 3), jnp.float32)
            * np.sqrt(2.0 / fan),
            "b": jnp.zeros((v,), jnp.float32)})
        cin = v
    dims = [(512 * 7 * 7, 4096), (4096, 4096), (4096, 1000)]
    for din, dout in dims:
        params.append({
            "w": jax.random.normal(next(ks), (din, dout), jnp.float32)
            * np.sqrt(1.0 / din),
            "b": jnp.zeros((dout,), jnp.float32)})
    mom = jax.tree.map(jnp.zeros_like, params)

    def fwd(p, x, key):
        i = 0
        for v in VGG_CFG:
            if v == "M":
                x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 2, 2),
                                      (1, 1, 2, 2), "VALID")
                continue
            x = conv(x, p[i]["w"], 1, 1) \
                + p[i]["b"].astype(x.dtype)[None, :, None, None]
            x = jax.nn.relu(x)
            i += 1
        x = x.reshape(x.shape[0], -1)
        for j, (din, dout) in enumerate(dims):
            fc = p[i + j]
            x = x @ fc["w"].astype(x.dtype) + fc["b"].astype(x.dtype)
            if j < 2:
                x = jnp.where(x > 0, x, jnp.asarray(1e-6, x.dtype))
                keep = jax.random.bernoulli(
                    jax.random.fold_in(key, j), 0.5, x.shape)
                x = jnp.where(keep, x / 0.5, 0.0)
        return jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)

    def loss_fn(p, x, y, key):
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        logp = fwd(p16, x.astype(jnp.bfloat16), key)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    def scan_body(carry, key):
        params, mom = carry
        kx, ky, kd = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (BATCH, 3, 224, 224), jnp.float32)
        y = jax.random.randint(ky, (BATCH,), 0, 1000)
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, kd)
        params, mom = _sgd_momentum_tree(params, grads, mom)
        return (params, mom), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, mom), iters)


# ------------------------------------------------------ TransformerLM pair

TLM = dict(vocab=32000, d=512, layers=6, heads=8, seq=512)


def framework_tlm(iters):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    Engine.set_compute_dtype(jnp.bfloat16)
    RandomGenerator.set_seed(1)
    model = TransformerLM(TLM["vocab"], hidden_size=TLM["d"],
                          num_layers=TLM["layers"], num_heads=TLM["heads"],
                          max_len=TLM["seq"]).training()
    model.ensure_initialized()
    optim = SGD(learning_rate=0.1)
    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    step = build_train_step(model, nn.SequenceCrossEntropyCriterion(),
                            optim)

    def scan_body(carry, key):
        params, opt_state, mstate = carry
        kx, kr = jax.random.split(key)
        x = jax.random.randint(kx, (BATCH, TLM["seq"]), 0, TLM["vocab"])
        params, opt_state, mstate, loss = step(params, opt_state, mstate,
                                               kr, 0.1, x, x)
        return (params, opt_state, mstate), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, opt_state, mstate), iters)


def hand_tlm(iters):
    """Raw-JAX decoder LM with models/transformer's exact semantics:
    learned pos embeddings, pre-norm blocks (uniform-init QKV/O and FFN
    with biases, gelu), ln_f, tied head, sequence CE, plain SGD,
    bf16 compute / f32 master."""
    V, D, L, H, S = (TLM["vocab"], TLM["d"], TLM["layers"], TLM["heads"],
                     TLM["seq"])
    hd = D // H
    key = jax.random.PRNGKey(1)
    ks = iter(jax.random.split(key, 16 + 8 * L))
    s = 1.0 / np.sqrt(D)

    def u(shape, scale):
        return jax.random.uniform(next(ks), shape, jnp.float32,
                                  -scale, scale)

    params = {"embed": jax.random.normal(next(ks), (V, D)) * s,
              "pos": jax.random.normal(next(ks), (S, D)) * s,
              "lnf": (jnp.ones((D,)), jnp.zeros((D,)))}
    blocks = []
    sf = 1.0 / np.sqrt(4 * D)
    # per-block constructions must stay DISTINCT buffers: the carry is
    # donated (donate_argnums), and XLA rejects the same buffer donated
    # twice — hoisting/sharing these zeros breaks run_chunk.
    # bigdl: disable-file=jnp-in-host-loop
    for _ in range(L):
        blocks.append({
            "ln1": (jnp.ones((D,)), jnp.zeros((D,))),
            "qkvo": [(u((D, D), s), jnp.zeros((D,))) for _ in range(4)],
            "ln2": (jnp.ones((D,)), jnp.zeros((D,))),
            "up": (u((D, 4 * D), s), jnp.zeros((4 * D,))),
            "down": (u((4 * D, D), sf), jnp.zeros((D,)))})
    params["blocks"] = blocks

    def ln(x, p):
        g, b = p
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + 1e-5) * g.astype(x.dtype) \
            + b.astype(x.dtype)

    def fwd(p, toks):
        b = toks.shape[0]
        x = p["embed"][toks] + p["pos"][None, :S]
        cmask = jnp.tril(jnp.ones((S, S), bool))  # hoisted: loop-invariant
        for blk in p["blocks"]:
            h = ln(x, blk["ln1"])
            (qw, qb), (kw, kb), (vw, vb), (ow, ob) = blk["qkvo"]

            def split(t):
                return t.reshape(b, S, H, hd).transpose(0, 2, 1, 3)
            q = split(h @ qw.astype(h.dtype) + qb.astype(h.dtype))
            k = split(h @ kw.astype(h.dtype) + kb.astype(h.dtype))
            v = split(h @ vw.astype(h.dtype) + vb.astype(h.dtype))
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
            sc = jnp.where(cmask, sc, jnp.finfo(sc.dtype).min)
            att = jax.nn.softmax(sc, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", att, v)
            out = out.transpose(0, 2, 1, 3).reshape(b, S, D)
            x = x + out @ ow.astype(x.dtype) + ob.astype(x.dtype)
            h = ln(x, blk["ln2"])
            uw, ub = blk["up"]
            dw, db = blk["down"]
            h = jax.nn.gelu(h @ uw.astype(h.dtype) + ub.astype(h.dtype))
            x = x + h @ dw.astype(h.dtype) + db.astype(h.dtype)
        x = ln(x, p["lnf"])
        return x @ p["embed"].T.astype(x.dtype)

    def loss_fn(p, toks):
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        logits = fwd(p16, toks).astype(jnp.float32).reshape(-1, V)
        t = toks.reshape(-1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=1).mean()

    def scan_body(carry, key):
        params = carry
        x = jax.random.randint(key, (BATCH, S), 0, V)
        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        params = jax.tree.map(
            lambda p, g: p - 0.1 * g.astype(jnp.float32), params, grads)
        return params, loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, params, iters)


# --------------------------------------------------- Inception-v1 pair

def framework_inception(iters):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.inception import Inception_v1_NoAuxClassifier
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    Engine.set_compute_dtype(jnp.bfloat16)
    RandomGenerator.set_seed(1)
    model = Inception_v1_NoAuxClassifier(1000).training()
    model.ensure_initialized()
    optim = SGD(learning_rate=0.01, momentum=0.9)
    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    step = build_train_step(model, nn.ClassNLLCriterion(), optim)

    def scan_body(carry, key):
        params, opt_state, mstate = carry
        kx, ky, kr = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (BATCH, 3, 224, 224), jnp.float32)
        y = jax.random.randint(ky, (BATCH,), 1, 1001).astype(jnp.float32)
        params, opt_state, mstate, loss = step(params, opt_state, mstate,
                                               kr, 0.01, x, y)
        return (params, opt_state, mstate), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, opt_state, mstate), iters)


# (input_size, (n1x1, (n3r, n3), (n5r, n5), npool)) per inception block
INC_CFG = [
    ("3a", 192, (64, (96, 128), (16, 32), 32)),
    ("3b", 256, (128, (128, 192), (32, 96), 64)),
    ("P", 0, None),
    ("4a", 480, (192, (96, 208), (16, 48), 64)),
    ("4b", 512, (160, (112, 224), (24, 64), 64)),
    ("4c", 512, (128, (128, 256), (24, 64), 64)),
    ("4d", 512, (112, (144, 288), (32, 64), 64)),
    ("4e", 528, (256, (160, 320), (32, 128), 128)),
    ("P", 0, None),
    ("5a", 832, (256, (160, 320), (32, 128), 128)),
    ("5b", 832, (384, (192, 384), (48, 128), 128)),
]


def _maxpool_ceil(x, k, s, pad=0):
    """Torch ceil-mode maxpool with symmetric base padding: the tail is
    additionally padded with -inf so the last partial window counts
    (matches nn.SpatialMaxPooling(...).ceil())."""
    n = x.shape[2] + 2 * pad
    out = -(-(n - k) // s) + 1
    extra = max((out - 1) * s + k - n, 0)
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, s, s),
        ((0, 0), (0, 0), (pad, pad + extra), (pad, pad + extra)))


def hand_inception(iters):
    """Raw-JAX GoogLeNet with the zoo model's exact op semantics
    (Inception_v1_NoAuxClassifier: biased Xavier convs + ReLU, LRN(5),
    ceil-mode pools, 4-branch channel concat, avgpool 7, Dropout(0.4),
    Linear 1024->1000, LogSoftMax+NLL, SGD momentum, bf16 compute /
    f32 master)."""
    key = jax.random.PRNGKey(1)
    ks = iter(jax.random.split(key, 256))

    def conv_p(cin, cout, k):
        fan_in, fan_out = cin * k * k, cout * k * k
        lim = np.sqrt(6.0 / (fan_in + fan_out))
        return {"w": jax.random.uniform(next(ks), (cout, cin, k, k),
                                        jnp.float32, -lim, lim),
                "b": jnp.zeros((cout,), jnp.float32)}

    params = {"stem1": conv_p(3, 64, 7), "stem2": conv_p(64, 64, 1),
              "stem3": conv_p(64, 192, 3)}
    for name, cin, cfg in INC_CFG:
        if cfg is None:
            continue
        n1, (n3r, n3), (n5r, n5), npool = cfg
        params[name] = {
            "b1": conv_p(cin, n1, 1),
            "b3r": conv_p(cin, n3r, 1), "b3": conv_p(n3r, n3, 3),
            "b5r": conv_p(cin, n5r, 1), "b5": conv_p(n5r, n5, 5),
            "bp": conv_p(cin, npool, 1)}
    lim = np.sqrt(6.0 / (1024 + 1000))
    params["fc"] = {"w": jax.random.uniform(next(ks), (1024, 1000),
                                            jnp.float32, -lim, lim),
                    "b": jnp.zeros((1000,), jnp.float32)}

    def cv(x, p, stride=1, pad=0):
        return conv(x, p["w"].astype(x.dtype), stride, pad) \
            + p["b"].astype(x.dtype)[None, :, None, None]

    def lrn(x, size=5, alpha=1e-4, beta=0.75):
        sq = x * x
        half = (size - 1) // 2
        # init must be a python scalar: a traced init value breaks
        # reduce_window's reverse-mode linearization
        summed = lax.reduce_window(
            sq, 0.0, lax.add, (1, size, 1, 1), (1, 1, 1, 1),
            ((0, 0), (half, size - 1 - half), (0, 0), (0, 0)))
        return x / jnp.power(1.0 + alpha / size * summed, beta)

    def block(x, p):
        b1 = jax.nn.relu(cv(x, p["b1"]))
        b3 = jax.nn.relu(cv(jax.nn.relu(cv(x, p["b3r"])), p["b3"],
                            1, 1))
        b5 = jax.nn.relu(cv(jax.nn.relu(cv(x, p["b5r"])), p["b5"],
                            1, 2))
        bp = jax.nn.relu(cv(_maxpool_ceil(x, 3, 1, pad=1), p["bp"]))
        return jnp.concatenate([b1, b3, b5, bp], axis=1)

    def fwd(p, x, key):
        x = jax.nn.relu(cv(x, p["stem1"], 2, 3))
        x = _maxpool_ceil(x, 3, 2)
        x = lrn(x)
        x = jax.nn.relu(cv(x, p["stem2"]))
        x = jax.nn.relu(cv(x, p["stem3"], 1, 1))
        x = lrn(x)
        x = _maxpool_ceil(x, 3, 2)
        for name, _, cfg in INC_CFG:
            if cfg is None:
                x = _maxpool_ceil(x, 3, 2)
            else:
                x = block(x, p[name])
        x = lax.reduce_window(x, 0.0, lax.add,
                              (1, 1, 7, 7), (1, 1, 1, 1), "VALID") / 49.0
        keep = jax.random.bernoulli(key, 0.6, x.shape)
        x = jnp.where(keep, x / 0.6, 0.0)
        x = x.reshape(x.shape[0], 1024)
        logits = x @ p["fc"]["w"].astype(x.dtype) \
            + p["fc"]["b"].astype(x.dtype)
        return jax.nn.log_softmax(logits.astype(jnp.float32), -1)

    def loss_fn(p, x, y, key):
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        logp = fwd(p16, x.astype(jnp.bfloat16), key)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    mom = jax.tree.map(jnp.zeros_like, params)

    def scan_body(carry, key):
        params, mom = carry
        kx, ky, kd = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (BATCH, 3, 224, 224), jnp.float32)
        y = jax.random.randint(ky, (BATCH,), 0, 1000)
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, kd)
        params, mom = _sgd_momentum_tree(params, grads, mom)
        return (params, mom), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, mom), iters)


# ------------------------------------------------------ PTB LSTM pair

PTB = dict(vocab=10000, hidden=650, layers=2, seq=35)


def framework_lstm(iters):
    """The scan-heavy zoo family: PTBModel (embedding + stacked
    Recurrent(LSTM) + TimeDistributed(Linear)), the recipe's
    TimeDistributedCriterion(CrossEntropy) objective."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.rnn import PTBModel
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    Engine.set_compute_dtype(jnp.bfloat16)
    RandomGenerator.set_seed(1)
    model = PTBModel(PTB["vocab"], PTB["hidden"], PTB["vocab"],
                     num_layers=PTB["layers"]).training()
    model.ensure_initialized()
    optim = SGD(learning_rate=0.1)
    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
    step = build_train_step(model, crit, optim)

    def scan_body(carry, key):
        params, opt_state, mstate = carry
        kx, kr = jax.random.split(key)
        x = jax.random.randint(kx, (BATCH, PTB["seq"]), 1,
                               PTB["vocab"] + 1)
        y = x.astype(jnp.float32)
        params, opt_state, mstate, loss = step(params, opt_state, mstate,
                                               kr, 0.1, x, y)
        return (params, opt_state, mstate), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, (params, opt_state, mstate), iters)


def hand_lstm(iters):
    """Raw-JAX stacked LSTM LM with the zoo model's exact semantics:
    1-based embedding lookup, fused (4H) i,f,g,o gates per step under a
    time-major lax.scan per layer, time-distributed linear head, CE,
    plain SGD, bf16 compute / f32 master."""
    V, H, L, S = PTB["vocab"], PTB["hidden"], PTB["layers"], PTB["seq"]
    key = jax.random.PRNGKey(1)
    ks = iter(jax.random.split(key, 16))
    stdv = 1.0 / np.sqrt(H)

    def u(shape, scale):
        return jax.random.uniform(next(ks), shape, jnp.float32,
                                  -scale, scale)

    params = {"emb": jax.random.normal(next(ks), (V, H)) * 0.1,
              "cells": [{"w_ih": u((4 * H, H), stdv),
                         "w_hh": u((4 * H, H), stdv),
                         "bias": u((4 * H,), stdv)} for _ in range(L)],
              "fc": {"w": u((H, V), stdv), "b": jnp.zeros((V,))}}

    def lstm_layer(p, xs):
        # xs: [S, B, H] time-major
        def step(hc, x):
            h, c = hc
            gates = x @ p["w_ih"].T.astype(x.dtype) \
                + h @ p["w_hh"].T.astype(x.dtype) \
                + p["bias"].astype(x.dtype)
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = (jax.nn.sigmoid(i), jax.nn.sigmoid(f),
                       jax.nn.sigmoid(o))
            c2 = f * c + i * jnp.tanh(g)
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2

        b = xs.shape[1]
        z = jnp.zeros((b, H), xs.dtype)
        _, hs = lax.scan(step, (z, z), xs)
        return hs

    def loss_fn(p, toks):
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        x = p16["emb"][toks - 1]                    # 1-based LookupTable
        x = x.transpose(1, 0, 2)                    # [S, B, H]
        for cell in p16["cells"]:
            x = lstm_layer(cell, x)
        logits = x @ p16["fc"]["w"].astype(x.dtype) \
            + p16["fc"]["b"].astype(x.dtype)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        t = (toks - 1).transpose(1, 0)
        return -jnp.take_along_axis(logp, t[..., None], axis=-1).mean()

    def scan_body(carry, key):
        params = carry
        x = jax.random.randint(key, (BATCH, S), 1, V + 1)
        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        params = jax.tree.map(
            lambda p, g: p - 0.1 * g.astype(jnp.float32), params, grads)
        return params, loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_chunk(carry, keys):
        return lax.scan(scan_body, carry, keys)

    return timed(run_chunk, params, iters)


MODES = {"fw_vgg16": framework_vgg16, "hand_vgg16": hand_vgg16,
         "fw_tlm": framework_tlm, "hand_tlm": hand_tlm,
         "fw_inception": framework_inception,
         "hand_inception": hand_inception,
         "fw_lstm": framework_lstm, "hand_lstm": hand_lstm}


if __name__ == "__main__":
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    mode = sys.argv[1]
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    if "tlm" in mode and "BENCH_BATCH" not in os.environ:
        BATCH = 16
    if "vgg" in mode and "BENCH_BATCH" not in os.environ:
        BATCH = 128
    if "inception" in mode and "BENCH_BATCH" not in os.environ:
        BATCH = 128
    if "lstm" in mode and "BENCH_BATCH" not in os.environ:
        BATCH = 64
    if mode in MODES:
        r = MODES[mode](iters)
    elif mode.startswith("hand"):
        r = hand(mode, iters)
    else:
        r = framework(mode, iters)
    # steps_per_sync: every ceiling harness dispatches SCAN fused steps
    # per host sync — the same window the Optimizer's set_steps_per_sync
    # knob gives training, so ablations and driver runs are comparable
    out = {"mode": mode, "items_per_sec": round(r, 1),
           "steps_per_sync": SCAN}
    if "tlm" in mode:
        out["tokens_per_sec"] = round(r * TLM["seq"], 1)
    if "lstm" in mode:
        out["tokens_per_sec"] = round(r * PTB["seq"], 1)
    out.update(mfu_fields(r))
    print(json.dumps(out))
    # one flag, default off: append a telemetry snapshot so BENCH
    # trajectories carry phase breakdowns, not just the one total
    jsonl = os.environ.get("BIGDL_METRICS_JSONL")
    if jsonl:
        _ITEMS_PER_S.observe(r, mode=mode)
        telemetry.snapshot_to_jsonl(jsonl,
                                    meta=dict(out, tool="ceiling",
                                              batch=BATCH, scan=SCAN,
                                              iters=iters))
