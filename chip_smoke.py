#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of the models the repo ships, on ONE TPU chip:

- ``train_resnet50``  ``LocalOptimizer(...).optimize()`` on ResNet-50
  ImageNet (3x224x224, bf16 compute), one repeated seeded batch;
- ``train_lm``        ``Optimizer`` with ``set_steps_per_sync(K)`` +
  ``set_precision("bf16_mixed")`` + Adam on ``TransformerLM`` at the
  GPT-2-small widths (12 x 768, 12 heads, FFN 3072, vocab 50257,
  sequence 1024);
- ``serve_lm``        ``GenerationService.load()`` + ``.generate()`` on
  the same LM, 16 slots, ``max_len`` 1024, ragged seeded prompts,
  greedy, checked token for token against a plain full re-forward.

``--chips 4`` runs instead — and only — the path across chips:
``DistriOptimizer`` on a ``[4]`` data mesh with ZeRO-2 against the same
steps on one device of the same process.

Every phase prints one JSON line of facts (not rates). The LAST line of
stdout is ``{"ok": true, "device": {...}}`` and is printed only when
every phase passed on a TPU. Without a TPU the script exits non-zero
before it builds anything; it never chooses a platform itself. One
process, no child that needs the chip, no network, seeded synthetic
data. ``tests/test_chip_smoke.py`` rehearses the same phase functions
on the CPU at a tiny size.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

#: the full-width sizes the driver's run uses; tests shrink them
RESNET = dict(depth=50, classes=1000, dataset="ImageNet", image=224,
              batch=64, steps=6, lr=0.02)
LM = dict(vocab=50257, hidden=768, layers=12, heads=12, ffn=3072,
          positions=1024, seq=1024, batch=8, steps=8, steps_per_sync=4,
          lr=3e-4, data_vocab=512)
SERVE = dict(slots=16, max_len=1024, length_buckets=None,
             prompt_lens=(5, 17, 64, 130, 300, 700), new_tokens=12,
             tolerance=0.1)
MESH = dict(chips=4, layers=2, steps=4, lr=3e-4, loss_tolerance=0.01)


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


class _Losses:
    """A ``set_train_summary`` observer that keeps the per-step loss
    (the Optimizer's own reporting hook — no loop of ours)."""

    def __init__(self):
        self.values = []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.values.append(float(value))


class _Compiles:
    """Counts the programs XLA was asked to compile, the seconds that
    took, and how many of them the persistent cache already held —
    through the public ``jax.monitoring`` events. A warm second run
    asks for as many programs and spends far fewer seconds."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.count, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == self.COMPILE:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **kw):
        if event == self.CACHE_HIT:
            self.hits += 1

    def mark(self):
        return self.count, self.seconds, self.hits

    def since(self, mark):
        return (self.count - mark[0], self.seconds - mark[1],
                self.hits - mark[2])


def _kernel_counters():
    """The dispatch layer's own counters: traces routed to a pallas
    kernel, and declines with their reason."""
    import bigdl_tpu.telemetry as telemetry

    taken = telemetry.counter("kernels/dispatch/pallas")
    declined = telemetry.counter("kernels/dispatch/reference")
    return {
        "taken": {ls["op"]: int(taken.value(**ls))
                  for ls in taken.label_sets()},
        "declined": {f"{ls['op']}({ls['reason']})":
                     int(declined.value(**ls))
                     for ls in declined.label_sets()},
    }


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _on_tpu() -> bool:
    """main() refuses anything else; the tests' rehearsal runs the
    phases on the CPU, where the TPU-only assertions do not apply."""
    import jax

    return jax.default_backend() == "tpu"


def _facts(phase, widths, t0, t_built, compiles, mark, setup_end=None,
           **more):
    """One phase's JSON line. Set-up is everything before ``t_built``
    plus the seconds XLA spent compiling — or, where the phase has a
    call that IS the set-up (``load()``), everything up to
    ``setup_end``."""
    n, compile_s, hits = compiles.since(mark)
    total = time.time() - t0
    setup = (t_built - t0) + compile_s if setup_end is None \
        else setup_end - t0
    out = {"phase": phase, "widths": widths,
           "seconds": {"setup_build_and_compile": round(setup, 2),
                       "steps": round(max(0.0, total - setup), 2)},
           "compiles": n, "compile_cache_hits": hits}
    out.update(more)
    out["kernels"] = _kernel_counters()
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


def _check_losses(phase, losses, steps):
    import math

    if len(losses) != steps:
        raise AssertionError(
            f"{phase}: {len(losses)} losses reported for {steps} steps")
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{phase}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{phase}: loss did not fall: first {losses[0]} "
            f"last {losses[-1]}")


# ------------------------------------------------------------- phases

def train_resnet50(cfg, seed, compiles):
    """Vision training through ``LocalOptimizer.optimize()``."""
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.optim import SGD, LocalOptimizer, max_iteration
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    t0, mark = time.time(), compiles.mark()
    RandomGenerator.set_seed(seed)
    rng = np.random.RandomState(seed)
    px = cfg["image"]
    images = rng.standard_normal(
        (cfg["batch"], 3, px, px)).astype(np.float32)
    labels = rng.randint(1, cfg["classes"] + 1,
                         cfg["batch"]).astype(np.float32)
    # one batch == the whole dataset: every step sees the same rows
    ds = DataSet.array([Sample(images[i], labels[i])
                        for i in range(cfg["batch"])]) \
        .transform(SampleToMiniBatch(cfg["batch"]))
    model = ResNet(cfg["classes"], depth=cfg["depth"],
                   dataset=cfg["dataset"])
    losses = _Losses()
    # bf16 compute, f32 parameters: what bench.py sets on an accelerator
    Engine.set_compute_dtype(jnp.bfloat16)
    try:
        opt = LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                             batch_size=cfg["batch"])
        opt.set_optim_method(SGD(learning_rate=cfg["lr"], momentum=0.9))
        opt.set_end_when(max_iteration(cfg["steps"]))
        opt.set_train_summary(losses)
        t_built = time.time()
        opt.optimize()
    finally:
        Engine.set_compute_dtype(jnp.float32)
    _check_losses("train_resnet50", losses.values, cfg["steps"])
    widths = {k: cfg[k] for k in ("depth", "classes", "dataset", "image",
                                  "batch")}
    widths["compute"] = "bfloat16"
    return _facts("train_resnet50", widths, t0, t_built, compiles, mark,
                  steps=cfg["steps"], loss_first=losses.values[0],
                  loss_last=losses.values[-1])


def _build_lm(cfg, layers=None):
    from bigdl_tpu.models import TransformerLM

    return TransformerLM(cfg["vocab"], hidden_size=cfg["hidden"],
                         num_layers=layers or cfg["layers"],
                         num_heads=cfg["heads"], ffn_size=cfg["ffn"],
                         max_len=cfg["positions"], tie_embeddings=True)


def _lm_dataset(cfg, seed, rows):
    """Seeded token windows drawn from the first ``data_vocab`` ids: a
    unigram signal any LM learns within a few steps, so "the loss fell"
    tests the optimizer and not the luck of a batch."""
    import numpy as np

    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch

    rng = np.random.RandomState(seed)
    toks = rng.randint(0, cfg["data_vocab"],
                       (rows, cfg["seq"] + 1)).astype(np.int32)
    return DataSet.array([Sample(toks[i, :-1], toks[i, 1:])
                          for i in range(rows)]) \
        .transform(SampleToMiniBatch(cfg["batch"]))


def _lm_widths(cfg, layers=None):
    w = {k: cfg[k] for k in ("vocab", "hidden", "heads", "ffn",
                             "positions", "seq", "batch")}
    w["layers"] = layers or cfg["layers"]
    return w


def train_lm(cfg, seed, compiles):
    """Language-model training through ``Optimizer`` with a K-step
    window, the bf16 mixed-precision policy and Adam."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import Adam, max_iteration
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.utils.random import RandomGenerator

    t0, mark = time.time(), compiles.mark()
    RandomGenerator.set_seed(seed)
    k = cfg["steps_per_sync"]
    # K batches an epoch, so every window is K steps long (a window
    # closes at an epoch boundary)
    ds = _lm_dataset(cfg, seed, rows=k * cfg["batch"])
    model = _build_lm(cfg)
    losses = _Losses()
    opt = Optimizer(model, ds, nn.SequenceCrossEntropyCriterion(),
                    batch_size=cfg["batch"])
    opt.set_optim_method(Adam(learning_rate=cfg["lr"]))
    opt.set_end_when(max_iteration(cfg["steps"]))
    opt.set_steps_per_sync(k)
    opt.set_precision("bf16_mixed")
    opt.set_train_summary(losses)
    t_built = time.time()
    opt.optimize()
    _check_losses("train_lm", losses.values, cfg["steps"])
    widths = _lm_widths(cfg)
    widths.update(precision="bf16_mixed", steps_per_sync=k,
                  optim="Adam")
    return _facts("train_lm", widths, t0, t_built, compiles, mark,
                  steps=cfg["steps"], loss_first=losses.values[0],
                  loss_last=losses.values[-1])


def serve_lm(lm_cfg, cfg, seed, compiles):
    """Generation through ``GenerationService.load()`` + ``generate()``
    at the kernel policy a TPU user gets by default, on seeded random
    weights (their next token hangs on the whole context, where a few
    training steps would leave one favourite token); every stream is
    compared with a plain full re-forward of the model."""
    import jax
    import numpy as np

    from bigdl_tpu import kernels
    from bigdl_tpu.generation import GenerationConfig, GenerationService
    from bigdl_tpu.utils.random import RandomGenerator

    t0, mark, on_tpu = time.time(), compiles.mark(), _on_tpu()
    before = _kernel_counters()["taken"].get("decode", 0)
    RandomGenerator.set_seed(seed)
    model = _build_lm(lm_cfg).evaluate()
    model.ensure_initialized()
    svc = GenerationService(config=GenerationConfig(
        slots=cfg["slots"], max_len=cfg["max_len"],
        length_buckets=cfg["length_buckets"],
        max_new_tokens=cfg["new_tokens"]))
    rng = np.random.RandomState(seed + 1)
    prompts = [rng.randint(0, lm_cfg["data_vocab"], n).astype(np.int32)
               for n in cfg["prompt_lens"]]
    try:
        t_built = time.time()
        svc.load("lm", model)            # warms every ladder rung
        t_loaded = time.time()
        streams = [svc.generate("lm", p, max_new_tokens=cfg["new_tokens"])
                   for p in prompts]
        outs = [np.asarray(s.result(timeout=600)) for s in streams]
        compile_count = svc.compile_count("lm")
        rungs = len(svc.ladder)
        decode_text = None
        if on_tpu:
            # the engine's own enumeration hook hands out the top-rung
            # decode jit: its compiled text must hold the kernel
            sv = svc.registry.current("lm")
            for name, jitted, args in svc.engine.abstract_programs(
                    sv.model, sv.params, sv.state):
                if name.startswith("decode/"):
                    decode_text = jitted.lower(*args).compile().as_text()
    finally:
        svc.shutdown(drain=False)

    for p, out in zip(prompts, outs):
        if len(out) != cfg["new_tokens"]:
            raise AssertionError(
                f"serve_lm: stream of prompt {len(p)} produced "
                f"{len(out)} of {cfg['new_tokens']} tokens")
    if compile_count > 2 * rungs:
        raise AssertionError(
            f"serve_lm: {compile_count} programs for {rungs} buckets "
            f"(bound 2 x buckets)")

    # the reference: ONE plain forward of the model over each stream's
    # prompt + generated tokens (causal, so right-padding to a common
    # length changes nothing to the left of it)
    params, state = model.get_parameters(), model.get_state()
    width = max(len(p) + len(o) for p, o in zip(prompts, outs))
    rows = np.zeros((len(prompts), width), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows[i, :len(p)] = p
        rows[i, len(p):len(p) + len(o)] = o
    forward = jax.jit(
        lambda pr, st, x: model.apply(pr, st, x, training=False)[0])
    ref = np.asarray(forward(params, state, rows), np.float32)
    if not np.isfinite(ref).all():
        raise AssertionError("serve_lm: reference logits not finite")
    tol = cfg["tolerance"]
    compared = near_ties = 0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        for j, tok in enumerate(o):
            row = ref[i, len(p) + j - 1]
            top2 = np.partition(row, -2)[-2:]
            if top2[1] - top2[0] > tol:
                # decisive position: the served token IS the argmax
                compared += 1
                if int(tok) != int(row.argmax()):
                    raise AssertionError(
                        f"serve_lm: stream {i} token {j}: served "
                        f"{int(tok)}, re-forward says "
                        f"{int(row.argmax())} (margin "
                        f"{float(top2[1] - top2[0]):.4f} > {tol})")
            else:
                # top two within tolerance: either is right, anything
                # further down is not
                near_ties += 1
                if row.max() - row[int(tok)] > tol:
                    raise AssertionError(
                        f"serve_lm: stream {i} token {j}: served "
                        f"{int(tok)} is {float(row.max() - row[tok]):.4f}"
                        f" below the re-forward's best (tolerance {tol})")
    if compared < near_ties:
        raise AssertionError(
            f"serve_lm: only {compared} decisive positions against "
            f"{near_ties} near-ties — the comparison proves nothing")

    taken = _kernel_counters()["taken"].get("decode", 0) - before
    if on_tpu:
        if kernels.interpret_mode():
            raise AssertionError("serve_lm: kernels resolved to the "
                                 "pallas interpreter on a TPU")
        if taken <= 0:
            raise AssertionError("serve_lm: the decode kernel was never "
                                 "taken at the default TPU policy")
        if decode_text is None or "tpu_custom_call" not in decode_text:
            raise AssertionError("serve_lm: the compiled decode program "
                                 "holds no tpu_custom_call")
    widths = _lm_widths(lm_cfg)
    widths.update(slots=cfg["slots"], max_len=cfg["max_len"],
                  buckets=list(svc.ladder), kv="float32")
    # load() is build + compile by construction
    return _facts("serve_lm", widths, t0, t_built, compiles, mark,
                  setup_end=t_loaded, requests=len(prompts),
                  prompt_lens=list(cfg["prompt_lens"]),
                  tokens_produced=int(sum(len(o) for o in outs)),
                  first_tokens=[int(o[0]) for o in outs],
                  engine_programs=compile_count,
                  engine_program_bound=2 * rungs,
                  decode_kernel_taken=taken,
                  interpret_mode=bool(kernels.interpret_mode()),
                  decode_has_tpu_custom_call=(
                      None if decode_text is None
                      else "tpu_custom_call" in decode_text),
                  reference={"tolerance": tol,
                             "decisive_positions_equal": compared,
                             "near_ties_within_tolerance": near_ties})


def train_lm_mesh(lm_cfg, cfg, seed, compiles):
    """The path across chips: ``DistriOptimizer`` on a ``[n]`` data mesh
    with ZeRO-2, against the same steps on one device of this process."""
    import jax
    import numpy as np

    import bigdl_tpu.nn as nn
    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu.analysis.hlo import (collective_counts,
                                        reduce_scatter_evidence)
    from bigdl_tpu.optim import Adam, LocalOptimizer, max_iteration
    from bigdl_tpu.optim.optimizer import (DistriOptimizer,
                                           build_train_step)
    from bigdl_tpu.parallel import ZeroConfig, make_mesh
    from bigdl_tpu.precision import PrecisionPolicy
    from bigdl_tpu.utils.random import RandomGenerator

    n = cfg["chips"]
    t0, mark = time.time(), compiles.mark()
    devices = jax.devices()[:n]
    mesh = make_mesh([n], ["data"], devices)
    zero = ZeroConfig(stage=2)
    rows = cfg["steps"] * lm_cfg["batch"]
    criterion = nn.SequenceCrossEntropyCriterion()

    def run(make_opt):
        RandomGenerator.set_seed(seed)
        model = _build_lm(lm_cfg, cfg["layers"])
        losses = _Losses()
        opt = make_opt(model, _lm_dataset(lm_cfg, seed, rows))
        opt.set_optim_method(Adam(learning_rate=cfg["lr"]))
        opt.set_end_when(max_iteration(cfg["steps"]))
        opt.set_precision("bf16_mixed")
        opt.set_train_summary(losses)
        opt.optimize()
        return opt, model, losses.values

    t_built = time.time()
    opt, model, mesh_losses = run(
        lambda m, ds: DistriOptimizer(
            m, ds, criterion, batch_size=lm_cfg["batch"],
            mesh=mesh).set_zero(zero))
    per_chip = {k: telemetry.gauge(
        f"train/memory/{k}_bytes_per_chip").value()
        for k in ("params", "opt_state")}
    _, _, one_losses = run(
        lambda m, ds: LocalOptimizer(m, ds, criterion,
                                     batch_size=lm_cfg["batch"]))

    _check_losses("train_lm_mesh", mesh_losses, cfg["steps"])
    worst = max(abs(a - b) for a, b in zip(mesh_losses, one_losses))
    if len(one_losses) != cfg["steps"] or worst > cfg["loss_tolerance"]:
        raise AssertionError(
            f"train_lm_mesh: mesh losses {mesh_losses} and one-device "
            f"losses {one_losses} differ by {worst} "
            f"(tolerance {cfg['loss_tolerance']})")

    # Is the work really spread? Ask the Optimizer's own placement
    # helpers — the calls optimize() makes — for the arrays it feeds the
    # step, then compile that very step and read its collectives.
    policy = PrecisionPolicy.named("bf16_mixed")
    params = opt._put_params(model.get_parameters())
    opt_state = opt._put_opt_state(
        opt.optim_method.init_state(model.get_parameters()))
    batch = next(iter(opt.dataset.data(train=False)))
    inp, tgt = opt._prep_io(batch)

    def spread(tree):
        leaves = [a for a in jax.tree.leaves(tree) if a.size >= n]
        devs = {s.device for a in leaves for s in a.addressable_shards}
        whole = sum(a.nbytes for a in leaves)
        one = sum(s.data.nbytes for a in leaves
                  for s in a.addressable_shards
                  if s.device == devices[0])
        return devs, whole, one

    p_devs, p_whole, p_one = spread(params)
    o_devs, o_whole, o_one = spread(opt_state)
    b_devs, b_whole, b_one = spread(inp)
    for what, devs in (("parameter", p_devs), ("optimizer-state", o_devs),
                       ("batch", b_devs)):
        if devs != set(devices):
            raise AssertionError(
                f"train_lm_mesh: {what} shards sit on "
                f"{sorted(d.id for d in devs)}, not on all {n} devices")
    if not o_one * n <= o_whole * 1.05:
        raise AssertionError(
            f"train_lm_mesh: optimizer state holds {o_one} bytes on one "
            f"device of {o_whole} — not about 1/{n} under ZeRO-2")
    if b_one * n != b_whole:
        raise AssertionError(
            f"train_lm_mesh: the batch is not split over 'data' "
            f"({b_one} of {b_whole} bytes on one device)")
    if not per_chip["opt_state"] * n <= o_whole * 1.05:
        raise AssertionError(
            f"train_lm_mesh: the run's own opt_state gauge says "
            f"{per_chip['opt_state']} bytes per chip of {o_whole}")

    step = build_train_step(model, criterion, opt.optim_method,
                            zero=zero, mesh=mesh, precision=policy)
    lr = np.float32(cfg["lr"])
    compiled = step.lower(params, opt_state,
                          opt._put_replicated(model.get_state()),
                          RandomGenerator.next_key(), lr, inp,
                          tgt).compile()
    counts = collective_counts(compiled)
    # the gradient reduce-scatter and the parameter all-gather ARE
    # ZeRO-2; the TPU compiler writes the first as a literal
    # reduce-scatter (the CPU one, in rehearsal, as all-reduce +
    # dynamic-slice — the repo's evidence rule knows both)
    scattered = counts["reduce-scatter"]["total"] > 0 if _on_tpu() \
        else reduce_scatter_evidence(counts)
    if not scattered or counts["all-gather"]["total"] == 0:
        raise AssertionError(
            f"train_lm_mesh: the compiled step lacks a reduce-scatter "
            f"or an all-gather (collectives: {counts})")

    widths = _lm_widths(lm_cfg, cfg["layers"])
    widths.update(precision="bf16_mixed", optim="Adam", zero_stage=2,
                  mesh={"data": n})
    return _facts(
        "train_lm_mesh", widths, t0, t_built, compiles, mark,
        steps=cfg["steps"], losses_mesh=mesh_losses,
        losses_one_device=one_losses, loss_max_abs_diff=worst,
        loss_tolerance=cfg["loss_tolerance"],
        devices_holding_shards=len(o_devs),
        param_bytes={"whole": p_whole, "on_one_device": p_one},
        opt_state_bytes={"whole": o_whole, "on_one_device": o_one,
                         "gauge_per_chip": per_chip["opt_state"]},
        batch_bytes={"whole": b_whole, "on_one_device": b_one},
        collectives=counts)


# --------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the path across chips and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()        # first: which machine is this?
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not a "
              "TPU — nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from bigdl_tpu.utils.engine import enable_compile_cache

    emit({"phase": "start", "compile_cache_dir": enable_compile_cache(),
          "jax": jax.__version__, "device_kind": dev.device_kind,
          "devices": len(devices), "chips": args.chips})
    compiles = _Compiles()
    if args.chips == 4:
        emit(train_lm_mesh(LM, MESH, args.seed, compiles))
    else:
        emit(train_resnet50(RESNET, args.seed, compiles))
        emit(train_lm(LM, args.seed, compiles))
        emit(serve_lm(LM, SERVE, args.seed, compiles))
    native = sys.modules.get("bigdl_tpu.native")
    if native is not None and native._lib is not None:
        # the .so is not in git: a fresh clone must not need it here
        raise AssertionError("the smoke's path loaded the native library")
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": args.chips}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
