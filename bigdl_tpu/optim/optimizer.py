"""Optimizer — the training runtime (BigDL optim/Optimizer.scala:42,
LocalOptimizer.scala:41, DistriOptimizer.scala:88-421).

TPU-first translation of the reference's two-level data parallelism:

- intra-node thread clones (DistriOptimizer.scala:116-118) -> the per-chip
  batch dimension; XLA vectorizes.
- AllReduceParameter's reduce-scatter/optimizer/all-gather over Spark
  BlockManager (AllReduceParameter.scala:214-303) -> ONE compiled step:
  forward + backward + gradient mean over the `data` mesh axis + optimizer
  update, jitted together so XLA fuses the collective into the backward pass
  and overlaps it with compute over ICI.
- The Spark driver loop (iteration barrier, triggers, metrics, checkpoint)
  -> this host Python loop.

The straggler-dropping machinery (DistriOptimizer.scala:337-365) has no TPU
equivalent — a synchronous pod has no stragglers — so ``set_drop_module_
property`` is accepted as a documented no-op for API parity. The
retry-from-checkpoint loop (DistriOptimizer.scala:789-855) IS kept.
"""
from __future__ import annotations

import logging
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu import faults
from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.dataset.prefetch import batch_signature, stack_minibatches
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.nn.module import AUX_LOSS_KEY, Criterion, Module
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random import RandomGenerator

logger = logging.getLogger("bigdl_tpu")

# process-wide training throughput counters (telemetry registry; the
# per-run phase times ride the Metrics histograms below)
_STEP_COUNT = telemetry.counter("train/optimizer/steps",
                                "optimizer steps completed")
_RECORD_COUNT = telemetry.counter("train/optimizer/records",
                                  "training records processed")
_RECOVERIES = telemetry.counter(
    "train/optimizer/recoveries",
    "retry-from-checkpoint recoveries performed by optimize()")
_WINDOW_GAP = telemetry.histogram(
    "train/optimizer/window_gap_ms",
    "host ms from one fused window's block_until_ready returning to the "
    "next window's dispatch returning, within one optimize() call")
_ATTN_IN_KERNEL = telemetry.histogram(
    "train/optimizer/attn_in_kernel_share",
    "of the attention calls in one traced window program, the share "
    "the flash dispatch took as a fused kernel (taken over taken + "
    "declined); one observation a trace that asked the dispatch")
# mixed-precision observability (Optimizer.set_precision): the loss
# scale and cumulative skipped steps are read off the (already-fetched)
# scaler state once per host sync; the policy/bytes gauges are set once
# at state layout
_LOSS_SCALE = telemetry.gauge(
    "train/precision/loss_scale",
    "current dynamic loss scale (1.0 when the policy does not scale)")
_SKIPPED_STEPS = telemetry.gauge(
    "train/precision/skipped_steps",
    "cumulative optimizer steps skipped on non-finite gradients")
_POLICY_INFO = telemetry.gauge(
    "train/precision/policy_info",
    "active precision policy (labels carry the dtypes); value is 1")
_PARAMS_F32_BYTES = telemetry.gauge(
    "train/precision/params_f32_bytes_per_chip",
    "per-chip param bytes the same layout would cost at float32 — the "
    "'before' against train/memory/params_bytes_per_chip")
_OPT_F32_BYTES = telemetry.gauge(
    "train/precision/opt_state_f32_bytes_per_chip",
    "per-chip optimizer-state bytes at float32 — the 'before' against "
    "train/memory/opt_state_bytes_per_chip")


class Metrics:
    """Named counters (optim/Metrics.scala:31) — host dict, no Spark
    accumulators needed.

    Migrated onto the telemetry registry: every ``add`` also lands in a
    ``train/optimizer/<metric>`` histogram, so the TensorBoard /
    Prometheus / JSONL exporters and ``tools.diagnose`` see the SAME
    numbers ``summary()`` prints. The local per-run list (and the
    ``summary()`` format) are unchanged — this class stays the per-run
    view, the registry the process-wide one."""

    def __init__(self, registry=None):
        self.values: Dict[str, List[float]] = {}
        self._registry = registry if registry is not None \
            else telemetry.registry()
        self._instruments: Dict[str, Any] = {}

    @staticmethod
    def _slug(name: str) -> str:
        """'data time' -> 'data_time' (the family/component/metric
        charset the telemetry-audit gate enforces)."""
        import re
        return re.sub(r"[^a-z0-9_]+", "_", name.lower()).strip("_")

    def add(self, name: str, value: float):
        self.values.setdefault(name, []).append(value)
        h = self._instruments.get(name)
        if h is None:
            h = self._registry.histogram(
                f"train/optimizer/{self._slug(name)}",
                f"Optimizer Metrics series {name!r} (seconds)")
            self._instruments[name] = h
        h.observe(value)

    def summary(self) -> str:
        parts = []
        for k, v in self.values.items():
            parts.append(f"{k}: avg {np.mean(v):.4f}s over {len(v)}")
        return "; ".join(parts)


def _collect_aux_losses(state_tree):
    """Sum every reserved ``AUX_LOSS_KEY`` leaf in a model-state tree (MoE
    load-balance terms, nn/moe.py). Only the dunder-namespaced key joins
    the objective — a user state entry named "aux_loss" does not.
    Differentiable — called inside loss_fn."""
    total = 0.0
    flat, _ = jax.tree_util.tree_flatten_with_path(state_tree)
    for path, leaf in flat:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if keys and keys[-1] == AUX_LOSS_KEY:
            total = total + leaf
    return total


def _fetch_replicated(x) -> np.ndarray:
    """Host-fetch a fully replicated device value, multi-host safe: a
    replicated array spanning non-addressable devices is not plain-
    readable, but any addressable shard holds the complete value."""
    try:
        return np.asarray(x)
    except Exception:
        return np.asarray(jax.device_get(x.addressable_shards[0].data))


def _to_scalar(x) -> float:
    """float(loss) that also works on multi-host global arrays."""
    return float(_fetch_replicated(x))


def _losses_list(losses, k: int):
    """The length-k loss vector a fused window returns, as host floats —
    ONE fetch per window."""
    return [float(v) for v in _fetch_replicated(losses).reshape(-1)[:k]]


def _record_scaler_gauges(opt_state):
    """Refresh the loss-scale/skipped-steps gauges from the (already
    synchronized) scaler state riding the optimizer-state tree — one
    cheap host read per sync, no extra device fetch ordering."""
    from bigdl_tpu.precision import SCALER_KEY
    ss = opt_state.get(SCALER_KEY) if isinstance(opt_state, dict) else None
    if ss is None:
        return
    _LOSS_SCALE.set(float(_fetch_replicated(ss["scale"])))
    _SKIPPED_STEPS.set(float(_fetch_replicated(ss["skipped"])))


def _window_stackable(batch: MiniBatch) -> bool:
    """True when every leaf of the MiniBatch is a dense HOST array —
    the only thing ``np.stack`` window stacking supports. Sparse COO
    batches keep the per-step path, and so do device-resident leaves
    (e.g. a ``device_prefetch``-staged pipeline): host-stacking those
    would silently round-trip device->host->device with a blocking
    sync per batch — the inverse of what windowing buys."""
    from bigdl_tpu.dataset.sample import HostBatchedCOO, SparseFeature

    def ok(x):
        if x is None:
            return True
        if isinstance(x, (list, tuple)):
            return all(ok(e) for e in x)
        return not isinstance(x, (HostBatchedCOO, SparseFeature,
                                  jax.Array))
    return ok(batch.input) and ok(batch.target)


def _allreduce_result(r):
    """Sum a ValidationResult across processes: gather (numerator,
    count) and rebuild, so every host reports the GLOBAL score."""
    from jax.experimental import multihost_utils

    from bigdl_tpu.optim.validation import AccuracyResult, LossResult

    value, count = r.result()
    arr = multihost_utils.process_allgather(
        np.array([value * count, count], np.float64))
    num, cnt = np.asarray(arr).reshape(-1, 2).sum(0)
    if isinstance(r, AccuracyResult):
        return AccuracyResult(int(round(num)), int(cnt))
    if isinstance(r, LossResult):
        return LossResult(float(num), int(cnt))
    return r  # unknown result type: keep the local value


def _local_rows(x) -> np.ndarray:
    """Materialize a (possibly multi-host, batch-sharded) array's rows
    held by THIS process, in batch order; plain arrays pass through."""
    try:
        return np.asarray(x)
    except Exception:
        shards = sorted(x.addressable_shards,
                        key=lambda s: (s.index[0].start or 0))
        seen, parts = set(), []
        for s in shards:  # dedupe replicated copies across local devices
            key = tuple((sl.start, sl.stop) for sl in s.index)
            if key in seen:
                continue
            seen.add(key)
            # scoring-path row materialization, one shard per local
            # device (bounded, not the checkpoint sweep)
            parts.append(np.asarray(jax.device_get(s.data)))  # bigdl: disable=blocking-copy-in-checkpoint
        return np.concatenate(parts)


def train_program_name(module: Module, suffix: str = "step") -> str:
    """The program-profile name a module's compiled train/eval/window
    program registers under (``telemetry.programs``) — ONE naming rule
    so the build sites and the rate-recording sync points agree. Uses
    the module's explicit ``set_name`` when given (stable across
    processes), else its class name."""
    name = getattr(module, "_name", None) or type(module).__name__
    return f"train/{name}/{suffix}"


def _batch_rows(inputs) -> int:
    """Leading-dim row count of a step's inputs (first leaf of a
    Table/list input) — the item basis program-profile MFU uses."""
    leaves = jax.tree_util.tree_leaves(inputs)
    return int(leaves[0].shape[0]) if leaves else 1


def build_train_step(module: Module, criterion: Criterion,
                     optim_method: OptimMethod,
                     aux_loss_weight: float = 0.01,
                     gradient_clip=None, zero=None, mesh=None,
                     sharding_rules=None, precision=None,
                     loss_scaler=None, seq_parallel=None):
    """The compiled hot path: loss + grad + update in one jit.

    Gradient normalization matches the reference (grads averaged over the
    global batch, DistriOptimizer.scala:296-310 divides by numFinished);
    param_scales implements layer-wise scaling / freeze. Auxiliary losses
    the model emits through its state (MoE load balancing) join the
    objective with weight ``aux_loss_weight`` so they actually produce
    router gradients. ``gradient_clip`` = ("constant", min, max) or
    ("l2norm", max_norm) applies the reference's gradient clipping
    (Optimizer.scala setConstantGradientClipping /
    setGradientClippingByl2Norm) to the aggregated gradients before the
    update — the global-L2 form is what keeps edge-of-stability recipes
    (classic PTB LSTM at lr 1.0) convergent.

    ``zero`` (a ``parallel.zero.ZeroConfig`` with ``mesh``, and the
    TP ``sharding_rules`` when params are rule-sharded) turns the
    update into its weight-update-sharded form: stage >= 2 constrains
    the fresh gradients to the 1/n data-axis layout (XLA lowers the
    gradient all-reduce to a reduce-scatter), the optimizer math then
    runs on shards, and the new params are constrained back to the
    at-rest layout — replicated/TP for stage <= 2 (the single
    all-gather), still sharded for stage 3 (forward/backward gather
    each layer just in time). Every new optimizer-state leaf is pinned
    to an explicit sharding so donated-jit out-shardings can never
    silently re-replicate a shard after the first update.

    ``precision`` (a ``precision.PrecisionPolicy``; None reads the
    legacy ``Engine`` dtype knobs) compiles the mixed-precision casts
    into the step: params/inputs cast to ``compute_dtype`` on entry,
    gradients come back in compute dtype (so a ZeRO reduce-scatter
    moves low-precision bytes), are cast to ``accum_dtype`` (f32) and
    unscaled, and the update runs on the f32 weights — the params tree
    itself when ``param_dtype`` is f32, else the f32 MASTER COPY kept
    in the optimizer state under ``precision.MASTER_KEY``. With
    ``loss_scaler`` (auto-created for f16 policies) the loss is scaled
    before ``jax.grad`` and a step with non-finite gradients is
    SKIPPED: params/optimizer state keep their previous values and the
    scaler backs off — all inside the compiled step, so the state
    machine rides the windowed scan carry bit-consistently.

    ``seq_parallel`` (a ``parallel.sequence.SeqParallelConfig``)
    installs sequence parallelism as a TRAIN-STEP policy: the model
    apply is traced under ``use_sequence_parallel``, so every
    ``MultiHeadAttention`` without an explicit ``ring_axis`` runs the
    ring/Ulysses kernel over the config's mesh axis. Like ``zero``,
    the policy no-ops quietly (dense attention, degree gauge reads 1)
    when it cannot apply — no mesh, or the axis missing/size-1. The SP collectives trace INSIDE the step,
    so under ``set_steps_per_sync(K)`` they land inside the scan body
    and the windowed dispatch boundary stays collective-free; ZeRO
    composes orthogonally (weights shard over the data axis, attention
    activations over the sequence axis).
    """
    if gradient_clip is not None and gradient_clip[0] not in (
            "constant", "l2norm"):
        raise ValueError(
            f"gradient_clip kind must be 'constant' or 'l2norm', got "
            f"{gradient_clip[0]!r}")
    zero_active = zero is not None and zero.active_on(mesh)
    import contextlib
    sp_scope = contextlib.nullcontext
    if seq_parallel is not None:
        from bigdl_tpu.parallel.sequence import (record_degree,
                                                 use_sequence_parallel)
        if seq_parallel.active_on(mesh):
            sp_scope = lambda: use_sequence_parallel(seq_parallel)
            record_degree(seq_parallel.degree())
        else:
            record_degree(1)
    from bigdl_tpu.precision import (MASTER_KEY, SCALER_KEY,
                                     DynamicLossScaler, PrecisionPolicy)
    policy = precision if precision is not None \
        else PrecisionPolicy.from_engine()
    scaler = None
    if policy.needs_loss_scaling:
        scaler = loss_scaler if loss_scaler is not None \
            else DynamicLossScaler()

    def step(params, opt_state, model_state, rng, lr, inputs, targets):
        scaler_state = opt_state.get(SCALER_KEY) \
            if isinstance(opt_state, dict) else None
        master = opt_state.get(MASTER_KEY) \
            if isinstance(opt_state, dict) else None
        inner_opt = {k: v for k, v in opt_state.items()
                     if k not in (SCALER_KEY, MASTER_KEY)} \
            if isinstance(opt_state, dict) else opt_state
        if scaler is not None and scaler_state is None:
            raise ValueError(
                "loss-scaling policy needs the scaler state in "
                "opt_state[precision.SCALER_KEY]; seed it with "
                "scaler.init_state() (Optimizer.set_precision does "
                "this automatically)")
        if policy.needs_master and master is None:
            raise ValueError(
                "low-precision param_dtype needs the f32 master copy "
                "in opt_state[precision.MASTER_KEY] "
                "(Optimizer.set_precision seeds it automatically)")

        def loss_fn(p_c):
            # cast-on-entry at the step boundary: fwd/bwd run in
            # compute_dtype (bf16 on TPU — the analogue of the
            # reference's fp16 gradient compression,
            # FP16CompressedTensor.scala); norm stats/softmax/loss stay
            # f32 inside the layers; cast-on-exit hands the loss an
            # output_dtype (f32) tensor.
            x_c = policy.cast_to_compute(inputs)
            # the SP policy is installed for the TRACE of the apply —
            # attention modules adopt it; once compiled, the routing is
            # baked in (toggling later never mutates this program)
            with sp_scope():
                out, new_mstate = module.apply(p_c, model_state, x_c,
                                               training=True, rng=rng)
            out = policy.cast_output(out)
            with jax.named_scope("loss"):
                loss = criterion.apply(out, targets)
            reg = module.regularization_loss(p_c)
            aux = _collect_aux_losses(new_mstate)
            total = loss + reg + aux_loss_weight * aux
            if scaler is not None:
                total = scaler.scale_loss(total, scaler_state)
            return total, (new_mstate, loss)

        # grads are taken wrt the COMPUTE-dtype params, so they arrive
        # in compute dtype — under ZeRO >= 2 the reduce-scatter below
        # therefore moves bf16/f16 bytes, half the f32 wire traffic
        p_c = policy.cast_to_compute(params)
        grads, (new_mstate, data_loss) = jax.grad(
            loss_fn, has_aux=True)(p_c)
        if zero_active and zero.stage >= 2:
            # the reduce-scatter point (arXiv:2004.13336): constrained
            # HERE, everything downstream — scaling, clipping, the
            # optimizer math — runs on 1/n shards
            from bigdl_tpu.parallel.zero import constrain_zero
            grads = constrain_zero(grads, mesh, zero, sharding_rules)
        grads = policy.cast_to_accum(grads)
        finite = None
        if scaler is not None:
            grads = scaler.unscale(grads, scaler_state)
            # the skip-step probe: checked AFTER unscaling so an
            # overflowed-scale inf is caught even when the raw f16
            # grads were finite
            finite = scaler.all_finite(grads)
        scales = module.param_scales(params)
        if any(s != 1.0 for s in jax.tree.leaves(scales)):
            grads = jax.tree.map(lambda g, s: g * s, grads, scales)
        if gradient_clip is not None:
            if gradient_clip[0] == "constant":
                lo, hi = gradient_clip[1], gradient_clip[2]
                grads = jax.tree.map(lambda g: jnp.clip(g, lo, hi),
                                     grads)
            else:  # global L2 norm accumulates f32 (sanctioned island)
                nrm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))  # bigdl: disable=implicit-upcast-in-trace
                    for g in jax.tree.leaves(grads)))
                scale = jnp.minimum(
                    1.0, gradient_clip[1] / jnp.maximum(nrm, 1e-12))
                grads = jax.tree.map(
                    lambda g: g * scale.astype(g.dtype), grads)
        # master-copy update: the f32 weights are the params tree when
        # param_dtype is f32, else the MASTER_KEY copy; low-precision
        # at-rest params are the master cast down after the update
        update_base = master if master is not None else params
        if master is None and policy.param_dtype != policy.accum_dtype:
            # no-master low-precision policy (the legacy Engine
            # default-dtype path): the update runs in param dtype,
            # exactly the pre-policy program
            from bigdl_tpu.precision import cast_floating
            grads = cast_floating(grads, policy.param_dtype)
        with jax.named_scope("optim_update"):
            new_base, new_inner = optim_method.update(grads, inner_opt,
                                                      update_base, lr)
        if master is not None:
            new_master = new_base
            new_params = policy.cast_to_param(new_master)
        else:
            new_master = None
            new_params = new_base
        if finite is not None:
            # skip-step select: a non-finite gradient leaves params,
            # master and EVERY optimizer buffer (moments, Adam's t) at
            # their previous values; only the scaler state advances
            def keep_old(new, old):
                return jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new, old)
            new_params = keep_old(new_params, params)
            new_inner = keep_old(new_inner, inner_opt)
            if new_master is not None:
                new_master = keep_old(new_master, master)
        new_opt = dict(new_inner) if isinstance(new_inner, dict) \
            else new_inner
        if new_master is not None:
            new_opt[MASTER_KEY] = new_master
        if scaler is not None:
            new_opt[SCALER_KEY] = scaler.next_state(scaler_state, finite)
        if zero_active:
            from bigdl_tpu.parallel.zero import (constrain_base,
                                                 constrain_zero)
            # pin EVERY fresh opt-state leaf (moments AND step
            # counters — and the f32 master copy, which shards exactly
            # like the optimizer state it lives in) to its explicit
            # sharded layout
            new_opt = constrain_zero(new_opt, mesh, zero, sharding_rules)
            if zero.stage == 3:
                # params stay sharded at rest; each layer all-gathers
                # just-in-time at its use inside the next fwd/bwd
                new_params = constrain_zero(new_params, mesh, zero,
                                            sharding_rules)
            else:
                # THE one params all-gather of the classic partitioned
                # parameter server (AllReduceParameter.scala:214-303)
                new_params = constrain_base(new_params, mesh,
                                            sharding_rules)
        return new_params, new_opt, new_mstate, data_loss

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    # program-profile hook (telemetry.programs; one flag check when
    # profiling is off): the standalone step registers its XLA
    # cost/memory analysis under train/program/* on first execution
    return telemetry.programs.maybe_wrap_jitted(
        train_program_name(module), "train", jitted,
        donation="params,opt_state,model_state",
        items_for=lambda args, kwargs: _batch_rows(args[5]))


def build_eval_step(module: Module, out_sharding=None, precision=None):
    """``out_sharding`` pins the output layout (batch-sharded over the
    data axis on a mesh): GSPMD is otherwise free to replicate the
    output, and multi-host scoring slices each process's LOCAL rows —
    those must be the rows that process fed. ``precision`` (a non-noop
    ``PrecisionPolicy``) runs the forward in compute dtype with the
    output cast back — validation scores the precision that actually
    trains/serves."""
    if precision is not None and not precision.is_noop:
        def eval_step(params, model_state, inputs):
            out, _ = precision.apply_module(module, params, model_state,
                                            inputs, training=False)
            return out
    else:
        def eval_step(params, model_state, inputs):
            out, _ = module.apply(params, model_state, inputs,
                                  training=False)
            return out

    return telemetry.programs.maybe_wrap_jitted(
        train_program_name(module, "eval"), "train",
        jax.jit(eval_step, out_shardings=out_sharding),
        items_for=lambda args, kwargs: _batch_rows(args[2]))


def _flash_dispatches():
    """(taken, declined) flash dispatches on this thread so far."""
    from bigdl_tpu.kernels import dispatch
    return (dispatch.taken_in_thread("flash"),
            dispatch.declined_in_thread("flash"))


def make_host_window(step):
    """The K-step fused host-feed window over ``step`` — ONE
    ``lax.scan`` dispatch per window, exactly the program
    ``set_steps_per_sync`` compiles: ``(params, opt_state, model_state,
    keys[K,...], lrs[K], xs[K,B,...], ys[K,B,...]) -> (params,
    opt_state, model_state, losses[K])`` with the carry donated.

    Factored out of the driver loop so the static program verifier
    (``analysis.programs``) lowers the very artifact the Optimizer
    dispatches — the windowed-HLO contracts (zero entry collectives,
    donation aliased through the scan carry) are checked on the real
    program, not a test replica."""
    def _window_host(p, o, m, keys, lrs, xs, ys):
        # scan over the [K, B, ...] stacked device buffer
        # (dataset.prefetch.stack_windows layout)
        def body(carry, sl):
            p, o, m = carry
            key, lr, x, yb = sl
            p, o, m, loss = step(p, o, m, key, lr, x, yb)
            return (p, o, m), loss
        (p, o, m), losses = jax.lax.scan(
            body, (p, o, m), (keys, lrs, xs, ys))
        return p, o, m, losses

    return jax.jit(_window_host, donate_argnums=(0, 1, 2))


class Optimizer:
    """Driver loop + fluent config surface (optim/Optimizer.scala:42).

    One class covers the reference's LocalOptimizer (single chip) and
    DistriOptimizer (multi-chip): the difference is only the mesh the batch
    is laid out over.
    """

    def __init__(self, model: Module, dataset: AbstractDataSet,
                 criterion: Criterion, batch_size: int = 32,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 data_axis: str = "data",
                 sharding_rules=None, zero1: bool = False):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.batch_size = batch_size
        self.mesh = mesh
        self.data_axis = data_axis
        # tensor/expert-parallel param layout (parallel/tp.py rules);
        # None = fully replicated params (pure DP, the reference's layout)
        self.sharding_rules = sharding_rules
        # ZeRO-1: optimizer state sharded over the data axis — the direct
        # analogue of the reference's per-node OWNED weight shard running
        # the OptimMethod (AllReduceParameter.scala:214-303). The bool is
        # the original knob; stages 2/3 (gradient reduce-scatter /
        # params-sharded-at-rest) arrive through set_zero(ZeroConfig).
        self.zero1 = zero1
        self.zero_config = None
        if zero1:
            from bigdl_tpu.parallel.zero import ZeroConfig
            self.zero_config = ZeroConfig(stage=1, data_axis=data_axis)
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = None
        # validation
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset: Optional[AbstractDataSet] = None
        self.validation_methods: Optional[List[ValidationMethod]] = None
        # checkpoint
        self.checkpoint_trigger: Optional[Trigger] = None
        self.checkpoint_path: Optional[str] = None
        self.is_overwrite = False
        # elastic checkpointing (set_checkpoint keep_last/async_write):
        # retention depth, per-shard async writer, and the SIGTERM
        # grace handler (set_preemption_handler / BIGDL_PREEMPT_GRACE)
        self.checkpoint_keep_last: Optional[int] = None
        self.checkpoint_async = False
        self._ckpt_writer = None
        self._preempt_grace = False
        self._grace = None
        # summaries
        self.train_summary = None
        self.validation_summary = None
        # failure retry (DistriOptimizer.scala:789-855)
        # multi-host fixed-batch guard, tracked PER STREAM: validation may
        # legitimately use a different batch size than training
        self._mp_batch_rows: Dict[str, int] = {}
        self._stream = "train"
        self.retry_times = int(os.environ.get("BIGDL_FAILURE_RETRY_TIMES", 5))
        # base of the exponential backoff between retries: the first
        # retry sleeps equal-jittered [base/2, base), doubling per
        # attempt; BIGDL_FAILURE_RETRY_MAX_INTERVAL caps growth
        self.retry_interval_s = float(
            os.environ.get("BIGDL_FAILURE_RETRY_INTERVAL", 1.0))
        self.retry_max_interval_s = float(
            os.environ.get("BIGDL_FAILURE_RETRY_MAX_INTERVAL", 30.0))
        self.metrics = Metrics()
        # windowed step driver (set_steps_per_sync): K train steps fused
        # into one lax.scan dispatch, host syncs only at window
        # boundaries. 1 = the classic per-step loop.
        self.steps_per_sync = 1
        # mixed-precision policy (set_precision); None = the legacy
        # Engine dtype knobs (f32 unless configured)
        self._precision = None
        self._loss_scaler = None
        # sequence-parallel training policy (set_sequence_parallel);
        # None = dense attention
        self._seq_parallel = None
        # gradient clipping (Optimizer.scala setConstantGradientClipping
        # / setGradientClippingByl2Norm); None = off
        self._gradient_clip = None
        # opt-in pre-flight shape check (analysis/shapecheck.py); None =
        # off. Set via set_preflight_spec.
        self._preflight_spec = None
        # single-slot (dataset, jitted fn) cache for device-cached
        # validation — replacing the validation dataset must free the
        # old split's HBM-resident arrays, not pin them forever
        self._dc_eval: Optional[tuple] = None
        self.driver_state: Dict[str, Any] = {"epoch": 1, "neval": 1,
                                             "recordsProcessedThisEpoch": 0}
        self._drop_percentage = 0.0  # accepted, no-op on TPU

    # -- fluent config (Optimizer.scala:120-343) ---------------------------
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset: AbstractDataSet,
                       methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None) -> "Optimizer":
        # a DeviceCachedArrayDataSet bakes its batch size into the
        # compiled sample+forward — a conflicting request would be
        # silently dropped, so reject it up front, BEFORE any state
        # mutation (a caller catching the error keeps its old config)
        ds_bs = getattr(dataset, "batch_size", None)
        if batch_size is not None and ds_bs is not None \
                and hasattr(dataset, "eval_batch_fn_on") \
                and batch_size != ds_bs:
            raise ValueError(
                f"device-cached validation runs at the dataset's own "
                f"batch_size={ds_bs}; got conflicting batch_size="
                f"{batch_size} (omit it or rebuild the dataset)")
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(methods)
        self._val_batch_size = batch_size or self.batch_size
        self._dc_eval = None  # new dataset: drop the old compiled slot
        return self

    def set_checkpoint(self, path: str, trigger: Trigger, *,
                       keep_last: Optional[int] = None,
                       async_write: bool = False) -> "Optimizer":
        """Checkpoint into ``path`` whenever ``trigger`` fires
        (Optimizer.scala:207 setCheckpoint), with the elastic
        extensions:

        ``async_write=True`` switches to the per-shard format-3 writer
        (``bigdl_tpu.elastic``): the step-loop stall shrinks to the
        device->host snapshot copy and the serialize/hash/commit tail
        runs on a background thread behind a barriered two-phase
        MANIFEST — a not-yet-committed checkpoint is never visible to
        ``find_latest_checkpoint``. Local/POSIX paths only.

        ``keep_last=N`` prunes older COMMITTED checkpoints beyond the
        newest N after each save — never the newest, never a
        ``*.corrupt-*`` quarantine, and safe concurrently with an
        in-flight async write."""
        from bigdl_tpu.utils import file_io
        if async_write and file_io.is_remote(path):
            raise ValueError(
                "async_write stages + renames on a local filesystem; "
                "remote checkpoint paths keep the sync format-2 writer")
        if keep_last is not None and int(keep_last) < 1:
            raise ValueError(
                f"keep_last must be >= 1, got {keep_last} (the newest "
                "committed checkpoint is never deleted)")
        if keep_last is not None and file_io.is_remote(path):
            raise ValueError(
                "keep_last retention walks + deletes local checkpoint "
                "dirs; on a remote store it would silently do nothing "
                "— manage object-store lifecycle rules instead")
        file_io.makedirs(path)
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_keep_last = None if keep_last is None \
            else int(keep_last)
        self.checkpoint_async = bool(async_write)
        if async_write and self._ckpt_writer is None:
            from bigdl_tpu.elastic import AsyncCheckpointWriter
            self._ckpt_writer = AsyncCheckpointWriter()
        return self

    def set_preemption_handler(self, enabled: bool = True) -> "Optimizer":
        """SIGTERM grace (``bigdl_tpu.elastic.preempt``): when the pod
        scheduler SIGTERMs this process, the step loop drains at the
        next boundary — flushes any in-flight async write, saves an
        EMERGENCY checkpoint synchronously, dumps a flight-recorder
        bundle — and exits through ``elastic.Preempted`` so the gang
        launcher relaunches (possibly at a different world size) and
        resumes from it. Also enabled by ``BIGDL_PREEMPT_GRACE=1``."""
        self._preempt_grace = bool(enabled)
        return self

    def overwrite_checkpoint(self) -> "Optimizer":
        self.is_overwrite = True
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_val_summary(self, summary) -> "Optimizer":
        self.validation_summary = summary
        return self

    def set_model(self, new_model: Module) -> "Optimizer":
        """Swap the model before optimize() (Optimizer.scala:230)."""
        self.model = new_model
        # the device-cached validation slot closed over the OLD model's
        # forward at trace time — drop it or validation would silently
        # score the previous architecture
        self._dc_eval = None
        return self

    def set_state(self, state: Dict[str, Any]) -> "Optimizer":
        """Seed the driver's optimization state — epoch/neval counters
        etc. (Optimizer.scala:240 setState). Counter keys also reach
        the OptimMethod's state so epoch/iteration-driven lr schedules
        start from the seeded position, not epoch 1."""
        self.driver_state.update(dict(state))
        for k in ("epoch", "neval"):
            if k in state:
                self.optim_method.state[k] = state[k]
        return self

    def set_constant_gradient_clipping(self, min_value: float,
                                       max_value: float) -> "Optimizer":
        """Clip every gradient element into [min, max]
        (Optimizer.scala setConstantGradientClipping)."""
        if float(min_value) > float(max_value):
            raise ValueError(
                f"constant gradient clipping needs min <= max, got "
                f"[{min_value}, {max_value}] (jnp.clip would silently "
                "collapse every gradient to max)")
        self._gradient_clip = ("constant", float(min_value),
                               float(max_value))
        return self

    def set_gradient_clipping_by_l2_norm(self,
                                         clip_norm: float) -> "Optimizer":
        """Scale the aggregated gradients so their GLOBAL L2 norm never
        exceeds ``clip_norm`` (Optimizer.scala
        setGradientClippingByl2Norm) — the classic stabilizer for RNN
        recipes at aggressive learning rates."""
        self._gradient_clip = ("l2norm", float(clip_norm))
        return self

    def disable_gradient_clipping(self) -> "Optimizer":
        """Optimizer.scala disableGradientClipping."""
        self._gradient_clip = None
        return self

    def set_steps_per_sync(self, k: int) -> "Optimizer":
        """Fuse up to ``k`` train steps into ONE compiled ``lax.scan``
        program and sync the host only at window boundaries.

        The per-step loop round-trips to the host every iteration
        (fetch the loss, run trigger/metric bookkeeping, dispatch the
        next step), so the device idles in the gaps; with ``k > 1`` the
        whole window runs as one donated jitted dispatch and losses
        come back as a length-``k`` vector fetched once. Driver
        counters (``neval``, ``recordsProcessedThisEpoch``), triggers
        and summaries then REPLAY the ``k`` per-step increments
        host-side after the fetch, so observable semantics match the
        per-step loop; windows flush early at validation / checkpoint /
        end-trigger boundaries, epoch rollovers and shard rotations,
        and the driver falls back to ``k=1`` whenever a trigger depends
        on runtime values (``Loss``/``score``), a trigger's
        dependencies are unknown, or the LR schedule is metric-driven
        (Plateau) — see ``docs/performance.md``. ``Metrics``/telemetry
        are recorded once per window with amortized ``t_data`` /
        ``t_compute`` attribution."""
        k = int(k)
        if k < 1:
            raise ValueError(f"steps_per_sync must be >= 1, got {k}")
        self.steps_per_sync = k
        return self

    def set_zero(self, config) -> "Optimizer":
        """Weight-update sharding policy (``parallel.zero.ZeroConfig``):
        stage 1 shards optimizer state over the data axis, stage 2
        additionally reduce-scatters gradients so each replica updates
        only its 1/n shard before a single params all-gather, stage 3
        keeps params sharded at rest with just-in-time per-layer
        gathers inside forward/backward. Composes with
        ``set_steps_per_sync(K)`` — the donated scan carry holds the
        sharded state and XLA overlaps the collectives with the
        neighbouring steps' compute — and with TP ``sharding_rules``
        (ZeRO shards the dims the rules leave free). A no-op off-mesh
        or when the data axis does not split; pass None (or stage 0)
        to disable. Checkpoints save the gathered, unsharded-equivalent
        state, so a run may resume onto a different stage or mesh
        width. The config's ``data_axis`` is reconciled with this
        Optimizer's own (a mismatched axis would silently deactivate
        the policy — ZeRO only makes sense over the axis the batch and
        gradient reduction shard on)."""
        import dataclasses as _dc

        from bigdl_tpu.parallel.zero import ZeroConfig
        if config is not None and not isinstance(config, ZeroConfig):
            raise TypeError(
                f"set_zero expects a parallel.ZeroConfig or None, got "
                f"{type(config).__name__}")
        if config is not None and config.data_axis != self.data_axis:
            config = _dc.replace(config, data_axis=self.data_axis)
        self.zero_config = config if config is not None \
            and config.stage > 0 else None
        self.zero1 = self.zero_config is not None \
            and self.zero_config.stage == 1
        return self

    def set_precision(self, policy, scaler=None) -> "Optimizer":
        """Mixed-precision policy for this run
        (``precision.PrecisionPolicy``, a preset name like
        ``"bf16_mixed"``, or None to revert to f32/Engine defaults).

        The policy threads the whole stack: forward/backward compile in
        ``compute_dtype``, gradients reduce(-scatter) in compute dtype
        under ZeRO, the update runs on f32 weights (the f32 master copy
        when ``param_dtype`` is low-precision), and f16 policies get a
        ``DynamicLossScaler`` (pass ``scaler`` to tune it) whose state
        rides the optimizer-state tree — so ``set_steps_per_sync(K)``
        windows and ZeRO stages 1-3 compose with no further
        configuration, and seeded K=1 vs K=8 runs stay bit-identical
        with the scaler in the scan carry."""
        from bigdl_tpu.precision import DynamicLossScaler, PrecisionPolicy
        if isinstance(policy, str):
            policy = PrecisionPolicy.named(policy)
        if policy is not None and not isinstance(policy, PrecisionPolicy):
            raise TypeError(
                f"set_precision expects a PrecisionPolicy, a preset "
                f"name or None, got {type(policy).__name__}")
        if scaler is not None and not isinstance(scaler,
                                                 DynamicLossScaler):
            raise TypeError(
                f"scaler must be a DynamicLossScaler, got "
                f"{type(scaler).__name__}")
        self._precision = policy
        self._loss_scaler = scaler
        # the compiled validation slot closed over the previous
        # precision regime — drop it like set_model does
        self._dc_eval = None
        return self

    def set_sequence_parallel(self, config) -> "Optimizer":
        """Sequence-parallel attention for this run
        (``parallel.sequence.SeqParallelConfig``, or None for dense).

        The train step traces the model under the policy, so every
        ``MultiHeadAttention`` without an explicit ``ring_axis`` runs
        the configured ring/Ulysses kernel over the named mesh axis —
        activation memory per chip drops to the LOCAL sequence length,
        which is what lets S=128K train at all. Composes with
        ``set_zero`` (weights shard over the data axis, attention over
        the sequence axis) and ``set_steps_per_sync`` (the SP
        collectives live inside the scan body; the windowed dispatch
        boundary stays collective-free). Quiet no-op when the policy
        cannot apply — the ``train/seq_parallel/degree`` gauge reports
        the degree actually achieved."""
        from bigdl_tpu.parallel.sequence import SeqParallelConfig
        if config is not None and not isinstance(config,
                                                 SeqParallelConfig):
            raise TypeError(
                f"set_sequence_parallel expects a "
                f"parallel.SeqParallelConfig or None, got "
                f"{type(config).__name__}")
        self._seq_parallel = config
        return self

    def set_preflight_spec(self, input_spec) -> "Optimizer":
        """Opt-in pre-flight: before any compilation, ``optimize()``
        shape/dtype-checks the model against ``input_spec`` (see
        ``analysis.spec``; strings/None dims are symbolic) under
        ``jax.eval_shape`` and rejects a mis-wired model with a
        layer-path diagnostic instead of a deep XLA trace after a
        30-second compile. Pass None to disable."""
        self._preflight_spec = input_spec
        return self

    def set_drop_module_property(self, drop_percentage: float,
                                 max_drop_percentage: float,
                                 batchsize: int = 100,
                                 warmup_iteration: int = 200) -> "Optimizer":
        """Straggler dropping (Optimizer.scala:276). A synchronous TPU pod
        has no stragglers; accepted for recipe compatibility, does nothing."""
        self._drop_percentage = drop_percentage
        return self

    # -- sharding helpers --------------------------------------------------
    def _multiprocess(self) -> bool:
        """True when the mesh spans more than this process's devices —
        the multi-host regime the reference reached through Spark
        executors (Engine.scala:93-106); arrays must then be assembled
        from per-process local data."""
        return self.mesh is not None and jax.process_count() > 1

    def _data_parallel(self) -> bool:
        """True when the mesh actually splits the batch: a data axis of
        size > 1 (a size-1 axis — what the recipe's mesh builder emits
        when TP/PP consume every device — is the replicated regime)."""
        return self.mesh.shape.get(self.data_axis, 1) > 1

    def _batch_sharding(self, batch_axis: int = 0):
        """Batch layout on the mesh: sharded over the data axis when it
        really splits, else replicated (pure TP/PP meshes).
        ``batch_axis`` is where the batch dimension sits — 0 for a plain
        MiniBatch, 1 for a stacked ``[K, B, ...]`` window buffer (the
        window axis stays unsharded)."""
        spec = jax.sharding.PartitionSpec(
            *([None] * batch_axis + [self.data_axis])) \
            if self._data_parallel() else jax.sharding.PartitionSpec()
        return jax.sharding.NamedSharding(self.mesh, spec)

    def _put_batch(self, arr):
        from bigdl_tpu.dataset.sample import HostBatchedCOO
        if isinstance(arr, HostBatchedCOO):
            # SparseMiniBatch feed (MiniBatch.scala:587): transfer the
            # static-shape COO leaves like any dense batch (batch-dim
            # sharded) and rebuild the jit-compatible BCOO pytree
            if self._multiprocess() and not arr.fixed_nnz:
                raise ValueError(
                    "multi-host sparse batches must pad nnz to a FIXED "
                    "length (SampleToMiniBatch(feature_padding="
                    "PaddingParam(fixed_length=...))): each process "
                    "pads to its own batch max otherwise, and differing "
                    "static shapes desynchronize the SPMD programs")
            vals = self._put_batch(arr.values)
            idx = self._put_batch(arr.indices)
            return arr.to_bcoo(indices=idx, values=vals)
        if self.mesh is not None:
            sh = self._batch_sharding()
            if self._multiprocess() and not self._data_parallel():
                # pure TP/PP mesh (no data axis): the batch is
                # REPLICATED and every process must feed the identical
                # rows — cross-process model collectives then see one
                # consistent batch (megatron's broadcast-input regime)
                from bigdl_tpu.parallel.tp import put_global
                return put_global(np.asarray(arr), sh)
            if self._multiprocess():
                # each process contributes ITS batch rows; the global
                # batch is their concatenation in process order (the
                # role Spark partition locality played). Every process
                # must feed the same row count every step — a ragged
                # final batch would change the global shape mid-run (or
                # desynchronize iteration counts and deadlock the
                # collective), so fail fast instead.
                a = np.asarray(arr)
                expect = self._mp_batch_rows.get(self._stream)
                if expect is None:
                    self._mp_batch_rows[self._stream] = a.shape[0]
                elif a.shape[0] != expect:
                    raise ValueError(
                        f"multi-host {self._stream} batch changed size "
                        f"{expect} -> {a.shape[0]}: local datasets must "
                        "yield equal fixed-size batches per stream (drop "
                        "the remainder or pad the final batch)")
                gshape = (a.shape[0] * jax.process_count(),) + a.shape[1:]
                return jax.make_array_from_process_local_data(sh, a,
                                                              gshape)
            return jax.device_put(jnp.asarray(arr), sh)
        return jnp.asarray(arr)

    def _put_replicated(self, tree):
        if self.mesh is not None:
            sh = jax.sharding.NamedSharding(self.mesh,
                                            jax.sharding.PartitionSpec())
            if self._multiprocess():
                # every process holds the full value (init is
                # seed-identical); put_global assembles the global array
                from bigdl_tpu.parallel.tp import put_global
                return jax.tree.map(lambda a: put_global(a, sh), tree)
            return jax.device_put(tree, sh)
        return tree

    def _active_zero(self):
        """The ZeroConfig in force for THIS run, or None: configured,
        stage > 0, and the mesh's data axis actually splits (LocalOptimizer
        and pure-TP meshes fall back to the dense layout)."""
        cfg = self.zero_config
        return cfg if cfg is not None and cfg.active_on(self.mesh) else None

    def _put_params(self, tree):
        """Params: TP/EP-sharded when rules are given, else replicated —
        except ZeRO stage 3, where params live SHARDED at rest over the
        data axis (composed with any TP rules) and each layer is
        all-gathered just-in-time inside the compiled forward/backward."""
        cfg = self._active_zero()
        if self.mesh is not None and self.sharding_rules is not None:
            from bigdl_tpu.parallel.tp import shard_params, validate_rules
            problems = validate_rules(tree, self.mesh, self.sharding_rules)
            if problems:
                raise ValueError("bad sharding rules:\n" +
                                 "\n".join(problems))
            if cfg is not None and cfg.stage == 3:
                from bigdl_tpu.parallel.zero import shard_zero_tree
                return shard_zero_tree(tree, self.mesh, cfg,
                                       self.sharding_rules)
            return shard_params(tree, self.mesh, self.sharding_rules)
        if cfg is not None and cfg.stage == 3:
            from bigdl_tpu.parallel.zero import shard_zero_tree
            return shard_zero_tree(tree, self.mesh, cfg)
        return self._put_replicated(tree)

    def _put_opt_state(self, tree):
        """Optimizer state (momentum/variance buffers mirror the params
        tree, so the TP rules match their paths too — re.search ignores the
        'momentum/' prefix). Under ZeRO (any stage), every buffer shards
        its first free divisible dim over the data axis — the reference's
        per-node owned shard running the OptimMethod
        (AllReduceParameter.scala:214-303) — with an EXPLICIT sharding on
        every leaf, matching the in-step constraints exactly so donated
        updates never re-lay-out."""
        if self.mesh is None:
            return tree
        cfg = self._active_zero()
        if cfg is not None:
            from bigdl_tpu.parallel.zero import place_zero_opt_state
            return place_zero_opt_state(tree, self.mesh, cfg,
                                        self.sharding_rules)
        if self.sharding_rules is not None:
            from bigdl_tpu.parallel.tp import shard_params
            return shard_params(tree, self.mesh, self.sharding_rules)
        return self._put_replicated(tree)

    # -- windowed driver planning (set_steps_per_sync) ---------------------
    def _window_limit(self, k: int, end_when, device_feed: bool):
        """Run-wide cap on the window size, with the reason for any
        fallback: windowed execution must be OBSERVABLY identical to the
        per-step loop, so anything the host cannot predict before the
        dispatch (loss-dependent or unknown triggers, metric-driven LR
        schedules) forces per-step sync."""
        if k <= 1:
            return 1, ""
        for what, t in (("end trigger", end_when),
                        ("validation trigger", self.validation_trigger),
                        ("checkpoint trigger", self.checkpoint_trigger)):
            if t is None or t.plannable():
                continue
            dep = sorted(t.depends_on) if t.depends_on is not None else None
            why = (f"{what} reads runtime state {dep}" if dep
                   else f"{what} has undeclared dependencies")
            return 1, why + "; per-step sync keeps its semantics exact"
        sched = getattr(self.optim_method, "learning_rate_schedule", None)
        if sched is not None and hasattr(sched, "record_metric"):
            return 1, ("metric-driven LR schedule (Plateau) adjusts per "
                       "step; per-step sync keeps it exact")
        get_trig = getattr(self.train_summary, "get_summary_trigger",
                           None) if self.train_summary is not None else None
        if get_trig is not None and get_trig("Parameters") is not None:
            return 1, ("train-summary Parameters histograms snapshot the "
                       "params of EACH step; per-step sync keeps them "
                       "exact")
        if not device_feed and self._multiprocess():
            return 1, ("multi-host host-feed runs per-step (stacked "
                       "window buffers are single-process)")
        return k, ""

    def _plan_window(self, k_max: int, state, bsz: int, ds_size: int,
                     end_when, shard_size=None) -> int:
        """Largest k <= k_max such that the per-step loop would do NO
        host work (trigger fire, epoch rollover, shard rotation) after
        steps 1..k-1. The k-th step may land ON a boundary: the window
        flushes there and the host replay handles it with the window's
        final (current) params."""
        if k_max <= 1:
            return 1
        n0 = state["neval"]
        ep0 = state["epoch"]
        rec = state["recordsProcessedThisEpoch"]
        spos = ((n0 - 1) * bsz) % shard_size if shard_size else None
        for i in range(1, k_max):
            rec += bsz
            if rec >= ds_size:
                return i  # epoch rollover: shuffle/permutation bookkeeping
            if spos is not None:
                spos += bsz
                if spos >= shard_size:
                    return i  # next shard must rotate in before step i+1
            sim = {"epoch": ep0, "neval": n0 + i,
                   "recordsProcessedThisEpoch": rec}
            for t in (end_when, self.validation_trigger,
                      self.checkpoint_trigger):
                if t is not None and t.peek(sim):
                    return i
        return k_max

    def _window_lrs(self, k: int, state):
        """The k learning rates the per-step loop would have computed,
        via k real ``update_hyper_parameter()`` calls (schedule counters
        advance exactly as they would per-step; the epoch cannot change
        mid-window because windows flush at rollovers)."""
        n0 = state["neval"]
        lrs = []
        for i in range(k):
            self.optim_method.state["neval"] = n0 + i
            lrs.append(self.optim_method.update_hyper_parameter())
        return lrs

    def _prep_io_window(self, batch: MiniBatch):
        """Stage a stacked ``[K, B, ...]`` window batch
        (``dataset.prefetch.stack_minibatches``): like :meth:`_prep_io`,
        but the batch dimension is axis 1, so :meth:`_batch_sharding`
        is asked for the axis-1 layout. Multi-host and sparse batches
        never reach here (the window limiter falls back to per-step,
        where :meth:`_put_batch` owns those regimes)."""
        sh = self._batch_sharding(batch_axis=1) if self.mesh is not None \
            else None

        def put(x):
            if x is None:
                return None
            if isinstance(x, (list, tuple)):
                from bigdl_tpu.utils.table import T as _T
                return _T(*[put(e) for e in x])
            return jnp.asarray(x) if sh is None \
                else jax.device_put(jnp.asarray(x), sh)
        return put(batch.get_input()), put(batch.get_target())

    def _prep_io(self, batch: MiniBatch):
        inp = batch.get_input()
        tgt = batch.get_target()
        if isinstance(inp, (list, tuple)):
            from bigdl_tpu.utils.table import T as _T
            inp = _T(*[self._put_batch(x) for x in inp])
        else:
            inp = self._put_batch(inp)
        if isinstance(tgt, (list, tuple)):
            from bigdl_tpu.utils.table import T as _T
            tgt = _T(*[self._put_batch(x) for x in tgt])
        elif tgt is not None:
            tgt = self._put_batch(tgt)
        return inp, tgt

    # -- checkpointing (DistriOptimizer.checkpoint :433-463) ---------------
    def _cursor_dataset(self):
        """The dataset (possibly behind ``TransformedDataSet`` wrappers —
        walk the ``.base`` chain) that carries a streaming-pipeline
        cursor, or None. Without the unwrap, ``pipe.as_dataset()
        .transform(...)`` would silently lose cursor checkpointing and a
        resumed run would replay already-consumed records."""
        ds = self.dataset
        seen = 0
        while ds is not None and seen < 32:  # cycle guard
            if callable(getattr(ds, "pipeline_state", None)) \
                    and callable(getattr(ds, "restore_pipeline_state",
                                         None)):
                return ds
            ds = getattr(ds, "base", None)
            seen += 1
        return None

    def _checkpoint(self, params, opt_state, model_state):
        from bigdl_tpu.utils.serialization import save_checkpoint
        neval = self.driver_state["neval"]
        suffix = "" if self.is_overwrite else f".{neval}"
        path = os.path.join(self.checkpoint_path, f"checkpoint{suffix}")
        if self.checkpoint_async:
            return self._checkpoint_elastic(path, params, opt_state,
                                            model_state)
        # single-writer in multi-host runs (the reference wrote once
        # from the driver, DistriOptimizer.scala:433-463): every process
        # participates in the collective host materialization inside
        # save_checkpoint, but only process 0 touches the (shared)
        # checkpoint storage — no N× duplicated IO
        writer = not self._multiprocess() or jax.process_index() == 0
        driver_state = {k: v for k, v in self.driver_state.items()}
        # streaming pipelines (datapipe PipelineDataSet) carry a read
        # cursor: checkpoint it alongside the driver counters so resume
        # continues the stream instead of replaying the epoch
        cursor_ds = self._cursor_dataset()
        if cursor_ds is not None and not self._multiprocess():
            # single-process only: the cursor is PROCESS-LOCAL (each
            # process reads its own shard split), but only process 0
            # writes the checkpoint — restoring its cursor onto every
            # process would desync the per-process streams. Multi-host
            # runs keep the pre-cursor resume semantics (epoch replay).
            driver_state["datapipe"] = cursor_ds.pipeline_state()
        save_checkpoint(path, params=params, opt_state=opt_state,
                        model_state=model_state,
                        optim_host_state=self.optim_method.get_state(),
                        driver_state=driver_state,
                        writer=writer)
        if writer:
            logger.info("checkpointed to %s", path)
            if self.checkpoint_keep_last:
                from bigdl_tpu.elastic import prune_checkpoints
                prune_checkpoints(self.checkpoint_path,
                                  self.checkpoint_keep_last)

    def _checkpoint_elastic(self, path, params, opt_state, model_state,
                            sync: bool = False):
        """The per-shard format-3 writer (``bigdl_tpu.elastic``): every
        process snapshots its own shards (no gather), process 0 commits
        the barriered MANIFEST; ``sync=False`` hands the write tail to
        the background writer. Each process contributes ITS datapipe
        cursor, so the manifest carries the full per-process cursor set
        for cross-world-size re-splitting on resume."""
        from bigdl_tpu import elastic
        meta = elastic.run_metadata(
            mesh=self.mesh, data_axis=self.data_axis,
            zero=self._active_zero(), precision=self._precision,
            process_count=jax.process_count() if self._multiprocess()
            else 1)
        cursor_ds = self._cursor_dataset()
        cursor = cursor_ds.pipeline_state() if cursor_ds is not None \
            else None
        elastic.save_checkpoint(
            path, params=params, opt_state=opt_state,
            model_state=model_state,
            optim_host_state=self.optim_method.get_state(),
            driver_state=dict(self.driver_state),
            run_meta=meta, cursor=cursor,
            process_index=jax.process_index() if self._multiprocess()
            else 0,
            process_count=meta["process_count"],
            writer=None if sync else self._ckpt_writer,
            keep_last=self.checkpoint_keep_last)
        logger.info("elastic checkpoint %s to %s",
                    "written" if sync else "enqueued", path)

    def _flush_ckpt_writer(self):
        """Drain the async writer (no-op without one): every resume /
        exit / emergency path calls this so a commit in flight is
        visible before ``find_latest_checkpoint`` runs — and so a
        background write failure surfaces into the classified retry
        loop exactly where the sync writer would have raised."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.flush()

    def _drain_preemption(self, params, opt_state, model_state):
        """The SIGTERM grace path, run at a step boundary (state is
        complete and consistent here): flush the in-flight async write,
        save an EMERGENCY checkpoint synchronously, dump a flight
        bundle, and raise ``Preempted`` — which escapes the retry loop
        (BaseException) so the gang launcher owns the recovery."""
        from bigdl_tpu.elastic import Preempted
        self._grace.count_preemption()
        logger.warning("SIGTERM grace: flushing emergency checkpoint")
        if self.checkpoint_path is not None:
            try:
                self._flush_ckpt_writer()
            except Exception:
                logger.exception("in-flight async write failed during "
                                 "preemption drain; writing emergency "
                                 "checkpoint anyway")
            neval = self.driver_state["neval"]
            suffix = "" if self.is_overwrite else f".{neval}"
            path = os.path.join(self.checkpoint_path,
                                f"checkpoint{suffix}")
            if self.checkpoint_async:
                self._checkpoint_elastic(path, params, opt_state,
                                         model_state, sync=True)
            else:
                self._checkpoint(params, opt_state, model_state)
        telemetry.flight.on_fatal("train/preempt")
        raise Preempted(
            f"SIGTERM at neval {self.driver_state['neval']}: emergency "
            "checkpoint flushed; relaunch resumes from it")

    def _try_resume(self):
        """Latest INTACT checkpoint's state, or None. A checkpoint that
        fails integrity verification (or any load error) is quarantined
        to ``*.corrupt-<pid>`` and the walk continues to the previous
        intact one — without this, a retry loop would re-raise on the
        same corrupt latest dir every attempt and the run could never
        recover. When quarantine itself is impossible (a filesystem
        that cannot rename — remote stores without mv, a read-only
        parent) the load error propagates: silently looping on an
        unremovable bad dir would hang the retry loop."""
        from bigdl_tpu.utils.serialization import (find_latest_checkpoint,
                                                   load_checkpoint,
                                                   quarantine_checkpoint)
        if not self.checkpoint_path:
            return None
        # a commit still on the background writer must land (or its
        # failure surface) before the latest-checkpoint walk
        self._flush_ckpt_writer()
        while True:
            latest = find_latest_checkpoint(self.checkpoint_path)
            if latest is None:
                return None
            try:
                ck = load_checkpoint(latest)
            except Exception as e:
                logger.warning(
                    "checkpoint %s unreadable (%s: %s); quarantining "
                    "and walking back", latest, type(e).__name__, e)
                if quarantine_checkpoint(latest) is None:
                    raise
                continue
            logger.warning("retry: resuming from %s", latest)
            return ck

    # -- validation (DistriOptimizer.scala:607-686) ------------------------
    def _validate(self, params, model_state, eval_step):
        self._stream = "validate"
        try:
            return self._validate_impl(params, model_state, eval_step)
        finally:
            self._stream = "train"

    def _validate_impl(self, params, model_state, eval_step):
        from bigdl_tpu.dataset.transformer import SampleToMiniBatch
        ds = self.validation_dataset
        if hasattr(ds, "eval_batch_fn_on"):
            return self._validate_device_cached(params, model_state, ds)
        it = ds.data(train=False)
        results = None
        # Accept datasets of Samples or of MiniBatches
        batcher = SampleToMiniBatch(self._val_batch_size)
        peek = []
        for el in it:
            peek.append(el)
            break
        if not peek:
            return {}
        import itertools
        full_it = itertools.chain(peek, it)
        if isinstance(peek[0], MiniBatch):
            batches = full_it
        else:
            batches = batcher.apply(full_it)
        for b in batches:
            inp, tgt = self._prep_io(b)
            out = eval_step(params, model_state, inp)
            # multi-host: out/tgt span non-addressable devices; each
            # process scores ITS rows (the reference aggregated
            # per-executor ValidationResults the same way — here the
            # local shard IS this process's data)
            out_np, tgt_np = _local_rows(out), _local_rows(tgt)
            batch_res = [m(out_np, tgt_np)
                         for m in self.validation_methods]
            if results is None:
                results = batch_res
            else:
                results = [r + br for r, br in zip(results, batch_res)]
        if self._multiprocess():
            # reduce ValidationResults across processes (the reference
            # reduce(+)s per-executor results, DistriOptimizer.scala:607)
            results = [_allreduce_result(r) for r in results]
        return self._score_summary(results)

    def _validate_device_cached(self, params, model_state, ds):
        """Trigger-driven validation straight off the HBM cache
        (DeviceCachedArrayDataSet passed to set_validation): one jitted
        sample+forward per batch, zero per-trigger host feed — the
        device-resident form of validation riding the same cached
        distributed dataset as training (DistriOptimizer.scala:607-686).

        Intentionally NOT delegated to Predictor._device_cached_sweep:
        validation fires every trigger, so the compiled sweep must be
        CACHED across calls (the single-slot ``_dc_eval`` below) —
        keep the divisibility guard and trim rules in lockstep with
        predictor.py's one-shot sweep when changing either.
        """
        fn = self._dc_eval[1] if (self._dc_eval is not None
                                  and self._dc_eval[0] is ds) else None
        if fn is None:
            ev_sh = self._batch_sharding() if self.mesh is not None \
                else None

            def _ev(p, m, start, images, labels):
                x, y = ds.eval_batch_fn_on(images, labels, start)
                out, _ = self.model.apply(p, m, x, training=False)
                return out, y

            fn = jax.jit(_ev, out_shardings=(ev_sh, ev_sh))
            self._dc_eval = (ds, fn)
        n, b = ds.size(), ds.batch_size
        if self._multiprocess() and n % b:
            raise ValueError(
                "device-cached multi-host validation needs batch_size to "
                "divide the dataset (a wrapped final batch cannot be "
                "trimmed consistently across processes)")
        results = None
        for start in range(0, n, b):
            out, y = fn(params, model_state, jnp.int32(start),
                        ds.images, ds.labels)
            out_np, tgt_np = _local_rows(out), _local_rows(y)
            valid = min(b, n - start)
            if valid < b:  # eval_batch_fn wraps modulo n; trim the tail
                out_np, tgt_np = out_np[:valid], tgt_np[:valid]
            batch_res = [m(out_np, tgt_np)
                         for m in self.validation_methods]
            results = batch_res if results is None else \
                [r + br for r, br in zip(results, batch_res)]
        if self._multiprocess():
            results = [_allreduce_result(r) for r in results]
        return self._score_summary(results)

    def _score_summary(self, results):
        summary = {}
        for m, r in zip(self.validation_methods, results):
            value, _ = r.result()
            # unique key per method so duplicates (e.g. two Loss instances)
            # don't overwrite each other — first key must stay the FIRST
            # method (driver_state["score"] reads it)
            key, k = m.name, 2
            while key in summary:
                key = f"{m.name}-{k}"
                k += 1
            summary[key] = value
            logger.info("validation %s: %s", key, r)
        return summary

    # -- the loop (optimize(), DistriOptimizer.scala:154-421) --------------
    def optimize(self) -> Module:
        if not Engine.is_initialized():
            Engine.init()
        if self._preflight_spec is not None:
            # pre-flight OUTSIDE the retry loop: a structurally broken
            # model fails identically every attempt, so reject it once,
            # with a layer-path diagnostic, before any init/compile work
            self.model.check(self._preflight_spec, training=True)
        # SIGTERM grace (set_preemption_handler / BIGDL_PREEMPT_GRACE):
        # installed around the whole retry loop so a preemption landing
        # mid-retry still drains through the emergency-checkpoint path
        if self._preempt_grace or os.environ.get(
                "BIGDL_PREEMPT_GRACE") == "1":
            from bigdl_tpu.elastic import GraceHandler
            self._grace = GraceHandler().install()
        try:
            return self._optimize_with_retry()
        finally:
            if self._grace is not None:
                self._grace.uninstall()
                self._grace = None

    def _optimize_with_retry(self) -> Module:
        from bigdl_tpu.faults.retry import backoff_delay, classify
        retries = 0
        while True:
            try:
                return self._optimize_impl()
            except (KeyboardInterrupt,):
                raise
            except Exception as e:  # retry-from-checkpoint loop
                # classified: structural/compile errors (bad types,
                # shape mismatches) fail identically every attempt —
                # fail fast with the first diagnostic; transient
                # IO/runtime errors retry with exponential backoff +
                # jitter so a fleet doesn't stampede whatever just
                # recovered
                retries += 1
                if classify(e) == "fatal" or retries > self.retry_times \
                        or self.checkpoint_path is None:
                    # the error is about to escape the process: dump a
                    # post-mortem bundle (no-op unless flight is armed)
                    telemetry.flight.on_fatal("train/optimizer", e)
                    raise
                _RECOVERIES.inc()
                delay = backoff_delay(retries - 1, self.retry_interval_s,
                                      self.retry_max_interval_s)
                logger.exception(
                    "training failed (%s); retry %d/%d in %.2fs",
                    e, retries, self.retry_times, delay)
                time.sleep(delay)

    def _optimize_impl(self) -> Module:
        model = self.model
        model.training()
        model.ensure_initialized()
        params = model.get_parameters()
        model_state = model.get_state()
        opt_state = self.optim_method.init_state(params)

        resumed = self._try_resume()
        if resumed is not None:
            params = resumed["params"]
            opt_state = resumed["opt_state"]
            model_state = resumed["model_state"]
            self.optim_method.load_state(resumed["optim_host_state"])
            self.driver_state.update(resumed["driver_state"])
            # a checkpointed streaming-pipeline cursor restores the data
            # position (see _checkpoint); popped so the driver counters
            # stay plain ints and a later dataset swap can't reuse it.
            # Multi-process mirrors the _checkpoint guard: the cursor is
            # process-0's PROCESS-LOCAL position — applying it to every
            # process's different shard split would desync the streams,
            # so multi-host resume keeps the epoch-replay fallback.
            cursor = self.driver_state.pop("datapipe", None)
            cursor_ds = self._cursor_dataset()
            if resumed.get("cursors"):
                # format-3 elastic checkpoint: the MANIFEST carries
                # EVERY writing process's cursor — re-split across the
                # CURRENT world size (exact when the count matches, an
                # epoch restart otherwise), which makes multi-process
                # cursor resume a supported path, not an exclusion
                from bigdl_tpu.elastic import resplit_cursor
                cursor = resplit_cursor(
                    resumed["cursors"],
                    jax.process_index() if self._multiprocess() else 0,
                    jax.process_count() if self._multiprocess() else 1)
                if cursor is not None and cursor_ds is not None:
                    cursor_ds.restore_pipeline_state(cursor)
            elif cursor is not None and cursor_ds is not None \
                    and not self._multiprocess():
                cursor_ds.restore_pipeline_state(cursor)
        # epoch/iteration-driven lr schedules read the OptimMethod's
        # state: sync the driver counters in (covers set_state called
        # before set_optim_method, and keeps both views consistent)
        for k in ("epoch", "neval"):
            if k in self.driver_state:
                self.optim_method.state[k] = self.driver_state[k]

        from bigdl_tpu.precision import (MASTER_KEY, SCALER_KEY,
                                         DynamicLossScaler,
                                         PrecisionPolicy)
        policy = self._precision if self._precision is not None \
            else PrecisionPolicy.from_engine()
        scaler = None
        if policy.needs_loss_scaling:
            scaler = self._loss_scaler if self._loss_scaler is not None \
                else DynamicLossScaler()
        if not isinstance(opt_state, dict):  # exotic OptimMethod state
            if policy.needs_master or scaler is not None:
                raise ValueError(
                    "set_precision with master weights / loss scaling "
                    "needs a dict-shaped optimizer state (every shipped "
                    "OptimMethod qualifies)")
        else:
            # a resumed checkpoint already carries these keys; a fresh
            # run (or one resumed from a pre-policy checkpoint, whose
            # params are f32) inserts them here
            if policy.needs_master and MASTER_KEY not in opt_state:
                # the f32 master copy (cast up if the module was built
                # under a low-precision Engine default dtype)
                opt_state[MASTER_KEY] = policy.cast_to_accum(params)
                params = policy.cast_to_param(params)
            if scaler is not None and SCALER_KEY not in opt_state:
                opt_state[SCALER_KEY] = scaler.init_state()
        if not policy.is_noop:
            logger.info("precision policy: %s", policy.describe())
            # value 1 marks the ACTIVE policy; series from earlier runs
            # in this process drop to 0 so diagnose can tell them apart
            for key in _POLICY_INFO._series():
                _POLICY_INFO.set(0.0, **dict(key))
            _POLICY_INFO.set(
                1.0, policy=policy.name,
                param=policy.param_dtype.name,
                compute=policy.compute_dtype.name,
                accum=policy.accum_dtype.name)
            _LOSS_SCALE.set(float(scaler.init_scale) if scaler else 1.0)
            _SKIPPED_STEPS.set(0.0)

        params = self._put_params(params)
        opt_state = self._put_opt_state(opt_state)
        model_state = self._put_replicated(model_state)
        if self.mesh is not None or not policy.is_noop:
            # per-chip memory proof: gauges read the PLACED shard sizes,
            # so the n-fold ZeRO reduction — and the low-precision
            # params/grads shrink — are exported numbers, not claims
            # (train/memory/*_bytes_per_chip; the f32-equivalent
            # "before" lands in train/precision/*_f32_bytes_per_chip)
            from bigdl_tpu.parallel.zero import (record_memory_gauges,
                                                 tree_bytes_per_chip)
            record_memory_gauges(params, opt_state)
            if not policy.is_noop:
                _PARAMS_F32_BYTES.set(tree_bytes_per_chip(
                    params, floating_as=jnp.float32))
                _OPT_F32_BYTES.set(tree_bytes_per_chip(
                    opt_state, floating_as=jnp.float32))

        step = build_train_step(model, self.criterion, self.optim_method,
                                gradient_clip=self._gradient_clip,
                                zero=self._active_zero(), mesh=self.mesh,
                                sharding_rules=self.sharding_rules,
                                precision=policy, loss_scaler=scaler,
                                seq_parallel=self._seq_parallel)
        ev_sh = self._batch_sharding() if self.mesh is not None else None
        # validation runs under the policy only when the user OPTED IN
        # via set_precision — the legacy Engine dtype knobs never cast
        # eval (pre-policy validation always scored the f32 forward)
        eval_step = build_eval_step(model, ev_sh,
                                    precision=self._precision)
        track_scaler = scaler is not None

        ds_size = self.dataset.size()
        state = self.driver_state
        # Device-cached feed (DeviceCachedArrayDataSet): the batch is
        # sampled + augmented INSIDE the jitted step — zero per-step
        # host->device traffic (the HBM form of the reference's decoded
        # executor cache, DataSet.scala CachedDistriDataSet:240).
        rotating = getattr(self.dataset, "rotating", False)
        device_feed = rotating or hasattr(self.dataset, "batch_fn")
        if rotating:
            # rotating HBM shard cache (RotatingDeviceDataSet): the slot
            # arrays MUST be step arguments — a closure would bake them
            # in as compile-time constants and train on the first shard
            # forever; as arguments, each rotation is a plain rebind of
            # the one compiled step
            ds = self.dataset
            tmpl = ds.template

            def _fused_rot(p, o, m, key, lr, ep, pos, images, labels):
                kb, kr = jax.random.split(key)
                x, y = tmpl.batch_fn_on(images, labels, kb,
                                        epoch=ep, pos=pos)
                return step(p, o, m, kr, lr, x, y)

            fused_step = jax.jit(_fused_rot, donate_argnums=(0, 1, 2))
            data_iter = None
        elif device_feed:
            ds = self.dataset
            # epoch-exact feed: the global iteration index drives a
            # per-epoch permutation inside batch_fn (DataSet.scala:240
            # shuffle semantics); datasets without sample_indices keep
            # the rng-only contract
            epoch_exact = hasattr(ds, "sample_indices")
            # on a mesh spanning processes the cache arrays are global
            # arrays with non-addressable shards — jit cannot close over
            # those; pass them as arguments (batch_fn_on) when available
            feed_by_arg = hasattr(ds, "batch_fn_on")

            if feed_by_arg:
                def _fused(p, o, m, key, lr, ep, pos, images, labels):
                    kb, kr = jax.random.split(key)
                    x, y = ds.batch_fn_on(images, labels, kb,
                                          epoch=ep, pos=pos) \
                        if epoch_exact else \
                        ds.batch_fn_on(images, labels, kb)
                    return step(p, o, m, kr, lr, x, y)
            else:
                def _fused(p, o, m, key, lr, ep, pos):
                    kb, kr = jax.random.split(key)
                    x, y = ds.batch_fn(kb, epoch=ep, pos=pos) \
                        if epoch_exact else ds.batch_fn(kb)
                    return step(p, o, m, kr, lr, x, y)

            # donate like build_train_step does — inner-jit donation is
            # ignored when traced inside an outer jit
            fused_step = jax.jit(_fused, donate_argnums=(0, 1, 2))
            data_iter = None
        else:
            data_iter = self.dataset.data(train=True)
        end_when = self.end_when
        if end_when is None:
            from bigdl_tpu.optim.trigger import max_epoch
            end_when = max_epoch(10)

        # -- windowed driver setup (set_steps_per_sync) -------------------
        # plan_bsz: the per-step record count windows are planned with
        # (device feeds are exact; host feeds re-check actual sizes while
        # gathering a window)
        plan_bsz = self.dataset.batch_size if (rotating or device_feed) \
            else self.batch_size
        k_cap, why = self._window_limit(self.steps_per_sync, end_when,
                                        rotating or device_feed)
        if k_cap < self.steps_per_sync:
            logger.info("steps_per_sync=%d: falling back to per-step "
                        "sync — %s", self.steps_per_sync, why)
        shard_size = self.dataset.rot.shard_size if rotating else None
        window_fn = None       # ONE jitted program per feed path; jax's
        host_window_fn = None  # compile cache keys it by (k, shapes)
        if k_cap > 1 and (rotating or device_feed):
            modulus = shard_size if rotating else ds_size
            if rotating:
                def _feed(arrs, kb, ep, pos):
                    return tmpl.batch_fn_on(arrs[0], arrs[1], kb,
                                            epoch=ep, pos=pos)
            elif feed_by_arg:
                if epoch_exact:
                    def _feed(arrs, kb, ep, pos):
                        return ds.batch_fn_on(arrs[0], arrs[1], kb,
                                              epoch=ep, pos=pos)
                else:
                    def _feed(arrs, kb, ep, pos):
                        return ds.batch_fn_on(arrs[0], arrs[1], kb)
            else:
                if epoch_exact:
                    def _feed(arrs, kb, ep, pos):
                        return ds.batch_fn(kb, epoch=ep, pos=pos)
                else:
                    def _feed(arrs, kb, ep, pos):
                        return ds.batch_fn(kb)

            def _window_dev(p, o, m, keys, lrs, ep0, pos0, *arrs):
                # K fused steps: the (epoch, pos) sample cursor advances
                # in the scan carry (all values stay < 2*modulus — no
                # int32 overflow however long the run); losses come back
                # as ONE length-K vector
                def body(carry, sl):
                    p, o, m, ep, pos = carry
                    key, lr = sl
                    kb, kr = jax.random.split(key)
                    x, yb = _feed(arrs, kb, ep, pos)
                    p, o, m, loss = step(p, o, m, kr, lr, x, yb)
                    pos = pos + plan_bsz
                    ep = ep + pos // modulus
                    pos = pos % modulus
                    return (p, o, m, ep, pos), loss
                (p, o, m, _, _), losses = jax.lax.scan(
                    body, (p, o, m, ep0, pos0), (keys, lrs))
                return p, o, m, losses

            window_fn = telemetry.programs.maybe_wrap_jitted(
                train_program_name(model, "window"), "train",
                jax.jit(_window_dev, donate_argnums=(0, 1, 2)),
                donation="params,opt_state,model_state",
                scan_length_for=lambda a, kw: int(a[3].shape[0]),
                items_for=lambda a, kw: int(a[3].shape[0]) * plan_bsz)
        elif k_cap > 1:
            def _host_window_items(a, kw):
                # xs is the [K, B, ...] stacked window: K*B records
                leaf = jax.tree_util.tree_leaves(a[5])[0]
                return int(leaf.shape[0]) * int(leaf.shape[1])

            host_window_fn = telemetry.programs.maybe_wrap_jitted(
                train_program_name(model, "window"), "train",
                make_host_window(step),
                donation="params,opt_state,model_state",
                scan_length_for=lambda a, kw: int(a[3].shape[0]),
                items_for=_host_window_items)

        def device_cursor_args():
            """Step arguments for the device-resident feeds at the
            CURRENT ``state['neval']`` — the ONE place the cursor
            convention lives, shared by the per-step and windowed
            dispatches (divergence here would silently split K=1 vs
            K>1 semantics). neval starts at 1 (reference convention);
            the sample stream is 0-based so epoch boundaries line up
            with recordsProcessedThisEpoch rollover; the cursor is
            decomposed HERE with exact Python integers, so no
            device-int overflow however long the run."""
            if rotating:
                visit, sp = self.dataset.shard_cursor(state["neval"])
                return (jnp.int32(visit), jnp.int32(sp),
                        self.dataset.images, self.dataset.labels)
            e0, p0 = divmod((state["neval"] - 1) * plan_bsz, ds_size)
            args = (jnp.int32(e0), jnp.int32(p0))
            if feed_by_arg:
                args += (self.dataset.images, self.dataset.labels)
            return args

        pending: List[MiniBatch] = []  # host batches pulled ahead
        warned_unstackable = False  # log the data-dependent fallback once

        def pull_batch() -> MiniBatch:
            b = pending.pop(0) if pending else next(data_iter)
            if not isinstance(b, MiniBatch):
                raise ValueError(
                    "dataset must yield MiniBatch; add SampleToMiniBatch")
            return b

        def post_step(loss_f, lr, bsz_i, throughput):
            """One step's worth of host bookkeeping. The per-step loop
            runs it after every step; the windowed driver REPLAYS it K
            times after the single window fetch, so counters, triggers,
            epoch rollovers and summaries observe the identical
            per-step sequence either way."""
            nonlocal data_iter
            if rotating:
                # the window/loss fetch completed this step; stream the
                # next shard piece now (alternation rule) and rotate
                # slots at shard boundaries
                self.dataset.after_step(state["neval"])
            state["neval"] += 1
            self.optim_method.state["neval"] = state["neval"]
            state["recordsProcessedThisEpoch"] += bsz_i
            state["Loss"] = loss_f
            state["LearningRate"] = lr
            state["Throughput"] = throughput
            logger.info(
                "Epoch %d iter %d: loss %.4f lr %.5f throughput %.1f rec/s",
                state["epoch"], state["neval"] - 1, loss_f, lr, throughput)

            if self.train_summary is not None:
                self.train_summary.add_scalar("Loss", loss_f, state["neval"])
                self.train_summary.add_scalar("LearningRate", lr,
                                              state["neval"])
                self.train_summary.add_scalar("Throughput", throughput,
                                              state["neval"])
                # per-parameter histograms, opt-in via trigger
                # (TrainSummary.scala:64; DistriOptimizer.scala:464-498)
                get_trig = getattr(self.train_summary,
                                   "get_summary_trigger", None)
                ptrig = get_trig("Parameters") if get_trig else None
                if ptrig is not None and ptrig(state):
                    flat, _ = jax.tree_util.tree_flatten_with_path(params)
                    for path, leaf in flat:
                        tag = "/".join(
                            str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
                        self.train_summary.add_histogram(
                            tag, np.asarray(leaf), state["neval"])

            # epoch rollover (DistriOptimizer.scala:368-380). Carry the
            # overshoot: when batch_size does not divide ds_size a batch
            # straddles the epoch boundary, and resetting to 0 would make
            # the driver's epoch drift from the sample stream's true
            # permutation epochs (epoch-driven lr schedules / triggers
            # would fire progressively late)
            while state["recordsProcessedThisEpoch"] >= ds_size:
                # while, not if: one batch can span several epochs when
                # batch_size > ds_size
                state["epoch"] += 1
                self.optim_method.state["epoch"] = state["epoch"]
                state["recordsProcessedThisEpoch"] -= ds_size
                if not device_feed and not getattr(
                        self.dataset, "continuous_stream", False):
                    # a restartable iterator begins a FRESH permutation,
                    # so the overshoot carry would skip its tail — reset
                    # to 0; continuous streams (device feed, the
                    # ImageFolder _IndexStream) keep the carry, which
                    # tracks their true permutation boundary exactly
                    state["recordsProcessedThisEpoch"] = 0
                    self.dataset.shuffle()
                    data_iter = self.dataset.data(train=True)

            # validation / checkpoint triggers (:382-411). Windows flush
            # at every plannable trigger boundary, so in K>1 mode these
            # can only fire on the LAST replayed step — where params are
            # exactly the window's (current) outputs.
            if (self.validation_trigger is not None
                    and self.validation_trigger(state)):
                with telemetry.span("optimizer/validate",
                                    step=state["neval"]):
                    scores = self._validate(params, model_state,
                                            eval_step)
                if scores:
                    # The first method's result drives maxScore/Plateau —
                    # a max() across heterogeneous methods (e.g. Top1 vs
                    # Loss) would act on the wrong number
                    # (DistriOptimizer.scala:382-397 uses head).
                    state["score"] = next(iter(scores.values()))
                    sched = getattr(self.optim_method,
                                    "learning_rate_schedule", None)
                    if sched is not None and hasattr(sched, "record_metric"):
                        sched.record_metric(state["score"])
                    if self.validation_summary is not None:
                        for k, v in scores.items():
                            self.validation_summary.add_scalar(
                                k, v, state["neval"])
            if (self.checkpoint_trigger is not None
                    and self.checkpoint_trigger(state)):
                with telemetry.span("optimizer/checkpoint",
                                    step=state["neval"]):
                    self._checkpoint(params, opt_state, model_state)

        wall_start = time.time()
        window_synced = None  # monotonic: the last window's sync returned
        while not end_when(state):
            if self._grace is not None and self._grace.requested():
                # SIGTERM grace: step boundary, state consistent —
                # flush the emergency checkpoint and exit via Preempted
                self._drain_preemption(params, opt_state, model_state)
            # scripted worker-death site (ExceptionTest's role): a chaos
            # schedule can raise (exercising the classified retry loop)
            # or SIGKILL here, keyed on the driver counters; disarmed
            # it's one flag check
            faults.point("train/step", neval=state["neval"],
                         epoch=state["epoch"])
            k_now = 1 if k_cap <= 1 else self._plan_window(
                k_cap, state, plan_bsz, ds_size, end_when,
                shard_size=shard_size)
            t0 = time.time()
            window_batches = None
            if k_now > 1 and not (rotating or device_feed):
                # host feed: gather a window of stackable equal-shape
                # prefetched batches; a shape change, sparse leaves, the
                # epoch boundary or exhaustion close the window early
                first = pull_batch()
                window_batches = [first]
                if not _window_stackable(first) and not warned_unstackable:
                    # config-level fallbacks log via _window_limit; this
                    # DATA-dependent one must be visible too, or a user
                    # chases a phantom "K=8 is no faster" regression
                    warned_unstackable = True
                    logger.info(
                        "steps_per_sync=%d: batches are not window-"
                        "stackable (sparse or device-resident leaves) — "
                        "running per-step", self.steps_per_sync)
                if _window_stackable(first):
                    sig = batch_signature(first)
                    rec_sim = (state["recordsProcessedThisEpoch"]
                               + first.size())

                    def boundary_after(steps_done, rec):
                        # _plan_window simulated with the CONFIGURED
                        # batch size; datasets may yield other sizes,
                        # so re-peek the plannable triggers with the
                        # ACTUAL accumulated record counts — a fire
                        # after the just-gathered step ends the window
                        sim = {"epoch": state["epoch"],
                               "neval": state["neval"] + steps_done,
                               "recordsProcessedThisEpoch": rec}
                        return any(t is not None and t.peek(sim)
                                   for t in (end_when,
                                             self.validation_trigger,
                                             self.checkpoint_trigger))

                    while len(window_batches) < k_now \
                            and rec_sim < ds_size \
                            and not boundary_after(len(window_batches),
                                                   rec_sim):
                        try:
                            b = pull_batch()
                        except StopIteration:
                            break
                        if not _window_stackable(b) \
                                or batch_signature(b) != sig:
                            pending.append(b)
                            break
                        window_batches.append(b)
                        rec_sim += b.size()
                k_now = len(window_batches)

            if k_now > 1:
                # ---- fused window: ONE dispatch, ONE host sync ------
                if rotating or device_feed:
                    sizes = [plan_bsz] * k_now
                    wargs = device_cursor_args()
                    t_data = time.time() - t0
                else:
                    sizes = [b.size() for b in window_batches]
                    stacked = stack_minibatches(window_batches)
                    inp, tgt = self._prep_io_window(stacked)
                    # close the staging window before dispatch, exactly
                    # like the per-step path (sanctioned window-boundary
                    # sync)
                    jax.block_until_ready((inp, tgt))  # bigdl: disable=sync-in-loop
                    t_data = time.time() - t0
                # LR schedule + RNG key prep sit BETWEEN the phase
                # windows, exactly where the per-step loop runs them —
                # K=1 and K>1 data_wait/compute stay comparable
                lr_list = self._window_lrs(k_now, state)
                keys = jnp.stack([RandomGenerator.next_key()
                                  for _ in range(k_now)])
                # scan xs are strongly typed, unlike the per-step path's
                # weak Python-float lr: stage in default_dtype so the
                # update math promotes identically (a strong f32 lr
                # against bf16 master params would widen the carry)
                lrs = jnp.asarray(lr_list, Engine.default_dtype())
                t1 = time.time()
                # the launch as a live span: what follows it up to the
                # end of optimizer/compute is the wait on the device
                asked = _flash_dispatches()
                with telemetry.span("optimizer/window/dispatch",
                                    step=state["neval"], steps=k_now):
                    if rotating or device_feed:
                        params, opt_state, model_state, losses = \
                            window_fn(params, opt_state, model_state,
                                      keys, lrs, *wargs)
                    else:
                        params, opt_state, model_state, losses = \
                            host_window_fn(params, opt_state, model_state,
                                           keys, lrs, inp, tgt)
                # a dispatch that traced its program asked the flash
                # dispatch once an attention call, on this thread
                took, declined = (now - before for now, before
                                  in zip(_flash_dispatches(), asked))
                if took + declined:
                    _ATTN_IN_KERNEL.observe(took / (took + declined))
                if window_synced is not None:
                    _WINDOW_GAP.observe(
                        (time.monotonic() - window_synced) * 1e3)
                # THE one sync per window: the losses fetch only gates
                # the loss path, so close the timing window on the full
                # outputs first (sanctioned window-boundary sync)
                jax.block_until_ready((params, opt_state, model_state))  # bigdl: disable=sync-in-loop
                window_synced = time.monotonic()
                loss_vals = _losses_list(losses, k_now)
                t_compute = time.time() - t1
                if track_scaler and telemetry.enabled():
                    _record_scaler_gauges(opt_state)
                if telemetry.enabled():
                    # per-WINDOW records (amortized granularity — see
                    # docs/performance.md); phase SUMS still equal the
                    # Metrics sums, so diagnose's invariant holds
                    telemetry.record("optimizer/data_wait", t_data,
                                     step=state["neval"])
                    telemetry.record("optimizer/compute", t_compute,
                                     step=state["neval"], steps=k_now)
                _STEP_COUNT.inc(k_now)
                _RECORD_COUNT.inc(sum(sizes))
                self.metrics.add("data time", t_data)
                self.metrics.add("computing time", t_compute)
                if telemetry.programs.enabled() and t_compute > 0:
                    # the measured window rate turns the registered
                    # analytic FLOPs into achieved-TFLOPs/MFU gauges
                    telemetry.programs.record_rate(
                        train_program_name(model, "window"),
                        sum(sizes) / t_compute)
                telemetry.flight.note_metrics({"step": state["neval"]})
                telemetry.agg.maybe_ship()
                rate = sum(sizes) / max(1e-9, t_data + t_compute)
                with telemetry.span("optimizer/window/replay",
                                    step=state["neval"], steps=k_now):
                    for i in range(k_now):
                        post_step(loss_vals[i], lr_list[i], sizes[i], rate)
                continue

            # ---- classic per-step path (k == 1) ---------------------
            window_synced = None  # a per-step tail is no window gap
            if rotating or device_feed:
                bsz = self.dataset.batch_size
                step_args = device_cursor_args()
                run_step = fused_step
            else:
                batch = window_batches[0] if window_batches \
                    else pull_batch()
                inp, tgt = self._prep_io(batch)
                # device_put above only DISPATCHED the transfer; without
                # this barrier the copy time would silently migrate into
                # t_compute and the data-vs-compute attribution would lie
                # (sanctioned per-step sync; steps_per_sync amortizes it)
                jax.block_until_ready((inp, tgt))  # bigdl: disable=sync-in-loop
                bsz = batch.size()
                step_args = (inp, tgt)
                run_step = step
            t_data = time.time() - t0
            # trace carries the EXACT t_data the Metrics dump reports,
            # so diagnose's phase attribution and Metrics.summary()
            # agree to the digit (enabled() hoist: the disabled path
            # must do no dict/label work in the hot loop)
            if telemetry.enabled():
                telemetry.record("optimizer/data_wait", t_data,
                                 step=state["neval"])

            lr = self.optim_method.update_hyper_parameter()
            rng = RandomGenerator.next_key()
            t1 = time.time()
            params, opt_state, model_state, loss = run_step(
                params, opt_state, model_state, rng, lr, *step_args)
            # fetching the loss scalar only gates on the loss VALUE; the
            # param/optimizer updates it does not depend on may still be
            # in flight, so close the timing window on the full outputs
            # (sanctioned per-step sync; steps_per_sync amortizes it)
            jax.block_until_ready((params, opt_state, model_state))  # bigdl: disable=sync-in-loop
            loss_f = _to_scalar(loss)
            t_compute = time.time() - t1
            if track_scaler and telemetry.enabled():
                _record_scaler_gauges(opt_state)
            if telemetry.enabled():
                telemetry.record("optimizer/compute", t_compute,
                                 step=state["neval"])
            _STEP_COUNT.inc()
            _RECORD_COUNT.inc(bsz)
            self.metrics.add("data time", t_data)
            self.metrics.add("computing time", t_compute)
            if telemetry.programs.enabled() and t_compute > 0:
                telemetry.programs.record_rate(
                    train_program_name(model), bsz / t_compute)
            telemetry.flight.note_metrics({"step": state["neval"]})
            telemetry.agg.maybe_ship()
            post_step(loss_f, lr, bsz,
                      bsz / max(1e-9, t_data + t_compute))

        # a run shorter than the ship interval must still leave its
        # end-of-run totals in the fleet snapshot file
        telemetry.agg.maybe_ship(force=True)
        logger.info("training done in %.1fs; %s", time.time() - wall_start,
                    self.metrics.summary())
        # the run is over: a checkpoint still on the background writer
        # must land (or surface its failure) before optimize() returns
        self._flush_ckpt_writer()
        # write trained params back to the stateful module (multi-host
        # safe: ZeRO-1 can leave updated params data-sharded, and a
        # spanning shard is not plain-readable — host_value reshards).
        # Under a master-weights policy the f32 MASTER copy is the
        # canonical result — the at-rest low-precision params are its
        # rounding, and downstream consumers (export, further finetunes)
        # want the full-precision weights.
        from bigdl_tpu.utils.serialization import host_value
        final_params = opt_state[MASTER_KEY] \
            if isinstance(opt_state, dict) and MASTER_KEY in opt_state \
            else params
        model.set_parameters(jax.tree.map(host_value, final_params))
        model.set_state(jax.tree.map(host_value, model_state))
        return model


class LocalOptimizer(Optimizer):
    """Single-process training on whatever single device jax default is
    (optim/LocalOptimizer.scala:41)."""

    def __init__(self, model, dataset, criterion, batch_size: int = 32):
        super().__init__(model, dataset, criterion, batch_size, mesh=None)


class DistriOptimizer(Optimizer):
    """Synchronous data-parallel training over the Engine mesh
    (optim/DistriOptimizer.scala:728)."""

    def __init__(self, model, dataset, criterion, batch_size: int = 32,
                 mesh: Optional[jax.sharding.Mesh] = None):
        super().__init__(model, dataset, criterion, batch_size,
                         mesh=mesh or Engine.mesh())
