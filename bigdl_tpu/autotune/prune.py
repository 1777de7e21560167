"""Static candidate pruning: price configs before anything runs.

Two gates, both execution-free, both auditable:

1. **Footprint gate** — a per-candidate HBM lower bound from
   ``jax.eval_shape`` alone (abstract trees, zero ``backend_compile``
   calls, zero device transfers) priced through
   :func:`bigdl_tpu.analysis.hlo.hbm_fit`. The bound counts what the
   program must pin no matter how XLA schedules it — resident state,
   the batch window, a gradient-sized temp — so anything it rejects is
   truly infeasible. Candidates pruned here are NEVER compiled (the
   test suite asserts this with a ``backend_compile`` counter).
2. **Contract gate** — survivors are lowered + AOT-compiled (still
   zero executions, the ``analysis/programs`` dry-run regime) into a
   :class:`~bigdl_tpu.analysis.hlo.ProgramSpec`; the compiled
   ``memory_analysis`` re-prices HBM exactly via :func:`hbm_fit` and
   the ``check --programs`` contract checks run over the spec —
   contract violators and exact-footprint overflows are dropped with
   the finding text as the reason.

Every dropped candidate lands in :attr:`PruneReport.pruned` with its
stage and reason — the sweep never silently caps anything.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from bigdl_tpu.autotune.space import Candidate

__all__ = ["PrunedCandidate", "PruneReport", "static_prune",
           "train_footprint", "serving_footprint"]


@dataclass(frozen=True)
class PrunedCandidate:
    """One rejected candidate: which gate dropped it and why."""

    candidate: Candidate
    stage: str  # "hbm" | "contract"
    reason: str

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready form (the pruned-candidate log line)."""
        return {"candidate": self.candidate.to_dict(),
                "stage": self.stage, "reason": self.reason}


@dataclass
class PruneReport:
    """The pruner's full verdict: survivors, the pruned list with
    reasons, and the budget everything was priced against."""

    kept: List[Candidate] = field(default_factory=list)
    pruned: List[PrunedCandidate] = field(default_factory=list)
    budget_bytes: Optional[int] = None

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready summary."""
        return {"kept": [c.cid for c in self.kept],
                "pruned": [p.to_dict() for p in self.pruned],
                "budget_bytes": self.budget_bytes}


def _tree_bytes(tree) -> int:
    import jax

    return int(sum(
        int(np.prod(leaf.shape or (1,))) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)))


def _tiny_train_model(name: str):
    """The tuner's tiny model twins, INITIALIZED — same builders the
    static HLO verifier enumerates (``analysis/programs``), so a
    candidate priced here prices the program family the real workload
    scales up. Contract-gate only: initialization executes, so the
    footprint gate uses :func:`_uninit_train_model` instead."""
    from bigdl_tpu.analysis.programs import _mlp, _tiny_lm

    if name == "transformer_lm":
        return _tiny_lm()
    return _mlp()


def _uninit_train_model(name: str):
    """The same twins UNCONSTRUCTED-state: module graph only, no
    ``ensure_initialized`` — pure Python, so the footprint gate stays
    at zero ``backend_compile`` calls (real init compiles the param
    samplers)."""
    if name == "transformer_lm":
        from bigdl_tpu.models import TransformerLM

        return TransformerLM(vocab_size=64, hidden_size=32,
                             num_layers=1, num_heads=4,
                             max_len=16).training()
    import bigdl_tpu.nn as nn

    return nn.Sequential().add(nn.Linear(16, 32)).add(nn.Tanh()) \
        .add(nn.Linear(32, 4)).add(nn.LogSoftMax()).training()


def _abstract_train_state(model, optim, policy):
    """(params, opt_state, mstate) as abstract trees from an
    UNINITIALIZED model — ``analysis/shapecheck``'s device-free idiom:
    ``model.init`` traced under ``jax.eval_shape`` with an abstract
    PRNG key, optimizer/policy state seeded the way
    ``analysis/programs._train_abstract`` does."""
    import jax
    import jax.numpy as jnp

    key_spec = jax.eval_shape(jax.random.PRNGKey,
                              jax.ShapeDtypeStruct((), jnp.uint32))
    params = jax.eval_shape(model.init, key_spec)
    mstate = jax.eval_shape(model.initial_state)

    def seed_state(p):
        opt = optim.init_state(p)
        if policy is not None:
            from bigdl_tpu.precision import (MASTER_KEY, SCALER_KEY,
                                             DynamicLossScaler)
            if policy.needs_master:
                opt[MASTER_KEY] = policy.cast_to_accum(p)
            if policy.needs_loss_scaling:
                opt[SCALER_KEY] = DynamicLossScaler().init_state()
        return opt

    opt_state = jax.eval_shape(seed_state, params)
    if policy is not None and policy.needs_master:
        params = jax.eval_shape(policy.cast_to_param, params)
    return params, opt_state, mstate


def _train_batch_sds(model_name: str, batch: int):
    import jax

    if model_name == "transformer_lm":
        x = jax.ShapeDtypeStruct((batch, 16), np.dtype(np.int32))
        y = jax.ShapeDtypeStruct((batch, 16), np.dtype(np.int32))
    else:
        x = jax.ShapeDtypeStruct((batch, 16), np.dtype(np.float32))
        y = jax.ShapeDtypeStruct((batch,), np.dtype(np.float32))
    return x, y


def _criterion_for(model_name: str):
    import bigdl_tpu.nn as nn

    if model_name == "transformer_lm":
        return nn.SequenceCrossEntropyCriterion()
    return nn.ClassNLLCriterion()


def _policy_for(cand: Candidate):
    from bigdl_tpu.precision import PrecisionPolicy

    name = cand.config["precision"]
    return None if name == "f32" else PrecisionPolicy.named(name)


def train_footprint(cand: Candidate, model_name: str,
                    ndev: int) -> Dict[str, float]:
    """Static per-device HBM lower bound for one train candidate, via
    ``jax.eval_shape`` only (zero compiles, zero executions): resident
    params + optimizer state + model state (ZeRO stage >= 1 shards the
    optimizer state over ``ndev``, stage 3 the params too), the K-step
    batch window, and a gradient-sized temp — the dict
    :func:`~bigdl_tpu.analysis.hlo.hbm_fit` prices."""
    from bigdl_tpu.optim import SGD

    cfg = cand.config
    model = _uninit_train_model(model_name)
    optim = SGD(learning_rate=0.1, momentum=0.9)
    params, opt_state, mstate = _abstract_train_state(
        model, optim, _policy_for(cand))
    k = int(cfg["steps_per_sync"])
    x, y = _train_batch_sds(model_name, int(cfg["batch_size"]))
    param_bytes = _tree_bytes(params)
    opt_bytes = _tree_bytes(opt_state)
    stage = int(cfg["zero_stage"])
    if stage >= 1:
        opt_bytes = opt_bytes // max(ndev, 1)
    if stage >= 3:
        param_bytes = param_bytes // max(ndev, 1)
    batch_bytes = (_tree_bytes(x) + _tree_bytes(y)) * k
    act_bytes = 0.0
    if model_name == "transformer_lm":
        # attention-activation lower bound — the term sequence
        # parallelism shards: the backward keeps the per-layer f32
        # q/k/v/out [B, S, E] tensors live, and under a degree-d SP
        # policy each chip holds S/d of them (that division is exactly
        # why an over-budget dense candidate can become feasible)
        b, s = (int(dim) for dim in x.shape)
        hidden = int(getattr(model, "hidden_size", 32))
        layers = max(int(getattr(model, "num_layers", 1)), 1)
        act_bytes = float(4 * b * s * hidden * 4 * layers)
        sp = int(cfg.get("seq_parallel", 0) or 0)
        if sp > 1:
            act_bytes /= sp
    return {"arg_bytes": float(param_bytes + opt_bytes
                               + _tree_bytes(mstate) + batch_bytes),
            # outputs alias the donated carry in every real step/window
            # program — counting them again would over-price donation
            "out_bytes": 0.0,
            # the backward pass materializes at least one gradient tree
            # plus the (possibly seq-sharded) attention activations
            "temp_bytes": float(param_bytes) + act_bytes}


def serving_footprint(cand: Candidate) -> Dict[str, float]:
    """Static HBM lower bound for one serving candidate: model params
    + the KV cache the slot/ladder geometry implies
    (:meth:`KVCache.spec_for_model` — ShapeDtypeStructs, nothing
    touches a device) + the candidate's prefix-cache budget."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.generation.kv_cache import KVCache
    from bigdl_tpu.models import TransformerLM

    cfg = cand.config
    max_len = int(cfg["length_buckets"][-1])
    # the measure harness's own tiny twin, positional table sized to
    # the candidate's ladder top (the cache time axis) — uninitialized:
    # the cache spec and the abstract param tree need shapes only
    model = TransformerLM(vocab_size=64, hidden_size=32, num_layers=1,
                          num_heads=4, max_len=max_len).evaluate()
    key_spec = jax.eval_shape(jax.random.PRNGKey,
                              jax.ShapeDtypeStruct((), jnp.uint32))
    params = jax.eval_shape(model.init, key_spec)
    cache = KVCache.spec_for_model(model, int(cfg["slots"]), max_len)
    return {"arg_bytes": float(_tree_bytes(params) + _tree_bytes(cache)),
            "out_bytes": 0.0,
            "temp_bytes": float(cfg["prefix_cache_bytes"])}


def _train_spec(cand: Candidate, model_name: str, budget: Optional[int]):
    """Lower + AOT-compile one train candidate's program (zero
    executions) into the ProgramSpec the contract checks consume —
    the K>1 case through ``make_host_window`` exactly like the real
    windowed driver."""
    import jax

    from bigdl_tpu.analysis.programs import (_key_struct,
                                             _train_abstract,
                                             spec_from_lowered)
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import (build_train_step,
                                           make_host_window)

    cfg = cand.config
    model = _tiny_train_model(model_name)
    optim = SGD(learning_rate=0.1, momentum=0.9)
    policy = _policy_for(cand)
    params, opt_state, mstate = _train_abstract(model, optim, policy)
    seq_cfg = None
    sp = int(cfg.get("seq_parallel", 0) or 0)
    if sp > 1:
        from bigdl_tpu.parallel import SeqParallelConfig, make_mesh
        seq_cfg = SeqParallelConfig(
            axis="seq", mesh=make_mesh([sp], ["seq"],
                                       jax.devices()[:sp]))
    step = build_train_step(model, _criterion_for(model_name), optim,
                            precision=policy, seq_parallel=seq_cfg)
    k = int(cfg["steps_per_sync"])
    x, y = _train_batch_sds(model_name, int(cfg["batch_size"]))
    key = _key_struct()
    lr = jax.ShapeDtypeStruct((), np.dtype(np.float32))
    if k > 1:
        window = make_host_window(step)
        keys = jax.ShapeDtypeStruct((k,) + key.shape, key.dtype)
        lrs = jax.ShapeDtypeStruct((k,), np.dtype(np.float32))
        xs = jax.ShapeDtypeStruct((k,) + x.shape, x.dtype)
        ys = jax.ShapeDtypeStruct((k,) + y.shape, y.dtype)
        lowered = window.lower(params, opt_state, mstate, keys, lrs,
                               xs, ys)
    else:
        lowered = step.lower(params, opt_state, mstate, key, lr, x, y)
    pol = cfg["precision"]
    return spec_from_lowered(
        f"autotune/{cand.cid}", lowered,
        window=k > 1, scan_length=k,
        policy=None if pol == "f32" else pol,
        hbm_budget=budget, extra={"kind": "autotune"})


def _contract_gate(cand: Candidate, model_name: str,
                   budget: Optional[int],
                   checks: Optional[Sequence[str]]
                   ) -> Optional[PrunedCandidate]:
    """Lower/compile the candidate and run the static contract checks
    + the exact compiled-footprint ``hbm_fit``; a verdict of None
    keeps the candidate."""
    from bigdl_tpu.analysis.hlo import hbm_fit, run_checks

    from bigdl_tpu import kernels

    try:
        if cand.regime == "train":
            if cand.config.get("flash"):
                kcfg = kernels.KernelConfig.all_on(
                    long_context=bool(
                        cand.config.get("long_context", False)))
            else:
                kcfg = kernels.KernelConfig.off()
            with kernels.use(kcfg):
                spec = _train_spec(cand, model_name, budget)
        else:
            return None  # serving contracts are covered by the
            # verifier's own generation legs; the engine compiles the
            # identical programs at measure time
    except Exception as e:
        return PrunedCandidate(cand, "contract",
                               f"lowering failed: {type(e).__name__}: "
                               f"{e}")
    if spec.memory is not None:
        fit = hbm_fit(spec.memory, budget)
        if not fit["fits"]:
            return PrunedCandidate(
                cand, "contract",
                f"compiled footprint {fit['total_bytes']} bytes over "
                f"budget {budget} ({fit['breakdown']})")
    findings = [f for f in run_checks([spec], checks)
                if not f.suppressed and f.severity == "error"]
    if findings:
        return PrunedCandidate(
            cand, "contract",
            "; ".join(f"{f.check}: {f.message}" for f in findings))
    return None


def static_prune(candidates: Sequence[Candidate], *,
                 hbm_budget: Optional[int] = None,
                 model: Optional[str] = None,
                 ndev: Optional[int] = None,
                 contract_checks: bool = True,
                 checks: Optional[Sequence[str]] = None) -> PruneReport:
    """Run both static gates over ``candidates`` (see module doc).

    ``hbm_budget`` defaults to ``analysis.programs.default_hbm_budget``
    (``BIGDL_HBM_BUDGET_GB``); ``model`` names the train-regime tiny
    twin (default: the space's natural twin, ``mlp`` unless a
    candidate asks for flash); ``contract_checks=False`` skips the
    lowering gate entirely — the footprint gate alone performs ZERO
    ``backend_compile`` calls, which is what the zero-compile test
    asserts. Returns a :class:`PruneReport`; every rejected candidate
    carries its stage and reason."""
    from bigdl_tpu.analysis.hlo import hbm_fit
    from bigdl_tpu.analysis.programs import default_hbm_budget

    budget = default_hbm_budget() if hbm_budget is None else hbm_budget
    if ndev is None:
        import jax
        ndev = len(jax.devices())
    report = PruneReport(budget_bytes=budget)
    for cand in candidates:
        mname = model or str(cand.config.get("model", "mlp"))
        if cand.regime == "train":
            footprint = train_footprint(cand, mname, ndev)
        else:
            footprint = serving_footprint(cand)
        fit = hbm_fit(footprint, budget)
        if not fit["fits"]:
            report.pruned.append(PrunedCandidate(
                cand, "hbm",
                f"static footprint {fit['total_bytes']} bytes over "
                f"budget {budget} ({fit['breakdown']})"))
            continue
        if contract_checks:
            verdict = _contract_gate(cand, mname, budget, checks)
            if verdict is not None:
                report.pruned.append(verdict)
                continue
        report.kept.append(cand)
    return report
