"""Program enumeration for the static HLO verifier.

Builds and **lowers** (never executes) the package's representative
compiled programs — train/eval steps, a ``steps_per_sync`` window, a
ZeRO-2 step on the CPU mesh, a bf16-policy step, a sequence-parallel
window, and the generation
prefill/decode pairs (single-shot and chunked-prefill engines) — into
:class:`~bigdl_tpu.analysis.hlo.ProgramSpec`
records the check registry runs over. ``python -m bigdl_tpu.tools.check
--programs`` is the CLI; ``tests/test_check_self.py`` is the tier-1
gate that keeps the package's own programs clean.

Everything here is abstract: arguments are ``jax.ShapeDtypeStruct``
trees (optimizer state and RNG keys derived via ``jax.eval_shape``), so
enumeration performs **zero executions and zero device transfers** —
lowering and ahead-of-time compilation only, asserted by the
backend-compile/execution counter test. That is exactly the dry-run
regime ROADMAP item 4's autotuner needs: :func:`spec_from_lowered` +
:func:`bigdl_tpu.analysis.hlo.hbm_fit` price a candidate config's HBM
feasibility without running it.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu.analysis.hlo import (ProgramFinding, ProgramSpec,
                                    parse_hlo, run_checks)

__all__ = ["donated_leaf_count", "abstract_tree", "spec_from_lowered",
           "enumerate_programs", "verify_programs",
           "check_compiled_program", "default_hbm_budget"]

#: default per-device HBM budget for the hbm-over-budget check when
#: neither the caller nor BIGDL_HBM_BUDGET_GB says otherwise — generous
#: on purpose (the self-gate verifies feasibility, the autotuner passes
#: the real device budget per candidate)
_DEFAULT_BUDGET_GB = 32.0


def default_hbm_budget() -> int:
    """Per-device HBM budget in bytes (``BIGDL_HBM_BUDGET_GB``
    override)."""
    gb = float(os.environ.get("BIGDL_HBM_BUDGET_GB", _DEFAULT_BUDGET_GB))
    return int(gb * (1 << 30))


def donated_leaf_count(lowered) -> int:
    """How many flat argument leaves the jit declared donated — read
    from the lowering's own ``args_info``, so the expectation and the
    compiled aliasing table come from the same program."""
    import jax

    flat = jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda a: hasattr(a, "donated"))
    return sum(1 for a in flat if a.donated)


def abstract_tree(tree):
    """A ``jax.ShapeDtypeStruct`` tree mirroring ``tree`` (host arrays,
    device arrays or structs alike) — what every lowering here consumes
    instead of live buffers; attach shardings by mapping over the
    result (:func:`_with_sharding`)."""
    import jax

    def leaf(a):
        shape = tuple(getattr(a, "shape", ()) or ())
        dtype = np.dtype(getattr(a, "dtype", np.float32))
        return jax.ShapeDtypeStruct(shape, dtype)

    return jax.tree.map(leaf, tree)


def _key_struct():
    import jax

    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def _sds(shape, dtype, mesh=None, spec=None):
    import jax

    if mesh is None:
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))
    from jax.sharding import NamedSharding
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                sharding=NamedSharding(mesh, spec))


def _with_sharding(tree, mesh, specs):
    """Re-issue an abstract tree with per-leaf NamedShardings."""
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda a, sp: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, sp)),
        tree, specs)


def spec_from_lowered(name: str, lowered, compiled=None,
                      **ctx) -> ProgramSpec:
    """Compile ``lowered`` ahead of time (no execution) and build the
    :class:`ProgramSpec` the checks consume: parsed compiled text
    (aliasing, collective placement), parsed pre-optimization text
    (shardings, dtype intent), ``memory_analysis`` numbers and the
    donated-leaf expectation from ``args_info``. Extra keyword context
    (``window``, ``zero_stage``, ``policy`` ...) passes through to the
    spec; pass ``compiled`` to reuse an already-compiled artifact."""
    if compiled is None:
        compiled = lowered.compile()
    module = parse_hlo(compiled.as_text())
    try:
        lowered_mod = parse_hlo(lowered.as_text(dialect="hlo"))
    except Exception:
        lowered_mod = None  # backend without the HLO dialect printer
    memory = None
    try:
        mem = compiled.memory_analysis()
        memory = {"arg_bytes": float(mem.argument_size_in_bytes),
                  "out_bytes": float(mem.output_size_in_bytes),
                  "temp_bytes": float(mem.temp_size_in_bytes)}
    except Exception:
        pass
    donated = ctx.pop("donated", None)
    if donated is None:
        try:
            donated = donated_leaf_count(lowered)
        except Exception:
            donated = -1
    return ProgramSpec(name=name, module=module, lowered=lowered_mod,
                       donated=donated, memory=memory, **ctx)


# ----------------------------------------------------------- the zoo legs

def _tiny_lm():
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(7)
    m = TransformerLM(vocab_size=64, hidden_size=32, num_layers=1,
                      num_heads=4, max_len=16).training()
    m.ensure_initialized()
    return m


def _mlp():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(7)
    m = nn.Sequential().add(nn.Linear(16, 32)).add(nn.Tanh()) \
        .add(nn.Linear(32, 4)).add(nn.LogSoftMax())
    m.training().ensure_initialized()
    return m


def _lenet():
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(7)
    m = LeNet5(10).training()
    m.ensure_initialized()
    return m


def _train_abstract(model, optim, policy=None):
    """(params, opt_state, model_state) as abstract trees — optimizer
    state (and the precision policy's master/scaler seeds) derived via
    ``jax.eval_shape``, so nothing touches a device."""
    import jax

    params = abstract_tree(model.get_parameters())
    mstate = abstract_tree(model.get_state())

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


def _train_step_spec(name, model, criterion, x_sds, y_sds, *,
                     policy=None, budget=None, suppress=()):
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step

    optim = SGD(learning_rate=0.1, momentum=0.9)
    params, opt_state, mstate = _train_abstract(model, optim, policy)
    step = build_train_step(model, criterion, optim, precision=policy)
    lowered = step.lower(params, opt_state, mstate, _key_struct(),
                         _sds((), np.float32), x_sds, y_sds)
    pol_name = policy.name if policy is not None else None
    compute = policy.compute_dtype.name if policy is not None else None
    if compute == "float16":
        compute = "f16"
    elif compute == "bfloat16":
        compute = "bf16"
    return spec_from_lowered(name, lowered, policy=pol_name,
                             compute_dtype=compute, hbm_budget=budget,
                             suppress=tuple(suppress),
                             extra={"kind": "train"})


def _eval_step_spec(name, model, x_sds, budget=None):
    from bigdl_tpu.optim.optimizer import build_eval_step

    params = abstract_tree(model.get_parameters())
    mstate = abstract_tree(model.get_state())
    step = build_eval_step(model.evaluate())
    lowered = step.lower(params, mstate, x_sds)
    model.training()
    return spec_from_lowered(name, lowered, hbm_budget=budget,
                             extra={"kind": "eval"})


def _window_specs(budget=None) -> List[ProgramSpec]:
    """The ``steps_per_sync`` window contract at K=8 (with a K=2
    companion for scan-dispatch-ratio): on a multi-device CPU mesh the
    window carries a ZeRO-2 sharded optimizer state, so the compiled
    program contains real collectives and the entry-collective check
    verifies the PR 8 dispatch-boundary contract structurally."""
    import jax
    from jax.sharding import PartitionSpec as P

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import (build_train_step,
                                           make_host_window)

    model = _mlp()
    optim = SGD(learning_rate=0.1, momentum=0.9)
    ndev = min(len(jax.devices()), 8)
    mesh = cfg = None
    if ndev > 1:
        from bigdl_tpu.parallel import ZeroConfig, make_mesh
        from bigdl_tpu.parallel.zero import tree_zero_specs
        mesh = make_mesh([ndev], ["data"], jax.devices()[:ndev])
        cfg = ZeroConfig(stage=2)
    params, opt_state, mstate = _train_abstract(model, optim)
    if mesh is not None:
        params = _with_sharding(params, mesh,
                                jax.tree.map(lambda _: P(), params))
        opt_state = _with_sharding(
            opt_state, mesh, tree_zero_specs(opt_state, mesh, cfg))
        mstate = _with_sharding(mstate, mesh,
                                jax.tree.map(lambda _: P(), mstate))
    step = build_train_step(model, nn.ClassNLLCriterion(), optim,
                            zero=cfg, mesh=mesh)
    window = make_host_window(step)
    key = _key_struct()
    rows = 16

    def lower_at(k):
        keys = _sds((k,) + key.shape, key.dtype)
        lrs = _sds((k,), np.float32)
        if mesh is None:
            xs = _sds((k, rows, 16), np.float32)
            ys = _sds((k, rows), np.float32)
        else:
            xs = _sds((k, rows, 16), np.float32, mesh, P(None, "data"))
            ys = _sds((k, rows), np.float32, mesh, P(None, "data"))
        return window.lower(params, opt_state, mstate, keys, lrs, xs, ys)

    shared = dict(window=True, zero_stage=cfg.stage if cfg else 0,
                  ndev=ndev, hbm_budget=budget,
                  extra={"kind": "window"})
    companion = spec_from_lowered("train/mlp/window@k2", lower_at(2),
                                  scan_length=2, **shared)
    spec = spec_from_lowered("train/mlp/window@k8", lower_at(8),
                             scan_length=8, companion=companion,
                             **shared)
    return [spec, companion]


def _zero_step_spec(budget=None) -> Optional[ProgramSpec]:
    """A plain (unwindowed) ZeRO-2 train step on the CPU mesh, with the
    opt-state parameter indices marked for replicated-large-operand.
    None when the process has a single device."""
    import jax
    from jax.sharding import PartitionSpec as P

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.parallel import ZeroConfig, make_mesh
    from bigdl_tpu.parallel.zero import tree_zero_specs

    ndev = min(len(jax.devices()), 8)
    if ndev < 2:
        return None
    mesh = make_mesh([ndev], ["data"], jax.devices()[:ndev])
    cfg = ZeroConfig(stage=2)
    model = _mlp()
    optim = SGD(learning_rate=0.1, momentum=0.9)
    params, opt_state, mstate = _train_abstract(model, optim)
    n_params = len(jax.tree.leaves(params))
    n_opt = len(jax.tree.leaves(opt_state))
    params = _with_sharding(params, mesh,
                            jax.tree.map(lambda _: P(), params))
    opt_state = _with_sharding(
        opt_state, mesh, tree_zero_specs(opt_state, mesh, cfg))
    mstate = _with_sharding(mstate, mesh,
                            jax.tree.map(lambda _: P(), mstate))
    step = build_train_step(model, nn.ClassNLLCriterion(), optim,
                            zero=cfg, mesh=mesh)
    lowered = step.lower(
        params, opt_state, mstate, _key_struct(), _sds((), np.float32),
        _sds((16, 16), np.float32, mesh, P("data")),
        _sds((16,), np.float32, mesh, P("data")))
    return spec_from_lowered(
        "train/mlp/zero2/step", lowered, zero_stage=2, ndev=ndev,
        sharded_params=tuple(range(n_params, n_params + n_opt)),
        # the MLP's leaves are KB-sized; verify their placement anyway
        large_bytes=1 << 10, hbm_budget=budget,
        extra={"kind": "zero"})


def _seq_parallel_window_spec(budget=None) -> Optional[ProgramSpec]:
    """A ``steps_per_sync`` window over a sequence-parallel transformer
    step: build_train_step(seq_parallel=...) on a ["seq"] mesh, K=2.
    This is the structural proof of the long-context composition
    contract — the ring collectives (``collective-permute`` /
    ``all-to-all``, both in the entry-collective check's
    COMMUNICATION_OPS) trace inside the scan body, so the windowed
    dispatch boundary stays collective-free. None (with a note) in a
    single-device process."""
    import jax
    from jax.sharding import PartitionSpec as P

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import (build_train_step,
                                           make_host_window)
    from bigdl_tpu.parallel import SeqParallelConfig, make_mesh

    ndev = min(len(jax.devices()), 8)
    if ndev < 2:
        return None
    mesh = make_mesh([ndev], ["seq"], jax.devices()[:ndev])
    model = _tiny_lm()
    optim = SGD(learning_rate=0.1, momentum=0.9)
    params, opt_state, mstate = _train_abstract(model, optim)
    params = _with_sharding(params, mesh,
                            jax.tree.map(lambda _: P(), params))
    opt_state = _with_sharding(opt_state, mesh,
                               jax.tree.map(lambda _: P(), opt_state))
    mstate = _with_sharding(mstate, mesh,
                            jax.tree.map(lambda _: P(), mstate))
    step = build_train_step(
        model, nn.SequenceCrossEntropyCriterion(), optim, mesh=mesh,
        seq_parallel=SeqParallelConfig(axis="seq", mesh=mesh))
    window = make_host_window(step)
    key = _key_struct()
    keys = _sds((2,) + key.shape, key.dtype)
    lowered = window.lower(
        params, opt_state, mstate, keys, _sds((2,), np.float32),
        _sds((2, 4, 16), np.int32), _sds((2, 4, 16), np.int32))
    return spec_from_lowered(
        "train/transformer_lm/seq_parallel/window@k2", lowered,
        window=True, scan_length=2, ndev=ndev, hbm_budget=budget,
        extra={"kind": "window"})


def _generation_specs(budget=None) -> List[ProgramSpec]:
    """The serving prefill/decode program pair (donated KV cache) via
    the DecodeEngine's enumeration hook — the exact jits the engine
    compiles, lowered over abstract cache/params trees. A second
    engine with ``prefill_chunk`` enumerates the CHUNKED long-prompt
    admission programs: the prefill jit's token operand is chunk-wide
    (never rung-wide), which is the whole point — a 128K rung admits
    through the same fixed-width program, and the donation/boundary
    checks hold for it like any other serving program."""
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache

    model = _tiny_lm()
    params = abstract_tree(model.get_parameters())
    state = abstract_tree(model.get_state())
    out = []
    for tag, engine in (
            ("", DecodeEngine(CompileCache(),
                              BucketLadder(16, buckets=(16,)),
                              slots=4, prefill_rows=2)),
            ("chunked/", DecodeEngine(CompileCache(),
                                      BucketLadder(16, buckets=(8, 16)),
                                      slots=4, prefill_rows=2,
                                      prefill_chunk=8))):
        for name, jitted, args in engine.abstract_programs(
                model, params, state, kv_dtype=np.float32):
            lowered = jitted.lower(*args)
            out.append(spec_from_lowered(
                f"serving/transformer_lm/{tag}{name}", lowered,
                hbm_budget=budget, extra={"kind": "serving"}))
    return out


def _serving_eval_spec(budget=None) -> ProgramSpec:
    """One bucketed serving eval program through the CompileCache's
    enumeration hook (the program ``step_for`` would compile)."""
    from bigdl_tpu.serving.compile_cache import CompileCache

    model = _lenet()
    model.evaluate()
    params = abstract_tree(model.get_parameters())
    state = abstract_tree(model.get_state())
    jitted = CompileCache.abstract_step(model)
    lowered = jitted.lower(params, state, _sds((8, 1, 28, 28),
                                               np.float32))
    model.training()
    return spec_from_lowered("serving/lenet5/eval/8", lowered,
                             hbm_budget=budget,
                             extra={"kind": "serving"})


def enumerate_programs(hbm_budget: Optional[int] = None
                       ) -> Tuple[List[ProgramSpec], List[str]]:
    """Build + lower the verification suite; returns ``(specs,
    notes)`` — notes name legs that were skipped (single-device
    process) so reports stay honest about coverage."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.precision import PrecisionPolicy

    budget = default_hbm_budget() if hbm_budget is None else hbm_budget
    notes: List[str] = []
    specs: List[ProgramSpec] = []

    lenet = _lenet()
    specs.append(_train_step_spec(
        "train/lenet5/step", lenet, nn.ClassNLLCriterion(),
        _sds((8, 1, 28, 28), np.float32), _sds((8,), np.float32),
        budget=budget))
    specs.append(_eval_step_spec("train/lenet5/eval", lenet,
                                 _sds((8, 1, 28, 28), np.float32),
                                 budget=budget))
    lm = _tiny_lm()
    specs.append(_train_step_spec(
        "train/transformer_lm/step", lm,
        nn.SequenceCrossEntropyCriterion(),
        _sds((4, 16), np.int32), _sds((4, 16), np.int32),
        budget=budget))
    specs.append(_train_step_spec(
        "train/transformer_lm/step@bf16", _tiny_lm(),
        nn.SequenceCrossEntropyCriterion(),
        _sds((4, 16), np.int32), _sds((4, 16), np.int32),
        policy=PrecisionPolicy.bf16_mixed(), budget=budget))
    specs.extend(_window_specs(budget))
    zero = _zero_step_spec(budget)
    if zero is not None:
        specs.append(zero)
    else:
        notes.append("zero leg skipped (single-device process; run "
                     "under XLA_FLAGS=--xla_force_host_platform_"
                     "device_count=8 for the mesh contract)")
    sp = _seq_parallel_window_spec(budget)
    if sp is not None:
        specs.append(sp)
    else:
        notes.append("seq-parallel window leg skipped (needs a "
                     "multi-device process; the entry-collective "
                     "contract for ring/Ulysses collectives is "
                     "verified where there is one)")
    specs.append(_serving_eval_spec(budget))
    specs.extend(_generation_specs(budget))
    return specs, notes


def verify_programs(checks: Optional[Sequence[str]] = None,
                    hbm_budget: Optional[int] = None
                    ) -> Tuple[List[ProgramFinding], List[ProgramSpec],
                               List[str]]:
    """Enumerate the suite and run the (optionally restricted) check
    set: ``(findings, specs, notes)``. Lowering/compiling only — zero
    executions (tested)."""
    specs, notes = enumerate_programs(hbm_budget)
    return run_checks(specs, checks), specs, notes


def check_compiled_program(name: str, lowered, compiled,
                           scan_length: int = 1,
                           hbm_budget: Optional[int] = None
                           ) -> List[Dict[str, object]]:
    """Context-light verification of ONE freshly compiled program —
    the ``telemetry.programs`` compile-site hook (enable with
    ``BIGDL_PROGRAM_CHECKS=1``): donation, dispatch-boundary and HBM
    checks run with whatever context the jit itself carries; policy/
    ZeRO contracts need the enumerated suite. Returns finding dicts
    (what ``ProgramProfile.checks`` stores and flight-recorder
    ``programs.json`` bundles ship)."""
    spec = spec_from_lowered(
        name, lowered, compiled=compiled,
        window=scan_length > 1, scan_length=scan_length,
        hbm_budget=default_hbm_budget() if hbm_budget is None
        else hbm_budget)
    return [f.to_dict() for f in run_checks([spec])]
