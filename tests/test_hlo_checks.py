"""Static HLO verifier (analysis/hlo.py + analysis/checks/): parser
goldens (incl. the tuple-typed async -start collectives real TPU
schedules emit), each check's clean + seeded-mutant fixture, the
zero.py back-compat shims, and the zero-execution contract — program
verification lowers and compiles, never runs."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.analysis.hlo import (ProgramSpec, available_checks,
                                    collective_counts, format_findings,
                                    hbm_fit, parse_hlo,
                                    reduce_scatter_evidence, run_checks)
from bigdl_tpu.analysis import programs as progs
from bigdl_tpu.nn.module import Module
from bigdl_tpu.optim import SGD
from bigdl_tpu.optim.optimizer import build_train_step
from bigdl_tpu.utils.random import RandomGenerator


@pytest.fixture(scope="module")
def devices8():
    d = jax.devices()
    if len(d) < 8:
        pytest.skip("needs 8 virtual devices")
    return d[:8]


GOLDEN = """\
HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }, entry_computation_layout={(f32[16]{0})->f32[16]{0}}
%body (p: f32[16]) -> f32[16] {
  %ag = f32[16]{0} all-gather(%p), replica_groups={}
  %ar = f32[2]{0} all-reduce(%p), to_apply=%sum
  ROOT %ds = f32[2]{0} dynamic-slice(%ar, %i), dynamic_slice_sizes={2}
}
ENTRY %main (x: f32[16]) -> f32[16] {
  %g = f32[16]{0} all-gather(%x), replica_groups={}
  %p0 = f32[16]{0} parameter(0), sharding={replicated}
  ROOT %w = f32[16]{0} while(%x), body=%body, condition=%cond
}
"""

ASYNC = """\
HloModule jit_async, buffer_donor={ (1, {}), (3, {}) }
ENTRY %main (x: f32[2,4]) -> f32[16,4] {
  %ags = (f32[2,4]{1,0}, f32[16,4]{1,0}) all-gather-start(%x), dimensions={0}
  %agd = f32[16,4]{1,0} all-gather-done(%ags)
  %rss = ((f32[16]{0}), f32[2]{0}) reduce-scatter-start(%y), dimensions={0}
  ROOT %rsd = f32[2]{0} reduce-scatter-done(%rss)
}
"""


# ------------------------------------------------------------------ parser

def test_parser_structure_and_links():
    m = parse_hlo(GOLDEN)
    assert set(m.computations) == {"body", "main"}
    assert m.entry is m.computations["main"] and m.entry.is_entry
    assert not m.computations["body"].is_entry
    w = m.entry.op("w")
    assert w.is_root and w.opcode == "while"
    assert w.called == {"body": "body", "condition": "cond"}
    ag = m.computations["body"].op("ag")
    assert ag.opcode == "all-gather" and ag.operands == ["p"]
    assert ag.dtype == "f32" and ag.dims == (16,)
    assert ag.result_bytes() == 64
    p0 = m.entry.op("p0")
    assert p0.parameter_index == 0 and p0.sharding == "replicated" \
        and p0.replicated


def test_parser_alias_and_donor_tables():
    m = parse_hlo(GOLDEN)
    assert m.aliased_params == {0, 2}
    a = parse_hlo(ASYNC)
    assert a.donor_params == {1, 3}
    assert a.donated_params == {1, 3}


def test_parser_async_tuple_start_ops():
    m = parse_hlo(ASYNC)
    ags = m.entry.op("ags")
    assert ags.opcode == "all-gather-start"
    # both leaves of the tuple type parsed
    assert ags.shapes == (("f32", (2, 4)), ("f32", (16, 4)))
    counts = collective_counts(m)
    assert counts["all-gather"] == {"total": 1, "entry": 1}
    assert counts["reduce-scatter"] == {"total": 1, "entry": 1}


def test_collective_counts_and_zero_shim_agree():
    """The parallel.zero spellings are deprecated shims over the ONE
    structural parser — byte-identical results on the goldens."""
    from bigdl_tpu.parallel import zero
    for text in (GOLDEN, ASYNC):
        assert zero.collective_counts(text) == collective_counts(text)
    counts = collective_counts(GOLDEN)
    assert counts["all-gather"] == {"total": 2, "entry": 1}
    assert counts["all-reduce"] == {"total": 1, "entry": 0}
    assert reduce_scatter_evidence(counts)
    assert zero.reduce_scatter_evidence(counts)


def test_tpu_fused_reduce_scatter_counts_as_one():
    """What the v5e compiler really writes for ZeRO-2's gradient
    reduce-scatter (libtpu 0.0.34): a ``fusion`` calling an
    ``all-reduce-scatter.N`` computation — all-reduce + dynamic-slice
    inside. It counts as ONE reduce-scatter, where the fusion stands."""
    text = """\
HloModule jit_step, is_scheduled=true

%add.1 (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %s = bf16[] add(%a, %b)
}

%all-reduce-scatter.10 (input.10: bf16[3072,768]) -> bf16[768,768] {
  %input.10 = bf16[3072,768]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.80 = bf16[3072,768]{1,0:T(8,128)(2,1)} all-reduce(%input.10), channel_id=104, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.1
  %partition-id.1 = u32[] partition-id()
  ROOT %dynamic-slice.9 = bf16[768,768]{1,0:T(8,128)(2,1)} dynamic-slice(%all-reduce.80, %partition-id.1, %partition-id.1), dynamic_slice_sizes={768,768}
}

ENTRY %main.1 (p0: bf16[3072,768]) -> bf16[768,768] {
  %p0 = bf16[3072,768]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %fusion.14 = bf16[768,768]{1,0:T(8,128)(2,1)} fusion(%p0), kind=kCustom, calls=%all-reduce-scatter.10
}
"""
    counts = collective_counts(text)
    assert counts["reduce-scatter"] == {"total": 1, "entry": 1}
    assert counts["all-reduce"] == {"total": 1, "entry": 0}
    assert reduce_scatter_evidence(counts)


def test_parser_lowered_bare_operands_def_use():
    """Lowered (pre-optimization) HLO writes operands without types —
    def-use edges must still resolve dtypes (the precision check's
    foundation)."""
    text = """\
HloModule jit_f
ENTRY main.4 {
  Arg_0.1 = bf16[4,8]{1,0} parameter(0)
  convert.2 = f32[4,8]{1,0} convert(Arg_0.1)
  multiply.3 = f32[4,8]{1,0} multiply(convert.2, convert.2)
  ROOT dot.4 = f32[4,4]{1,0} dot(multiply.3, convert.2), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}
"""
    m = parse_hlo(text)
    dot = m.entry.op("dot.4")
    assert dot.operands == ["multiply.3", "convert.2"]
    assert m.entry.operand_dtypes(dot) == ["f32", "f32"]
    assert m.entry.operand_op(dot, 0).opcode == "multiply"


# --------------------------------------------------- donation fixtures

def _mlp():
    RandomGenerator.set_seed(7)
    m = nn.Sequential().add(nn.Linear(16, 32)).add(nn.Tanh()) \
        .add(nn.Linear(32, 4)).add(nn.LogSoftMax())
    m.training().ensure_initialized()
    return m


@pytest.fixture(scope="module")
def donation_specs():
    """The same train step lowered WITH donation (clean) and WITHOUT
    (the seeded mutant: declared donation that the compiled program
    cannot honor)."""
    model = _mlp()
    optim = SGD(learning_rate=0.1, momentum=0.9)
    params, opt_state, mstate = progs._train_abstract(model, optim)
    step = build_train_step(model, nn.ClassNLLCriterion(), optim)
    args = (params, opt_state, mstate, progs._key_struct(),
            progs._sds((), np.float32), progs._sds((8, 16), np.float32),
            progs._sds((8,), np.float32))
    clean = progs.spec_from_lowered("fixture/donated", step.lower(*args))

    def undonated(p, o, m, key, lr, x, y):  # the mutant: no donation
        return step(p, o, m, key, lr, x, y)

    mutant = progs.spec_from_lowered(
        "fixture/undonated", jax.jit(undonated).lower(*args),
        donated=clean.donated)  # contract says leaves SHOULD donate
    return clean, mutant


def test_donation_dropped_clean(donation_specs):
    clean, _ = donation_specs
    assert clean.donated > 0
    assert not run_checks([clean], checks=["donation-dropped"])


def test_donation_dropped_mutant(donation_specs):
    _, mutant = donation_specs
    findings = run_checks([mutant], checks=["donation-dropped"])
    assert len(findings) == 1
    f = findings[0]
    assert f.check == "donation-dropped" and f.severity == "error"
    assert f"{mutant.donated} leaves declared donated but only 0" \
        in f.message


# ------------------------------------------------- windowed collectives

@pytest.fixture(scope="module")
def window_mutants(devices8):
    """An ENTRY-gather window (clean twin keeps the gather inside the
    scan) and an UNROLLED window pair (K=2, K=8) whose collective count
    scales with K."""
    from bigdl_tpu.parallel import make_mesh
    mesh = make_mesh([8], ["data"], devices8)
    repl = NamedSharding(mesh, P())
    shrd = NamedSharding(mesh, P("data"))

    def body_ops(c, x):
        g = jax.lax.with_sharding_constraint(x.mean(0) * c, shrd)
        c = jax.lax.with_sharding_constraint(c - g, repl)
        return c, g.sum()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def win_clean(p, xs):
        return jax.lax.scan(body_ops, p, xs)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def win_entry_gather(p, xs):
        # the mutant: the gather hoisted OUT of the scan to ENTRY
        p = jax.lax.with_sharding_constraint(p, repl)
        def body(c, x):
            return c - x.mean(0), c.sum()
        return jax.lax.scan(body, p, xs)

    p_sh = progs._sds((8,), np.float32, mesh, P("data"))
    p_re = progs._sds((8,), np.float32, mesh, P())

    def xs(k):
        return progs._sds((k, 16, 8), np.float32, mesh, P(None, "data"))

    clean = progs.spec_from_lowered(
        "fixture/window", win_clean.lower(p_re, xs(4)), window=True,
        scan_length=4)
    hoisted = progs.spec_from_lowered(
        "fixture/window-entry-gather",
        win_entry_gather.lower(p_sh, xs(4)), window=True, scan_length=4)

    def unrolled(k):
        @jax.jit
        def f(p, xs):
            for i in range(k):  # the mutant: K unrolled steps
                p, _ = body_ops(p, xs[i])
            return p
        return progs.spec_from_lowered(
            f"fixture/window-unrolled@k{k}", f.lower(p_re, xs(k)),
            window=True, scan_length=k)

    lo, hi = unrolled(2), unrolled(8)
    hi.companion = lo
    return clean, hoisted, hi


def test_entry_collective_clean(window_mutants):
    clean, _, _ = window_mutants
    assert not run_checks([clean], checks=["entry-collective"])


def test_entry_collective_mutant(window_mutants):
    _, hoisted, _ = window_mutants
    findings = run_checks([hoisted], checks=["entry-collective"])
    assert findings, "hoisted gather must trip entry-collective"
    assert findings[0].severity == "error"
    assert "ENTRY computation" in findings[0].message
    assert "all-gather" in findings[0].message


def test_scan_dispatch_ratio_clean(window_mutants):
    """A scanned window's body appears once whatever K — give the
    clean program a same-shape companion and the ratio check passes."""
    clean, _, _ = window_mutants
    companion = ProgramSpec(name="fixture/window@k2",
                            module=clean.module, window=True,
                            scan_length=2)
    spec = ProgramSpec(name="fixture/window@k4", module=clean.module,
                       window=True, scan_length=4, companion=companion)
    assert not run_checks([spec], checks=["scan-dispatch-ratio"])


def test_scan_dispatch_ratio_mutant(window_mutants):
    _, _, hi = window_mutants
    findings = run_checks([hi], checks=["scan-dispatch-ratio"])
    assert findings, "unrolled window must trip scan-dispatch-ratio"
    assert "grew with K" in findings[0].message


# ------------------------------------------- replicated large operand

@pytest.fixture(scope="module")
def zero_mutant(devices8):
    """A stage-2 step lowered with the optimizer state REPLICATED —
    the placement the ZeRO policy exists to prevent."""
    from bigdl_tpu.parallel import ZeroConfig, make_mesh
    mesh = make_mesh([8], ["data"], devices8)
    cfg = ZeroConfig(stage=2)
    model = _mlp()
    optim = SGD(learning_rate=0.1, momentum=0.9)
    params, opt_state, mstate = progs._train_abstract(model, optim)
    n_params = len(jax.tree.leaves(params))
    n_opt = len(jax.tree.leaves(opt_state))
    params = progs._with_sharding(params, mesh,
                                  jax.tree.map(lambda _: P(), params))
    opt_state = progs._with_sharding(  # the mutant: replicated
        opt_state, mesh, jax.tree.map(lambda _: P(), opt_state))
    mstate = progs._with_sharding(mstate, mesh,
                                  jax.tree.map(lambda _: P(), mstate))
    step = build_train_step(model, nn.ClassNLLCriterion(), optim,
                            zero=cfg, mesh=mesh)
    lowered = step.lower(
        params, opt_state, mstate, progs._key_struct(),
        progs._sds((), np.float32),
        progs._sds((16, 16), np.float32, mesh, P("data")),
        progs._sds((16,), np.float32, mesh, P("data")))
    return progs.spec_from_lowered(
        "fixture/zero2-replicated", lowered, zero_stage=2, ndev=8,
        sharded_params=tuple(range(n_params, n_params + n_opt)),
        large_bytes=1 << 10)


def test_replicated_large_operand_mutant(zero_mutant):
    findings = run_checks([zero_mutant],
                          checks=["replicated-large-operand"])
    assert findings, "replicated opt state must trip the check"
    f = findings[0]
    assert f.severity == "error" and "replicated" in f.message
    assert "8-device mesh" in f.message


def test_replicated_large_operand_needs_zero_context(zero_mutant):
    """Without a declared stage >= 2 context the same program is not a
    violation — replication is the stage-0 contract."""
    spec = ProgramSpec(name="fixture/stage0", module=zero_mutant.module,
                       lowered=zero_mutant.lowered, zero_stage=0,
                       ndev=8, sharded_params=zero_mutant.sharded_params,
                       large_bytes=1 << 10)
    assert not run_checks([spec], checks=["replicated-large-operand"])


# --------------------------------------------------------- precision

class _UpcastLayer(Module):
    """The seeded mutant: an activation-sized astype(f32) followed by
    f32 arithmetic mid-model — real compute escapes the policy."""

    def apply(self, params, state, x, training=False, rng=None):
        wide = x.astype(jnp.float32)  # bigdl: disable=implicit-upcast-in-trace
        return wide * 1.5 + 0.25, state


@pytest.fixture(scope="module")
def precision_specs():
    from bigdl_tpu.precision import PrecisionPolicy
    pol = PrecisionPolicy.bf16_mixed()
    optim = SGD(learning_rate=0.1)

    def build(with_leak):
        RandomGenerator.set_seed(7)
        m = nn.Sequential().add(nn.Linear(64, 64))
        if with_leak:
            m.add(_UpcastLayer())
        m.add(nn.Linear(64, 4)).add(nn.LogSoftMax())
        m.training().ensure_initialized()
        params, opt_state, mstate = progs._train_abstract(m, optim, pol)
        step = build_train_step(m, nn.ClassNLLCriterion(), optim,
                                precision=pol)
        lowered = step.lower(
            params, opt_state, mstate, progs._key_struct(),
            progs._sds((), np.float32),
            progs._sds((64, 64), np.float32),
            progs._sds((64,), np.float32))
        return progs.spec_from_lowered(
            "fixture/bf16" + ("-leak" if with_leak else ""), lowered,
            policy="bf16_mixed", compute_dtype="bf16")

    return build(False), build(True)


def test_precision_leak_clean(precision_specs):
    clean, _ = precision_specs
    assert not run_checks([clean], checks=["precision-leak"])


def test_precision_leak_mutant(precision_specs):
    _, leak = precision_specs
    findings = run_checks([leak], checks=["precision-leak"])
    assert findings, "astype(f32) before a matmul must trip the check"
    f = findings[0]
    assert f.severity == "error"
    assert "bf16_mixed policy" in f.message and "f32" in f.message


def test_precision_leak_ignores_f32_policy(precision_specs):
    _, leak = precision_specs
    spec = ProgramSpec(name="f32", module=leak.module,
                       lowered=leak.lowered, policy="f32",
                       compute_dtype=None)
    assert not run_checks([spec], checks=["precision-leak"])


# --------------------------------------------------------------- HBM

def test_hbm_over_budget(donation_specs):
    clean, _ = donation_specs
    assert clean.memory is not None
    ok = ProgramSpec(name="fits", memory=clean.memory,
                     hbm_budget=64 << 30)
    bad = ProgramSpec(name="oom", memory=clean.memory, hbm_budget=16)
    assert not run_checks([ok], checks=["hbm-over-budget"])
    findings = run_checks([bad], checks=["hbm-over-budget"])
    assert findings and "16-byte per-device budget" in findings[0].message


def test_hbm_fit_autotuner_api(donation_specs):
    """The autotuner-facing primitive: pure dict in, verdict out —
    prune infeasible candidate configs without compiling them twice or
    running anything."""
    clean, _ = donation_specs
    fit = hbm_fit(clean.memory, None)
    assert fit["fits"] and fit["budget_bytes"] is None
    fit = hbm_fit(clean.memory, 8)
    assert not fit["fits"]
    assert fit["total_bytes"] == int(sum(fit["breakdown"].values()))


# ----------------------------------------------------- engine behaviors

def test_findings_suppression_and_report(donation_specs):
    _, mutant = donation_specs
    spec = ProgramSpec(name=mutant.name, module=mutant.module,
                       donated=mutant.donated,
                       suppress=("donation-dropped",))
    findings = run_checks([spec], checks=["donation-dropped"])
    assert findings and findings[0].suppressed
    report = format_findings(findings, programs=1)
    assert "0 program findings (1 suppressed)" in report
    assert "(suppressed)" in findings[0].format()
    d = findings[0].to_dict()
    assert d["suppressed"] and d["check"] == "donation-dropped"


def test_available_checks_covers_the_six():
    names = {c.name for c in available_checks()}
    assert {"donation-dropped", "entry-collective",
            "replicated-large-operand", "precision-leak",
            "hbm-over-budget", "scan-dispatch-ratio"} <= names


def test_unknown_check_raises():
    with pytest.raises(KeyError):
        run_checks([ProgramSpec(name="x")], checks=["no-such-check"])


def test_verification_compiles_but_never_executes():
    """The acceptance contract: building a spec + running checks is
    lowering/AOT-compiling only — the execution path is never entered
    (asserted via the compile counter and an execute counter)."""
    from _compile_counter import count_compiles
    from jax._src.interpreters import pxla

    model = _mlp()
    optim = SGD(learning_rate=0.1, momentum=0.9)
    params, opt_state, mstate = progs._train_abstract(model, optim)
    step = build_train_step(model, nn.ClassNLLCriterion(), optim)

    execs = []
    orig_call = pxla.ExecuteReplicated.__call__

    def counting_call(self, *a, **k):
        execs.append(1)
        return orig_call(self, *a, **k)

    pxla.ExecuteReplicated.__call__ = counting_call
    try:
        with count_compiles() as compiles:
            lowered = step.lower(
                params, opt_state, mstate, progs._key_struct(),
                progs._sds((), np.float32),
                progs._sds((8, 16), np.float32),
                progs._sds((8,), np.float32))
            spec = progs.spec_from_lowered("exec-proof/step", lowered)
            findings = run_checks([spec])
    finally:
        pxla.ExecuteReplicated.__call__ = orig_call
    assert compiles, "verification must have AOT-compiled the program"
    assert execs == [], f"verification executed {len(execs)} programs"
    assert not [f for f in findings if not f.suppressed]


def test_check_compiled_program_and_profile_verdict(donation_specs):
    """The telemetry.programs integration: compile-site verification
    attaches a verdict to the profile, diagnose renders it, and
    ``to_dict`` ships it (the flight-recorder programs.json path)."""
    from bigdl_tpu.telemetry.programs import ProgramRegistry
    from bigdl_tpu.tools.diagnose import _device_lines, device_summary

    clean, mutant = donation_specs
    r = ProgramRegistry(metrics=__import__(
        "bigdl_tpu.telemetry", fromlist=["telemetry"]).MetricsRegistry())
    r.register("fixture/undonated", "train", analysis={})
    findings = run_checks([mutant], checks=["donation-dropped"])
    r.attach_checks("fixture/undonated", findings)
    prof = r.get("fixture/undonated")
    assert prof.checks is not None and not prof.checks["clean"]
    assert prof.checks["findings"][0]["check"] == "donation-dropped"
    assert prof.to_dict()["checks"] == prof.checks  # bundles ship it

    r.register("fixture/clean", "train", analysis={})
    r.attach_checks("fixture/clean", [])
    rows = device_summary([p.to_dict() for p in r.profiles()])
    lines = _device_lines(rows)
    joined = "\n".join(lines)
    assert "checks clean" in joined
    assert "1 finding [donation-dropped]" in joined


def test_compile_site_checks_attach_to_profile():
    """BIGDL_PROGRAM_CHECKS path: with profiling + checks enabled, a
    program compiled through maybe_wrap_jitted verifies itself at the
    compile site and carries the verdict on its profile (what diagnose
    prints and flight bundles ship)."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import programs as tp

    model = _mlp()
    optim = SGD(learning_rate=0.1, momentum=0.9)
    step = build_train_step(model, nn.ClassNLLCriterion(), optim)
    reg = tp.ProgramRegistry(metrics=telemetry.MetricsRegistry())
    wrapped = tp._ProfiledProgram(
        "selfcheck/step", "train", step,
        donation="params,opt_state,model_state", prog_registry=reg)
    params = model.get_parameters()
    opt_state = optim.init_state(params)
    x = np.zeros((8, 16), np.float32)
    y = np.ones((8,), np.float32)
    was = tp.checks_enabled()
    tp.enable_checks()
    try:
        wrapped(params, opt_state, model.get_state(),
                jax.random.PRNGKey(0), 0.1, x, y)
    finally:
        if not was:
            tp.disable_checks()
    prof = reg.get("selfcheck/step")
    assert prof is not None and prof.checks is not None
    assert prof.checks["clean"], prof.checks
    assert prof.to_dict()["checks"]["clean"]
