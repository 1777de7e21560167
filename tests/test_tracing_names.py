"""Names a trace can carry (ISSUE 26): the decode loop's iteration in
telemetry spans, the spans on the profiler's clock, the jitted serving
programs and the Pallas kernels under stable names, the transformer's
blocks under a fixed vocabulary of ``jax.named_scope``s, and the train
window's dispatch/replay spans with the always-on gap histogram.

All on the CPU at a tiny size: what the names look like in a chip's
trace is in PERF.md."""
import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.generation import GenerationConfig, GenerationService
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.utils.random import RandomGenerator

#: docs/telemetry.md "Scopes": by role, never by layer index
SCOPES = ("embed", "attn/qkv", "attn/core", "attn/out", "attn/kv_write",
          "mlp", "norm", "lm_head", "loss", "optim_update")
LOOP_SPANS = ("serving/idle", "serving/housekeep", "serving/admit",
              "serving/prepare", "serving/decode", "serving/sample")
STEP_CHILDREN = ("serving/decode/dispatch", "serving/decode/device_wait",
                 "serving/decode/logits_d2h")


def _model(hidden=32, layers=2, vocab=50):
    RandomGenerator.set_seed(42)
    m = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                      num_layers=layers, num_heads=4,
                      max_len=32).evaluate()
    m.ensure_initialized()
    return m


def _serve(n_requests=6, new_tokens=6, **model):
    """A tiny service driven to completion, then idle for a moment (so
    that the loop's wait is on record too) and shut down."""
    svc = GenerationService(config=GenerationConfig(
        slots=4, max_len=16, length_buckets=(16,), prefill_rows=2))
    svc.load("lm", _model(**model))
    try:
        streams = [svc.generate("lm", [1 + i, 2, 3],
                                max_new_tokens=new_tokens)
                   for i in range(n_requests)]
        for s in streams:
            assert len(s.result(timeout=120)) == new_tokens
        # a second wave after the loop went idle: serving/idle closes
        # when it wakes
        svc.generate("lm", [4, 5], max_new_tokens=2).result(timeout=120)
    finally:
        svc.shutdown()


def _traced_serve():
    """The spans one small run recorded, and the tracer's thread names.
    The model is wide enough for a step to outweigh the few dozen
    microseconds of bookkeeping between two spans."""
    telemetry.tracer().clear()
    telemetry.enable()
    try:
        _serve(new_tokens=10, hidden=256, layers=4, vocab=2000)
    finally:
        telemetry.disable()
    spans = telemetry.tracer().spans()
    names = {e["tid"]: e["args"]["name"]
             for e in telemetry.tracer().chrome_trace_events()
             if e["ph"] == "M"}
    telemetry.tracer().clear()
    return spans, names


@pytest.fixture(scope="module")
def traced_serve():
    return _traced_serve()


#: span names use the instruments' alphabet (``telemetry.NAME_RE``) in
#: two or three segments, as ``serving/prefill`` and
#: ``serving/request/decode`` always have
_SPAN_NAME = re.compile(r"^[a-z0-9_]+(/[a-z0-9_]+){1,2}$")


def _inside(child, parent):
    return (child.tid == parent.tid and child.ts >= parent.ts
            and child.ts + child.dur <= parent.ts + parent.dur + 1e-9)


@pytest.mark.parametrize("span,parent", [
    ("serving/idle", None), ("serving/housekeep", None),
    ("serving/admit", None),
    ("serving/prefill", "serving/admit"),
    ("serving/prefill/device_wait", "serving/prefill"),
    ("serving/prefill/logits_d2h", "serving/prefill"),
    ("serving/prepare", None), ("serving/decode", None),
    ("serving/decode/dispatch", "serving/decode"),
    ("serving/decode/device_wait", "serving/decode"),
    ("serving/decode/logits_d2h", "serving/decode"),
    ("serving/sample", None)])
def test_the_loops_iteration_is_in_spans(traced_serve, span, parent):
    """Every span of the table is recorded, on the engine's thread,
    directly under the parent the table gives it."""
    spans, thread_names = traced_serve
    mine = [s for s in spans if s.name == span]
    assert mine, f"no {span} span was recorded"
    for s in mine:
        assert thread_names[s.tid] == "serving-decode-lm"
        assert _SPAN_NAME.match(s.name)
        if parent is None:
            assert s.depth == 0
            continue
        holders = [p for p in spans
                   if p.name == parent and _inside(s, p)]
        assert len(holders) == 1 and s.depth == holders[0].depth + 1


def test_a_decode_step_is_at_least_its_three_children(traced_serve):
    spans, _ = traced_serve
    steps = [s for s in spans if s.name == "serving/decode"]
    assert len(steps) >= 9
    for step in steps:
        parts = [c for c in spans
                 if c.name in STEP_CHILDREN and _inside(c, step)]
        assert sorted(c.name for c in parts) == sorted(STEP_CHILDREN)
        assert step.dur >= sum(c.dur for c in parts)


def _loop_coverage(spans):
    (tid,) = {s.tid for s in spans if s.name in LOOP_SPANS}
    top = [s for s in spans if s.tid == tid and s.name in LOOP_SPANS]
    first = min(s.ts for s in top)
    last = max(s.ts + s.dur for s in top)
    covered = sum(s.dur for s in top)
    assert covered <= (last - first) * (1 + 1e-6)     # disjoint
    return covered / (last - first)


def test_six_spans_tile_the_engine_threads_time(traced_serve):
    """idle + housekeep + admit + prepare + decode + sample leave under
    1% of the thread's time between its first and last span unnamed. A
    busy machine takes the thread off the CPU between two spans and can
    only lower the reading, so the best of three runs is judged."""
    best = _loop_coverage(traced_serve[0])
    for _ in range(2):
        if best >= 0.99:
            break
        best = max(best, _loop_coverage(_traced_serve()[0]))
    assert best >= 0.99, (
        f"the six spans cover {best:.1%} of the engine thread's time")


def test_the_ring_holds_no_record_a_token(traced_serve):
    """A request leaves its token-0 record and one tenure record, never
    one a decode step; the decode span carries what the export needs
    to expand the tenure (``step``, ``group``, ``attend_len``)."""
    spans, _ = traced_serve
    requests = sum(s.name == "serving/request/queue_wait" for s in spans)
    assert requests == 7
    assert sum(s.name == "serving/request/decode" for s in spans) \
        == requests
    assert sum(s.name == "serving/request/tenure" for s in spans) \
        == requests
    for s in spans:
        if s.name == "serving/decode":
            assert {"step", "group", "attend_len"} <= set(s.args)


def test_disabled_the_same_run_records_nothing():
    assert not telemetry.enabled()
    telemetry.tracer().clear()
    _serve(n_requests=2, new_tokens=3)
    assert len(telemetry.tracer()) == 0


def test_verify_steps_carry_the_same_children():
    """``DecodeEngine.verify`` (speculative decoding's step) is split
    like ``decode``, under whatever span its caller holds open."""
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.generation.kv_cache import KVCache
    from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache
    from bigdl_tpu.serving.registry import ModelRegistry

    model = _model()
    registry = ModelRegistry()
    registry.load("lm", model)
    servable = registry.current("lm")
    engine = DecodeEngine(CompileCache(), BucketLadder(16), 2, 1)
    kv = KVCache.for_model(model, 2, 16)
    telemetry.tracer().clear()
    telemetry.enable()
    try:
        with telemetry.span("fleet/verify"):
            logits, attend = engine.verify(
                servable, kv, np.ones((2, 3), np.int32),
                np.zeros((2,), np.int32), np.ones((2,), bool))
    finally:
        telemetry.disable()
    assert logits.shape == (2, 3, 50) and attend == 4
    spans = telemetry.tracer().spans()
    telemetry.tracer().clear()
    (outer,) = [s for s in spans if s.name == "fleet/verify"]
    kids = [s for s in spans if s.name in STEP_CHILDREN]
    assert sorted(s.name for s in kids) == sorted(STEP_CHILDREN)
    assert all(_inside(s, outer) and s.depth == 1 for s in kids)


# ------------------------------------------------- the profiler's clock

def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    profile = ProfileData.from_file(path)
    return {ev.name for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_an_enabled_span_is_a_host_event_of_the_profilers_trace(tmp_path):
    """A live span lies in the profiler's own trace under its name; a
    pre-measured ``record()`` stays in the ring alone."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    telemetry.tracer().clear()
    telemetry.enable()
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with telemetry.span("serving/decode/dispatch", slots=2):
                jnp.ones((8,)).block_until_ready()
            telemetry.record("optimizer/compute", 0.01)
        finally:
            jax.profiler.stop_trace()
    finally:
        telemetry.disable()
    ring = {s.name for s in telemetry.tracer().spans()}
    telemetry.tracer().clear()
    assert ring == {"serving/decode/dispatch", "optimizer/compute"}
    host = _host_event_names(str(tmp_path))
    assert "serving/decode/dispatch" in host
    assert "optimizer/compute" not in host


def test_a_disabled_span_reaches_no_profiler(tmp_path):
    assert not telemetry.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("serving/decode/dispatch"):
            jnp.ones((8,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert "serving/decode/dispatch" not in _host_event_names(str(tmp_path))


# ------------------------------------------- program and scope names

@pytest.fixture(scope="module")
def serving_programs():
    """{kind: (jitted function, its lowered text with locations)} of a
    tiny engine's top rung."""
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache

    model = _model()
    engine = DecodeEngine(CompileCache(), BucketLadder(16), 2, 1)
    out = {}
    for name, jitted, args in engine.abstract_programs(
            model, model.get_parameters(), model.get_state()):
        out[name.split("/")[0]] = (
            jitted, jitted.lower(*args).as_text(debug_info=True))
    return out


@pytest.mark.parametrize("kind", ["prefill", "decode", "verify"])
def test_serving_programs_are_named_for_what_they_are(serving_programs,
                                                      kind):
    jitted, text = serving_programs[kind]
    assert jitted.__name__ == f"serving_{kind}"
    assert f"module @jit_serving_{kind}" in text


def _op_names(text):
    """The ``op_name`` paths of a lowered module's locations."""
    return set(re.findall(r'loc\("(jit\([^"]+)"', text))


@pytest.mark.parametrize("scope", [s for s in SCOPES
                                   if s not in ("loss", "optim_update")])
@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_serving_programs_hold_each_scope(serving_programs, kind, scope):
    paths = _op_names(serving_programs[kind][1])
    assert any(p.startswith(f"jit(serving_{kind})/")
               and f"/{scope}/" in p for p in paths), scope


@pytest.fixture(scope="module")
def train_step_text():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.analysis.programs import _key_struct, _train_abstract
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.precision import PrecisionPolicy

    model, optim = _model().training(), Adam(1e-3)
    policy = PrecisionPolicy.named("bf16_mixed")
    params, opt_state, mstate = _train_abstract(model, optim, policy)
    tokens = jax.ShapeDtypeStruct((2, 8), np.int32)
    step = build_train_step(model, nn.SequenceCrossEntropyCriterion(),
                            optim, precision=policy)
    return step.lower(
        params, opt_state, mstate, _key_struct(),
        jax.ShapeDtypeStruct((), np.float32), tokens,
        tokens).as_text(debug_info=True)


@pytest.mark.parametrize("scope", [s for s in SCOPES
                                   if s != "attn/kv_write"])
def test_the_train_step_holds_each_scope(train_step_text, scope):
    """Forward scopes appear bare and, in the backward pass, inside
    ``transpose(jvp(...))``; the update and the loss under their own."""
    paths = _op_names(train_step_text)
    assert any(f"{scope}/" in p or f"{scope})" in p for p in paths), scope
    if scope in ("attn/qkv", "attn/core", "mlp"):
        assert any("transpose(jvp(" + scope in p for p in paths), scope


def test_scopes_name_roles_not_layers(train_step_text, serving_programs):
    for text in (train_step_text, serving_programs["decode"][1]):
        assert not any("block_" in p for p in _op_names(text))


# ---------------------------------------- a hybrid decoder's scopes

HYBRID_SCOPES = ("ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm",
                 "ssm/out_proj", "moe/latent")


@pytest.fixture(scope="module")
def hybrid_programs():
    """The lowered prefill and decode of a tiny decoder with a
    state-space layer, latent experts and an attention layer."""
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.models import PatternDecoderLM
    from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache

    RandomGenerator.set_seed(3)
    model = PatternDecoderLM(
        64, 32, [("ssm", "none"), ("none", "experts"), ("global", "none")],
        2, 1, 16, 0, window=0, max_len=16, rope_layers="none",
        expert_size=24, shared_size=40, router_experts=4, top_k=2,
        block_style="prenorm", qk_norm=False, attn_gate=False,
        expert_activation="relu2", expert_gated=False, latent_size=16,
        ssm=dict(num_heads=2, head_dim=8, state_size=8, groups=1,
                 chunk=4)).evaluate()
    engine = DecodeEngine(CompileCache(), BucketLadder(16), 2, 1)
    programs = engine.abstract_programs(
        model, model.get_parameters(), model.get_state())
    return {name.split("/")[0]: jitted.lower(*args).as_text(debug_info=True)
            for name, jitted, args in programs}


@pytest.mark.parametrize("scope", HYBRID_SCOPES)
@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_a_hybrid_decoder_holds_its_scopes(hybrid_programs, kind, scope):
    paths = _op_names(hybrid_programs[kind])
    assert any(p.startswith(f"jit(serving_{kind})/")
               and f"/{scope}/" in p for p in paths), scope


def test_a_recurrent_model_has_no_verify_program(hybrid_programs):
    assert sorted(hybrid_programs) == ["decode", "prefill"]


# ------------------------------------------------------- kernel names

def _pallas_names(jaxpr):
    """The ``name`` of every ``pallas_call`` in a jaxpr, sub-jaxprs
    (jit, custom_vjp, scan) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _pallas_names(inner)
    return found


def _attention_args(s=128, d=64):
    q = jnp.zeros((1, 2, s, d), jnp.float32)
    return q, q, q


def _kernel_cases():
    from bigdl_tpu.kernels.flash_attention import (
        blockwise_flash_attention, flash_attention)
    from bigdl_tpu.kernels.int8_gemm import pallas_quantized_matmul
    from bigdl_tpu.kernels.ragged_decode import ragged_decode_attention
    from bigdl_tpu.kernels.ssm_decode import ssm_decode_pallas

    def grad_of(attn):
        return jax.grad(lambda q, k, v: attn(q, k, v).sum(),
                        argnums=(0, 1, 2))

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=True)
    blockwise = lambda q, k, v: blockwise_flash_attention(
        q, k, v, causal=True, interpret=True)
    cache = jnp.zeros((2, 2, 8, 16), jnp.float32)   # [slots, H, D, T]
    lengths = jnp.ones((2,), jnp.int32)

    return {
        "ragged_decode": (
            lambda q, k, v: ragged_decode_attention(
                q, k, v, lengths, lengths - 1, k[..., 0], v[..., 0],
                interpret=True),
            (jnp.zeros((2, 2, 8)), cache, cache),
            {"bigdl_ragged_decode"}),
        "ssm_decode": (
            lambda state, dec, dtx, bc: ssm_decode_pallas(
                state, dec, dtx, bc, interpret=True),
            (jnp.zeros((2, 2, 8, 32)), jnp.ones((2, 2, 32)),
             jnp.ones((2, 2, 32)), jnp.ones((2, 8, 4))),
            {"bigdl_ssm_decode"}),
        "int8_gemm": (
            lambda x, w, xs, ws: pallas_quantized_matmul(
                x, w, xs, ws, interpret=True),
            (jnp.zeros((128, 128), jnp.int8), jnp.zeros((128, 128), jnp.int8),
             jnp.ones((128, 1)), jnp.ones((128,))),
            {"bigdl_int8_gemm"}),
        "flash_fwd": (flash, _attention_args(), {"bigdl_flash_fwd"}),
        "flash_grad": (grad_of(flash), _attention_args(),
                       {"bigdl_flash_fwd", "bigdl_flash_bwd"}),
        "blockwise_fwd": (blockwise, _attention_args(),
                          {"bigdl_flash_blockwise_fwd"}),
        "blockwise_grad": (grad_of(blockwise), _attention_args(),
                           {"bigdl_flash_blockwise_fwd",
                            "bigdl_flash_blockwise_dq",
                            "bigdl_flash_blockwise_dkv"}),
    }


@pytest.mark.parametrize("kernel", [
    "ragged_decode", "ssm_decode", "int8_gemm", "flash_fwd", "flash_grad",
    "blockwise_fwd", "blockwise_grad"])
def test_every_pallas_call_has_its_name(kernel):
    fn, args, names = _kernel_cases()[kernel]
    found = _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)
    assert found and set(found) == names


def test_no_pallas_call_site_is_left_unnamed():
    """The walk above meets nine sites (the eighth: the expert layer's
    ``bigdl_moe_gmm``; the ninth: the state-space decode step's
    ``bigdl_ssm_decode``); a tenth added to ``kernels/`` without a
    ``name=`` shows here."""
    import ast

    import bigdl_tpu.kernels as kernels

    sites = []
    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(kernels.__file__), "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) \
                    and getattr(node.func, "attr", "") == "pallas_call":
                named = [k.value.value for k in node.keywords
                         if k.arg == "name"]
                assert named and named[0].startswith("bigdl_"), \
                    f"{path}:{node.lineno}: pallas_call without name="
                sites.append(named[0])
    assert len(sites) == len(set(sites)) == 9


# -------------------------------------------------- the train window

@pytest.fixture(scope="module")
def windowed_run():
    """Three ``optimize()`` calls of one K=2 ``Optimizer``, the first
    two with the tracer on, the last with it off: the spans, and what
    the gap histogram observed in each call."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import max_iteration

    RandomGenerator.set_seed(7)
    rows = np.random.RandomState(0).randint(0, 50, (32, 9)).astype(np.int32)
    ds = DataSet.array([Sample(r[:-1], r[1:]) for r in rows]) \
        .transform(SampleToMiniBatch(4))
    opt = Optimizer(_model().training(), ds,
                    nn.SequenceCrossEntropyCriterion(), batch_size=4)
    opt.set_optim_method(optim.Adam(learning_rate=1e-3))
    opt.set_steps_per_sync(2)
    gap = telemetry.registry().get("train/optimizer/window_gap_ms")
    counts = [gap.count()]
    telemetry.tracer().clear()
    telemetry.enable()
    try:
        for end in (6, 10, 14):      # 3 windows, then 2, then 2 untraced
            if end == 14:
                telemetry.disable()
            opt.set_end_when(max_iteration(end))
            opt.optimize()
            counts.append(gap.count())
    finally:
        telemetry.disable()
    spans = telemetry.tracer().spans()
    telemetry.tracer().clear()
    return spans, counts, gap.samples()[-4:]


def test_window_dispatch_and_replay_are_live_spans(windowed_run):
    spans, _, _ = windowed_run
    by = lambda name: [s for s in spans if s.name == name]
    dispatch, replay = by("optimizer/window/dispatch"), \
        by("optimizer/window/replay")
    compute = by("optimizer/compute")
    assert len(dispatch) == len(replay) == len(compute) == 5
    assert {s.tid for s in dispatch + replay} == {threading.get_ident()}
    for d, c, r in zip(dispatch, compute, replay):
        # the launch opens the compute phase; the replay follows it
        assert c.ts - 1e-3 <= d.ts and d.ts + d.dur <= c.ts + c.dur + 1e-3
        assert r.ts >= d.ts + d.dur
        assert d.args["steps"] == r.args["steps"] == 2


def test_the_gap_histogram_skips_each_calls_first_window(windowed_run):
    """... and is always on: the third call ran with the tracer off
    (the benchmark's train driver never switches it on)."""
    _, counts, last = windowed_run
    assert [b - a for a, b in zip(counts, counts[1:])] == [2, 1, 1]
    assert all(0.0 < ms < 60e3 for ms in last)
    gap = telemetry.registry().get("train/optimizer/window_gap_ms")
    assert gap.kind == "histogram" and telemetry.NAME_RE.match(gap.name)


@pytest.mark.parametrize("config, share", [
    (None, 0.0),                    # the CPU default: every layer declines
    ("flash", 1.0),                 # flash on: every layer takes the kernel
])
def test_a_traced_window_observes_the_attention_share(config, share):
    """``train/optimizer/attn_in_kernel_share``: when a dispatch traces
    its window program, the flash dispatches taken over taken +
    declined in that trace, always on; a window that traces nothing
    observes nothing. The benchmark's ``program_histogram`` reader
    reads it as ``train_attn_in_kernel_share.train``."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import kernels, optim
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import max_iteration

    RandomGenerator.set_seed(9)
    rows = np.random.RandomState(1).randint(0, 50, (32, 9)).astype(np.int32)
    ds = DataSet.array([Sample(r[:-1], r[1:]) for r in rows]) \
        .transform(SampleToMiniBatch(4))
    opt = Optimizer(_model().training(), ds,
                    nn.SequenceCrossEntropyCriterion(), batch_size=4)
    opt.set_optim_method(optim.Adam(learning_rate=1e-3))
    opt.set_steps_per_sync(2)
    hist = telemetry.registry().get("train/optimizer/attn_in_kernel_share")
    assert hist.kind == "histogram" and telemetry.NAME_RE.match(hist.name)
    before = hist.count()
    policy = kernels.KernelConfig(flash_attention=True, interpret=True) \
        if config == "flash" else kernels.config._default()
    assert policy.flash_attention is (config == "flash")
    with kernels.use(policy):
        opt.set_end_when(max_iteration(6))      # three windows, one trace
        opt.optimize()
    assert hist.count() == before + 1
    assert hist.samples()[-1] == share



# ------------------------------------- the benchmark's reader of phases

_OF = ["serving/idle", "serving/housekeep", "serving/admit",
       "serving/prepare", "serving/decode", "serving/sample"]
_PHASES = {"serving/idle": [0.5], "serving/housekeep": [0.01] * 10,
           "serving/admit": [0.05] * 4, "serving/prepare": [0.02] * 10,
           "serving/decode": [0.2] * 10, "serving/sample": [0.03] * 10,
           "serving/decode/dispatch": [0.01] * 10,
           "serving/prefill/device_wait": [0.03] * 4}   # whole: 3.3 s


@pytest.mark.parametrize("spans,plus,minus,want", [
    (_PHASES, ["serving/prepare", "serving/decode/dispatch"], (),
     100 * 0.3 / 3.3),
    (_PHASES, ["serving/admit"], ["serving/prefill/device_wait"],
     100 * (0.2 - 0.12) / 3.3),
    # the parent commit: no serving/prepare span, so no launch share
    ({k: v for k, v in _PHASES.items() if k != "serving/prepare"},
     ["serving/prepare", "serving/decode/dispatch"], (), None),
    # nothing of the whole on record
    ({"serving/decode/dispatch": [0.01]}, ["serving/decode/dispatch"],
     (), None)], ids=["plus", "minus", "no-plus", "no-whole"])
def test_span_balance_pct_reads_a_phase_of_the_tiling(spans, plus, minus,
                                                      want):
    import types

    from benchmarks.readers import span_balance_pct

    ctx = types.SimpleNamespace(spans=spans)
    got = span_balance_pct.read(ctx, plus, _OF, minus)
    assert got == (None if want is None else pytest.approx(want))
