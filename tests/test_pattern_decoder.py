"""``PatternDecoderLM`` (window and global attention layers in one
cache, grouped K/V heads, a routed expert layer that holds a share of
the router's experts) against the plain reference
``benchmarks/models/afmoe.py``, at a small size on the CPU: hidden 64,
4 query heads over 2 K/V heads of 16, window 8, 8 experts top-2, one
dense layer then a period of 4 (window, window, window, global).

Tolerances. Program and reference both compute in float32 here, from
the same weights, in different orders (the program's grouped products
and cached attention sum in other orders than the reference's plain
forms): logits of size ~5 agree to a few 1e-6; every comparison below
allows 1e-4, forty times that and a hundredth of the smallest step
between two candidates' logits that these seeds show.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.models import afmoe                        # noqa: E402
from bigdl_tpu import kernels, telemetry                   # noqa: E402
from bigdl_tpu.generation import (GenerationConfig,        # noqa: E402
                                  GenerationService)
from bigdl_tpu.generation.engine import DecodeEngine       # noqa: E402
from bigdl_tpu.generation.kv_cache import KVCache          # noqa: E402
from bigdl_tpu.kernels import KernelConfig                 # noqa: E402
from bigdl_tpu.serving.compile_cache import (BucketLadder,  # noqa: E402
                                             CompileCache)
from bigdl_tpu.serving.registry import ModelRegistry       # noqa: E402

ATOL = 1e-4
SEED = 5
WINDOW = 8


def tiny(held=8, offset=0, router=8):
    return {
        "family": "afmoe", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_shared_experts": 1, "num_experts": held,
        "num_experts_per_tok": 2, "num_hidden_layers": 5,
        "num_dense_layers": 1,
        "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
        "vocab_size": 256, "sliding_window": WINDOW,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "route_scale": 2.448, "route_norm": True, "mup_enabled": True,
        "max_position_embeddings": 64, "initializer_range": 0.2,
        "deployment": {"router_experts": router,
                       "expert_offset": offset}}


def build(cfg):
    model = afmoe.build_program_model(cfg).evaluate()
    model.set_parameters(afmoe.make_program_params(cfg, SEED, "float32"))
    return model


@pytest.fixture(scope="module")
def cut():
    """The chip's share: experts 2..5 of the router's 8."""
    cfg = tiny(held=4, offset=2)
    return cfg, build(cfg)


# ---------------------------------------------------- the full forward

@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2), (2, 6)])
def test_full_forward_logits_match_the_reference(held, offset):
    """Sequences four times the window: rotary on window layers only,
    the window mask, grouped heads, the gate, the sandwich norms, the
    router's bias and the share of experts held."""
    cfg = tiny(held, offset)
    model = build(cfg)
    toks = np.random.RandomState(0).randint(0, 256, (2, 32))
    got, state = jax.jit(lambda p, t: model.apply(
        p, model.initial_state(), t))(model.get_parameters(),
                                      toks.astype(np.int32))
    ref = afmoe.ref_forward(cfg, SEED, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=ATOL, rtol=0)
    # the dense layer reports no expert statistics, the others do
    assert "moe_stats" not in state["block_0"]["mlp"]
    stats = np.asarray(state["block_1"]["mlp"]["moe_stats"])
    assert 1 <= stats[0] <= held and stats[2] <= stats[1] <= 64 * 2


# ------------------------------------- prefill, then decode through it

def _engine(rungs, slots=4, rows=2, chunk=None):
    return DecodeEngine(CompileCache(), BucketLadder(rungs[-1], rungs),
                        slots=slots, prefill_rows=rows, prefill_chunk=chunk)


def _serve_logits(model, eng, prompts, steps, feed):
    """Prefill ``prompts`` into slots 0.., then ``steps`` decode steps
    feeding ``feed[i][j]`` (not the argmax: every row then follows a
    fixed token string the reference can re-forward). Returns, per row,
    the logits at the last prompt position and at every fed token."""
    sv = ModelRegistry().load("m", model)
    kv = KVCache.for_model(model, eng.slots, eng.ladder.max_batch_size)
    out = [[] for _ in prompts]
    for i, p in enumerate(prompts):      # one batch a prompt: own rung
        logits, _ = eng.prefill(sv, kv, [p], [i])
        out[i].append(logits[0])
    rungs = set()
    for j in range(steps):
        tokens = np.zeros(eng.slots, np.int32)
        positions = np.zeros(eng.slots, np.int32)
        active = np.zeros(eng.slots, bool)
        for i in range(len(prompts)):
            tokens[i], positions[i], active[i] = (feed[i][j],
                                                  kv.lengths[i], True)
        logits, rung = eng.decode(sv, kv, tokens, positions, active)
        rungs.add(rung)
        for i in range(len(prompts)):
            kv.lengths[i] += 1
            out[i].append(logits[i])
    return [np.stack(o) for o in out], rungs, kv


def _reference_rows(cfg, prompts, feed, steps):
    got = []
    for p, f in zip(prompts, feed):
        full = np.concatenate([p, f[:steps]])[None]
        pad = (-full.shape[1]) % 8
        ref = np.asarray(afmoe.ref_forward(
            cfg, SEED, np.pad(full, ((0, 0), (0, pad)))))[0]
        got.append(ref[len(p) - 1:len(p) + steps])
    return got


@pytest.mark.parametrize("policy", ["reference", "pallas"])
def test_prefill_then_decode_through_both_kinds_of_cache(cut, policy):
    """Prompts shorter (5) and longer (12, 14) than the window of 8,
    decoded 13 steps past it: the rings wrap (positions up to 26 in 8
    columns), the global layer's rung changes 16 -> 32 under way, and a
    prompt longer than the window is prefilled in one shot (only its
    last 8 positions stay in the ring). Logits, not tokens, at every
    position served. ``pallas``: the decode kernel with grouped heads
    and the grouped-product kernel, in the interpreter."""
    cfg, model = cut
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 256, n).astype(np.int32)
               for n in (5, 12, 14)]
    feed = [rng.randint(0, 256, 13).astype(np.int32) for _ in prompts]
    config = KernelConfig.off() if policy == "reference" else \
        KernelConfig(decode_attention=True, grouped_matmul=True)
    with kernels.use(config):
        before = kernels.dispatch.taken_in_thread()
        got, rungs, kv = _serve_logits(model, _engine((8, 16, 32)),
                                       prompts, 13, feed)
        taken = kernels.dispatch.taken_in_thread() - before
    assert (taken > 0) == (policy == "pallas")
    assert rungs == {16, 32}
    # four rings of 8 columns, one global entry of 32
    assert [a.shape for a in kv.k] == [(4, 2, 16, 8)] * 4 \
        + [(4, 2, 16, 32)]
    assert kv.kind_bytes() == {"window": 4 * 2 * 4 * 2 * 16 * 8 * 4,
                               "global": 2 * 4 * 2 * 16 * 32 * 4,
                               "state": 0}
    for g, r in zip(got, _reference_rows(cfg, prompts, feed, 13)):
        np.testing.assert_allclose(g, r, atol=ATOL, rtol=0)


def test_a_rung_too_wide_for_one_shot_is_chunked_by_the_engine(
        cut, monkeypatch):
    """With the score budget cut to 4 heads x 4 tokens x the rung, the
    engine fills rung 32 in ``[1, 4]`` pieces by itself: chunks that
    start inside, at and past the window's edge read the ring as the
    chunk before left it, and the served logits stay the reference's.
    A rung that fits keeps its one-shot shape."""
    from bigdl_tpu.generation import engine as engine_mod

    cfg, model = cut
    eng = _engine((8, 32), rows=2)
    assert eng.prefill_shape(model, 32) == (2, 32)
    monkeypatch.setattr(engine_mod, "_PREFILL_SCORE_BYTES",
                        4 * 4 * 32 * 4)
    assert eng.prefill_shape(model, 32) == (1, 4)
    assert eng.prefill_shape(model, 8) == (2, 8)
    assert eng.prefill_dispatches(model, 32, [21, 9], [0, 0]) == 6 + 3
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 256, n).astype(np.int32) for n in (21, 9)]
    feed = [rng.randint(0, 256, 6).astype(np.int32) for _ in prompts]
    got, _, _ = _serve_logits(model, eng, prompts, 6, feed)
    for g, r in zip(got, _reference_rows(cfg, prompts, feed, 6)):
        np.testing.assert_allclose(g, r, atol=ATOL, rtol=0)


def test_generation_service_serves_it_and_records_the_expert_counts(cut):
    """``load`` / ``generate`` with nothing but slots, ``max_len`` and
    the ladder: the cache's kinds and dtype come from the model, the
    served tokens are the reference's best (``ref_token_gaps`` reads 0),
    every decode step leaves the expert counts in a ``serving/moe/step``
    ring record while the tracer is on (no always-on instrument holds
    them), and the cache's bytes by kind are in the gauges."""
    cfg, model = cut
    reg = telemetry.registry()
    telemetry.tracer().clear()
    telemetry.enable()
    svc = GenerationService(config=GenerationConfig(
        slots=4, max_len=32, length_buckets=[8, 16, 32],
        max_new_tokens=16))
    try:
        svc.load("lm", model)
        rng = np.random.RandomState(3)
        rows = []
        for plen, new in [(5, 10), (12, 14), (20, 10)]:
            p = rng.randint(0, 256, plen).astype(np.int32)
            rows.append((p, np.asarray(svc.generate(
                "lm", p, max_new_tokens=new).result(120))))
    finally:
        svc.shutdown()
        telemetry.disable()
    steps = [s.args for s in telemetry.tracer().spans()
             if s.name == "serving/moe/step" and s.args["kind"] == "decode"]
    telemetry.tracer().clear()
    served, _ = afmoe.ref_token_gaps(
        dict(cfg, reference={"route_tie_margin": 1e-5}), SEED, rows)
    assert sum(len(g) for g in served) >= 30
    assert max(float(g.max()) for g in served) <= ATOL
    assert len(steps) == 9 + 13 + 9        # one a decode step
    assert all(0 < a["experts_touched"] / a["layers"] <= 4 for a in steps)
    # one request decodes at a time here: 2 pairs a step, on 2 experts
    assert max(a["pairs_per_expert_max"] for a in steps) <= 1
    assert not [n for n in reg.names() if n.startswith("serving/moe/")]
    assert reg.get("serving/cache/window_bytes").value(model="lm") \
        == 4 * 2 * 4 * 2 * 16 * 8 * 4
    assert reg.get("serving/cache/global_bytes").value(model="lm") \
        == 2 * 4 * 2 * 16 * 32 * 4


def test_expert_counts_stay_on_the_device_while_the_tracer_is_off():
    from bigdl_tpu.generation.engine import _record_moe

    class OnTheDevice:
        def __array__(self, *args, **kwargs):
            raise AssertionError("the expert counts were copied")

    assert not telemetry.enabled()
    _record_moe(OnTheDevice(), "decode")


def test_the_prefix_cache_refuses_entries_of_several_kinds(cut):
    _, model = cut
    svc = GenerationService(config=GenerationConfig(
        slots=2, max_len=16, length_buckets=[16],
        prefix_cache_bytes=1 << 20))
    try:
        with pytest.raises(ValueError, match="several kinds"):
            svc.load("lm", model)
    finally:
        svc.shutdown()


# ------------------------------- the engine's contract, and no more

#: what ``generation/engine.py``'s docstring says a served decoder
#: provides, and what ``ModelRegistry.load`` asks of any module
_CONTRACT = ("apply", "cache_layout", "cache_dtype", "scoreless_prefill",
             "num_layers", "num_heads", "max_len", "vocab_size")
_REGISTRY = ("ensure_initialized", "get_parameters", "get_state")


class _ContractOnly:
    """A decoder that shows exactly the written contract of ``model``:
    any other attribute the engine or the cache might probe for
    (``hidden_size``, ``head_dim``, ``window``, ...) is not there."""

    def __init__(self, model, without=()):
        self._model = model
        self._names = set(_CONTRACT + _REGISTRY) - set(without)

    def __getattr__(self, name):
        if name.startswith("_") or name not in self._names:
            raise AttributeError(
                f"{name!r} is not in the served-decoder contract")
        return getattr(self._model, name)


def _tiny_gpt():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(SEED)
    model = TransformerLM(vocab_size=64, hidden_size=32, num_layers=2,
                          num_heads=4, max_len=64).evaluate()
    model.ensure_initialized()
    return model


def _tiny_hybrid():
    """State-space layers, latent experts and an attention layer, one
    mixer or one FFN a layer (``tests/test_hybrid_decoder.py``)."""
    import json

    from benchmarks.models import nemotron_h

    with open(os.path.join(ROOT, "benchmarks", "tests", "tiny", "configs",
                           "tiny-nemotron-h.json")) as f:
        cfg = json.load(f)
    model = nemotron_h.build_program_model(cfg).evaluate()
    model.set_parameters(nemotron_h.make_program_params(cfg, SEED))
    return model


_FAMILIES = {"gpt2": _tiny_gpt, "hybrid": _tiny_hybrid,
             "pattern": lambda: build(tiny())}


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_decoder_is_served_on_the_written_contract_alone(family):
    """``KVCache.for_model`` and the engine's three programs ask a
    model for what the contract lists and nothing else: each family,
    shown through :class:`_ContractOnly`, loads and serves the tokens
    it serves unwrapped; without ``cache_layout`` the load fails with
    the plain ``AttributeError`` that names it."""
    model = _FAMILIES[family]()
    prompt = np.random.RandomState(4).randint(0, 64, 11).astype(np.int32)

    def served(decoder):
        svc = GenerationService(config=GenerationConfig(
            slots=2, max_len=32, length_buckets=[16, 32],
            max_new_tokens=8))
        try:
            svc.load("lm", decoder)
            return list(svc.generate("lm", prompt).result(120))
        finally:
            svc.shutdown()

    tokens = served(_ContractOnly(model))
    assert len(tokens) == 8 and tokens == served(model)
    with pytest.raises(AttributeError, match="cache_layout"):
        served(_ContractOnly(model, without=("cache_layout",)))


# ------------------------- a one-shot prefill reads nothing of the cache

def _leftovers(kv, seed):
    """Every array of the cache filled with another request's values."""
    rng = np.random.RandomState(seed)
    kv.entries = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        kv.entries)
    return jax.tree.map(np.asarray, kv.entries)


def _below_length(entries, slot, length):
    """What a later step may attend of ``slot``: a K/V entry's columns
    below the row's length (all of a ring the row has filled), a state
    whole."""
    return [np.asarray(a[slot, :, :, :min(length, a.shape[3])]
                       if n in ("k", "v") else a[slot])
            for e in entries for n, a in sorted(e.items())]


def _prefill_then_decode(model, eng, kv, prompt, slot, feed):
    """One prompt into ``slot`` (the batch's other row is padding), then
    a decode step a fed token with that slot alone live. Returns the
    logits of the prefill and of each step, and the cache's host copy
    right after the prefill."""
    sv = ModelRegistry().load("m", model)
    logits, _ = eng.prefill(sv, kv, [prompt], [slot])
    out = [logits[0]]
    after_prefill = jax.tree.map(np.asarray, kv.entries)
    for token in feed:
        tokens = np.zeros(eng.slots, np.int32)
        positions = np.zeros(eng.slots, np.int32)
        active = np.zeros(eng.slots, bool)
        tokens[slot], positions[slot], active[slot] = (
            token, kv.lengths[slot], True)
        logits, _ = eng.decode(sv, kv, tokens, positions, active)
        kv.lengths[slot] += 1
        out.append(logits[slot])
    return np.stack(out), after_prefill


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_fresh_prefill_over_leftovers_equals_one_over_zeros(family):
    """A slot that holds another request's leftovers (here: every array
    of the cache random) is prefilled in one shot (rung 16, a prompt of
    11; the second row of the batch is padding, ``slot_ids == slots``)
    and decoded 8 steps. The prefill's logits, every step's, and the
    cache below the row's length are BITWISE those of the same run on a
    cache of zeros: the ``fresh`` program reads nothing of the slot.
    Right after the prefill the three neighbours - the last among them
    the slot a padding row's id clamps to - hold their leftovers to the
    byte."""
    model = _FAMILIES[family]()
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, 64, 11).astype(np.int32)
    feed = rng.randint(0, 64, 8).astype(np.int32)
    runs = {}
    for name in ("zeros", "leftovers"):
        eng = _engine((16, 32))
        assert eng.prefill_shape(model, 16) == (2, 16)      # one shot
        kv = KVCache.for_model(model, eng.slots, 32)
        before = _leftovers(kv, 7) if name == "leftovers" else None
        logits, after = _prefill_then_decode(model, eng, kv, prompt, 1,
                                             feed)
        runs[name] = (logits, _below_length(kv.entries, 1, 11 + 8))
        if before is not None:
            for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
                np.testing.assert_array_equal(a[[0, 2, 3]], b[[0, 2, 3]])
                assert not np.array_equal(a[1], b[1])
    np.testing.assert_array_equal(runs["leftovers"][0], runs["zeros"][0])
    assert len(runs["zeros"][1]) > 0
    for got, want in zip(runs["leftovers"][1], runs["zeros"][1]):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_chunked_prefill_still_gathers_and_a_fresh_one_does_not(family):
    """``prefill_chunk`` 8 at rung 16: the second piece attends what the
    first wrote, so the program gathers its rows from the slots and the
    logits are the one-shot program's (over leftovers too: a piece at
    offset 0 masks what the slot held). Program by program, from the
    traced equations: the chunked one reads every cache array through a
    ``gather``; in the ``fresh`` one a cache array is the operand of its
    ``scatter`` and of nothing else."""
    model = _FAMILIES[family]()
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, 64, 11).astype(np.int32)
    feed = rng.randint(0, 64, 3).astype(np.int32)
    logits = {}
    for name, chunk in (("one_shot", None), ("chunked", 8)):
        eng = _engine((16, 32), chunk=chunk)
        assert eng.prefill_shape(model, 16) == (2, chunk or 16)
        kv = KVCache.for_model(model, eng.slots, 32)
        _leftovers(kv, 9)
        logits[name], _ = _prefill_then_decode(model, eng, kv, prompt, 2,
                                               feed)
    np.testing.assert_allclose(logits["chunked"], logits["one_shot"],
                               atol=ATOL, rtol=0)

    spec = KVCache.spec_for_model(model, 4, 32)
    leaves = len(jax.tree.leaves(spec))
    rows = jax.ShapeDtypeStruct((2,), np.int32)
    for fresh, width in ((True, 16), (False, 8)):
        jaxpr = jax.make_jaxpr(
            DecodeEngine._prefill_jit(model, 16, lambda: None, fresh))(
                model.get_parameters(), model.get_state(), spec,
                jax.ShapeDtypeStruct((2, width), np.int32), rows, rows,
                rows).jaxpr
        (call,) = jaxpr.eqns                          # the jit itself
        body = call.params["jaxpr"].jaxpr
        first = len(jax.tree.leaves((model.get_parameters(),
                                     model.get_state())))
        cache_vars = set(body.invars[first:first + leaves])
        readers = [e.primitive.name for e in body.eqns
                   if cache_vars & {v for v in e.invars
                                    if not hasattr(v, "val")}]
        if fresh:
            assert readers == ["scatter"] * leaves, readers
        else:
            assert sorted(set(readers)) == ["gather", "scatter"], readers
            assert readers.count("gather") == leaves


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_prefill_call_records_what_it_did_not_read(family):
    """``serving/prefill/kv``, one ring record a prefill call while the
    tracer is on: ``rows_bytes``, the program's rows over every entry at
    the rung's width (a ring at its own), and ``unread_bytes``, all of
    them for a one-shot call and none for a chunk; nothing with the
    tracer off."""
    model = _FAMILIES[family]()
    prompt = np.arange(11, dtype=np.int32)
    sv = ModelRegistry().load("m", model)

    def records(chunk):
        eng = _engine((16, 32), chunk=chunk)
        kv = KVCache.for_model(model, eng.slots, 32)
        eng.prefill(sv, kv, [prompt], [0])
        want = 2 * sum(
            a.dtype.itemsize * int(np.prod(
                a.shape[1:3] + (min(16, a.shape[3]),) if n in ("k", "v")
                else a.shape[1:]))
            for e in kv.entries for n, a in e.items())
        return want, [r.args for r in telemetry.tracer().spans()
                      if r.name == "serving/prefill/kv"]

    telemetry.tracer().clear()
    assert records(None)[1] == []
    telemetry.enable()
    try:
        want, recs = records(None)
        assert want > 0
        assert recs == [{"rows_bytes": want, "unread_bytes": want}]
        telemetry.tracer().clear()
        want, recs = records(8)
        assert recs == [{"rows_bytes": want, "unread_bytes": 0}] * 2
    finally:
        telemetry.disable()
        telemetry.tracer().clear()


# ------------------------------------------------------ the expert layer

def _dense_form(params, x, idx, w, offset, gated, act):
    """The all-experts form the sorted dispatch replaced: every held
    expert over every token, the combine weights zeroing the rest."""
    held = params["w_up"].shape[0]
    comb = jnp.sum(jnp.where((idx - offset)[..., None] == jnp.arange(held),
                             w[..., None], 0.0), axis=1)        # [T, E]
    hid = jnp.einsum("th,ehf->etf", x, params["w_up"])
    if gated:
        hid = act(jnp.einsum("th,ehf->etf", x, params["w_gate"])) * hid
    else:
        hid = act(hid)
    return jnp.einsum("etf,efh,te->th", hid, params["w_down"], comb)


@pytest.mark.parametrize("policy", ["reference", "pallas"])
@pytest.mark.parametrize("gated", [False, True])
def test_sorted_dispatch_equals_the_all_experts_form_under_imbalance(
        gated, policy):
    """A planted imbalance: expert 3 takes every token's first choice,
    expert 0 takes none, the second choices spread over 1, 2 and two
    experts held elsewhere (5, 6). Runs cross tile boundaries (40 pairs
    on one expert, tiles of 8) and an expert with no pair owns no
    tile."""
    from bigdl_tpu.nn.moe import dispatch_plan, routed_experts

    rng = np.random.RandomState(4)
    t, h, f, held = 40, 16, 24, 4
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    params = {"w_up": jnp.asarray(rng.randn(held, h, f) * 0.3,
                                  jnp.float32),
              "w_down": jnp.asarray(rng.randn(held, f, h) * 0.3,
                                    jnp.float32)}
    if gated:
        params["w_gate"] = jnp.asarray(rng.randn(held, h, f) * 0.3,
                                       jnp.float32)
    idx = jnp.stack([jnp.full((t,), 3),
                     jnp.asarray(rng.choice([1, 2, 5, 6], t))], axis=1)
    w = jnp.asarray(rng.rand(t, 2), jnp.float32)
    config = KernelConfig.off() if policy == "reference" else \
        KernelConfig(grouped_matmul=True)
    with kernels.use(config):
        got, stats = jax.jit(lambda p, x: routed_experts(
            p, x, idx, w, offset=0, router_experts=8,
            activation="silu"))(params, x)
    want = _dense_form(params, x, idx, w, 0, gated, jax.nn.silu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=8)[:held]
    assert counts[0] == 0 and counts[3] == t
    assert list(np.asarray(stats)) == [float((counts > 0).sum()),
                                       float(counts.sum()), float(t)]
    # the plan: every pair on a held expert has a row of its own inside
    # that expert's tiles; a pair held elsewhere has none
    rows, pair_row, tile_expert, n_tiles, c = dispatch_plan(idx, 0, held,
                                                            8)
    pair_row, tile_expert = np.asarray(pair_row), np.asarray(tile_expert)
    assert list(np.asarray(c)) == list(counts)
    local = np.asarray(idx) < held
    assert len(set(pair_row[local])) == local.sum()
    assert (pair_row[~local] == len(np.asarray(rows))).all()
    assert (tile_expert[pair_row[local] // 8]
            == np.asarray(idx)[local]).all()
    assert int(n_tiles[0]) == sum(-(-n // 8) for n in counts)


def test_moe_trains_through_the_sorted_dispatch():
    """``nn.MoE`` as ``TransformerLM(moe_experts=)`` builds it (soft-max
    scoring, ``gelu``, every expert held): the gradient through the
    grouped-product kernel equals the gradient of the plain form."""
    import bigdl_tpu.nn as nn

    m = nn.MoE(16, 32, num_experts=4, top_k=2)
    p = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    loss = lambda pp: jnp.sum(m.apply(pp, m.initial_state(), x)[0] ** 2)
    want = jax.grad(loss)(p)
    with kernels.use(KernelConfig(grouped_matmul=True)):
        got = jax.grad(loss)(p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=0)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(want))


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test: four chips hold 2 of the router's 8 experts
    each. The routed parts the four program layers give, plus the
    shared expert counted ONCE, add up to the uncut reference layer;
    and each program share equals the reference given the same share."""
    from bigdl_tpu.nn.moe import MoE

    full = tiny(held=8, offset=0)
    z = afmoe.dims(full)
    lp = afmoe.make_layer(full, SEED, 2)            # an expert layer
    u = jnp.asarray(np.random.RandomState(6).randn(24, z["h"]),
                    jnp.float32)
    shared, routed, _ = afmoe.moe_parts(full, "f32", lp, u)
    whole = shared + routed
    total = jnp.zeros_like(whole)
    for chip in range(4):
        sl = slice(2 * chip, 2 * chip + 2)
        layer = MoE(z["h"], z["Fe"], 2, z["k"], "silu", gated=True,
                    scoring="sigmoid", router_experts=8,
                    expert_offset=2 * chip, router_bias=True,
                    route_scale=z["scale"], shared_size=0)
        params = {"router": lp["router"],
                  "router_bias": lp["router_bias"],
                  "w_gate": lp["e_gate"][sl], "w_up": lp["e_up"][sl],
                  "w_down": lp["e_down"][sl]}
        part, _ = layer.apply(params, {}, u[None])
        cut = dict(lp, e_gate=lp["e_gate"][sl], e_up=lp["e_up"][sl],
                   e_down=lp["e_down"][sl])
        _, ref_part, _ = afmoe.moe_parts(full, "f32", cut, u,
                                         offset=2 * chip)
        np.testing.assert_allclose(np.asarray(part[0]),
                                   np.asarray(ref_part), atol=1e-5,
                                   rtol=0)
        total = total + part[0]
    assert float(jnp.abs(routed).max()) > 0.1
    np.testing.assert_allclose(np.asarray(shared + total),
                               np.asarray(whole), atol=1e-5, rtol=0)


def test_the_reference_sets_near_ties_aside_by_its_own_margin():
    """A margin wide enough to catch positions sets them aside from
    both lists, and a share past its limit returns NaN gaps."""
    cfg = tiny(held=4, offset=2)
    rng = np.random.RandomState(7)
    rows = [(rng.randint(0, 256, 6).astype(np.int32),
             rng.randint(0, 256, 20).astype(np.int32))]
    all_, _ = afmoe.ref_token_gaps(cfg, SEED, rows)
    some, _ = afmoe.ref_token_gaps(
        dict(cfg, reference={"route_tie_margin": 0.02}), SEED, rows)
    assert len(all_[0]) == 20 and 0 < len(some[0]) < 20
    none, _ = afmoe.ref_token_gaps(
        dict(cfg, reference={"route_tie_margin": 0.02,
                             "set_aside_share_limit": 0.01}), SEED, rows)
    assert np.isnan(none[0]).all()


@pytest.mark.parametrize("offset", [0, 4, 12])
def test_the_routing_margin_is_what_decides_the_held_part(offset):
    """``route``'s margin against its meaning: moving any ONE held
    expert's ``s + b`` by less than the margin leaves the set of held
    experts chosen as it is, and moving the nearest one by a little
    more changes it - also where the last chosen and the first left
    out are both held elsewhere and the third in line is held here
    (16 experts, 4 held, top-2: most positions)."""
    cfg = tiny(held=4, offset=offset, router=16)
    z = afmoe.dims(cfg)
    rng = np.random.RandomState(8)
    lp = {"router": jnp.asarray(0.3 * rng.randn(z["h"], 16), jnp.float32),
          "router_bias": jnp.asarray(0.02 * rng.randn(16), jnp.float32)}
    u = jnp.asarray(rng.randn(200, z["h"]), jnp.float32)
    idx, _, margin = jax.device_get(afmoe.route(cfg, lp, u))
    margin = np.asarray(margin, np.float64)
    sb = np.asarray(jax.nn.sigmoid(u @ lp["router"]) + lp["router_bias"],
                    np.float64)
    here = np.arange(offset, offset + 4)

    def part(scores):                   # the held experts among the top 2
        top = np.argsort(-scores)[:z["k"]]
        return frozenset(int(e) for e in top if e in here)

    edge_elsewhere = 0
    for t in range(len(u)):
        assert part(sb[t]) == frozenset(
            int(e) for e in idx[t, :z["k"]] if e in here)
        moved = []
        for e in here:
            for sign in (-1.0, 1.0):
                for share, out in ((0.9, None), (1.1, moved)):
                    s = sb[t].copy()
                    s[e] += sign * share * margin[t]
                    if out is None:
                        assert part(s) == part(sb[t]), (t, e, sign)
                    else:
                        out.append(part(s) != part(sb[t]))
        assert any(moved), t
        edge_elsewhere += not any(e in here for e in idx[t, 1:3])
    assert edge_elsewhere > 50          # the case the edge alone misses


def test_required_operations_and_bytes_of_the_cell():
    """Hand-worked values for ``trinity-large-ep8`` (ISSUE 28's table)."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity-large-ep8.json")) as f:
        cfg = json.load(f)
    assert afmoe.attn_params(cfg) == 3 * 18874368 + 2 * 3145728
    assert afmoe.expert_bytes(cfg, 2) == 3 * 3072 * 3072 * 2
    assert afmoe.param_count(cfg) == pytest.approx(4.32e9, rel=2e-3)
    # one decoded token at context 5000: 2 x (5 x 62.9M attention +
    # 113.2M dense FFN + 4 x (28.3M shared + 0.79M router + 0.5 x 28.3M
    # routed) + 76.9M head) + 4 x 6144 x (4 x 4096 + 5000)
    matmul = (5 * 62914560 + 113246208
              + 4 * (28311552 + 786432 + 0.5 * 28311552))
    assert afmoe.serve_flops_per_token(cfg, 5000) == pytest.approx(
        2 * (matmul + 25024 * 3072) + 4 * 6144 * (4 * 4096 + 5000))
    span = sum(afmoe.serve_flops_per_token(cfg, p + 1) - 2 * 25024 * 3072
               for p in range(4090, 4100)) + 2 * 25024 * 3072
    assert afmoe.serve_flops_span(cfg, 4090, 4100) == pytest.approx(span)
    # 2 x 8 x 128 x 2 B a column; 4 rings of 4096, one whole context
    assert afmoe.kv_read_bytes(cfg, 5000, 2) == 4096 * (4 * 4096 + 5000)
    assert afmoe.kv_read_bytes(cfg, 100, 2) == 4096 * 5 * 100
