"""A decoder whose layers are ONE mixer or ONE feed-forward part each
(``PatternDecoderLM`` with state-space layers, latent ungated relu^2
experts and a position-free attention layer) against the plain
reference ``benchmarks/models/nemotron_h.py``, at a small size on the
CPU: hidden 64, the pattern ``MEM*EME``, 4 Mamba heads of 16 over 2
groups of 16 states (chunk 8), 4 query heads over 2 K/V heads of 16, 16
router experts top-3 of which 4, 8 or all are held, latent 32.

Tolerances. In float32 program and reference compute from the same
weights in different orders (the chunked scan against the token-by-token
recurrence, grouped products against the all-experts form): logits of
size ~7 agree to a few 1e-6, and every float32 comparison allows 1e-4.
Under the cell's policy (bfloat16 weights, activations, K/V and
convolution tail; float32 state) the same logits differ from the
float32 reference by up to ~0.06 over these seeds (8 bits of mantissa
through seven layers); the comparison allows 0.2.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.models import nemotron_h as fam            # noqa: E402
from bigdl_tpu import kernels, telemetry                   # noqa: E402
from bigdl_tpu.generation import (GenerationConfig,        # noqa: E402
                                  GenerationService)
from bigdl_tpu.generation.engine import DecodeEngine       # noqa: E402
from bigdl_tpu.generation.kv_cache import (KVCache,        # noqa: E402
                                           RecurrentStateError)
from bigdl_tpu.kernels import KernelConfig                 # noqa: E402
from bigdl_tpu.nn import ssm                               # noqa: E402
from bigdl_tpu.nn.moe import MoE                           # noqa: E402
from bigdl_tpu.serving.compile_cache import (BucketLadder,  # noqa: E402
                                             CompileCache)
from bigdl_tpu.serving.registry import ModelRegistry       # noqa: E402

ATOL = 1e-4
ATOL_BF16 = 0.2
SEED = 5


def tiny(held=16, offset=0):
    with open(os.path.join(ROOT, "benchmarks", "tests", "tiny", "configs",
                           "tiny-nemotron-h.json")) as f:
        cfg = json.load(f)
    cfg["n_routed_experts"] = cfg["num_experts"] = held
    cfg["deployment"] = {"router_experts": 16, "expert_offset": offset}
    return cfg


def build(cfg, dtype="float32"):
    model = fam.build_program_model(cfg).evaluate()
    model.set_parameters(fam.make_program_params(cfg, SEED, dtype))
    return model


@pytest.fixture(scope="module")
def cut():
    """The chip's share: experts 4..7 of the router's 16."""
    cfg = tiny(held=4, offset=4)
    return cfg, build(cfg)


# ------------------------------------------------ (a) the full forward

@pytest.mark.parametrize("held,offset", [(16, 0), (4, 4), (8, 8)])
def test_full_forward_logits_match_the_reference(held, offset):
    """21 tokens (no multiple of the chunk): the chunked scan against
    the token-by-token recurrence, the convolution, the grouped gated
    norm, attention without positions, the latent experts and the share
    held, under one pre-norm residual a layer."""
    cfg = tiny(held, offset)
    model = build(cfg)
    toks = np.random.RandomState(0).randint(0, 256, (2, 21))
    got, state = jax.jit(lambda p, t: model.apply(
        p, model.initial_state(), t))(model.get_parameters(),
                                      toks.astype(np.int32))
    ref = fam.ref_forward(cfg, SEED, np.pad(toks, ((0, 0), (0, 3))))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref)[:, :21],
                               atol=ATOL, rtol=0)
    # a Mamba layer keeps no state leaf, an expert layer its statistics
    assert state["block_0"] == {}
    stats = np.asarray(state["block_1"]["mlp"]["moe_stats"])
    assert 1 <= stats[0] <= held and stats[2] <= stats[1] <= 42 * 3
    assert fam.param_count(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(
            model.get_parameters()))


# ----------------------------- (b) prefill, then decode through the cache

def _engine(rungs, slots=4, rows=2, chunk=None):
    return DecodeEngine(CompileCache(), BucketLadder(rungs[-1], rungs),
                        slots=slots, prefill_rows=rows, prefill_chunk=chunk)


def _serve_logits(model, eng, prompts, steps, feed, slots=None, kv=None):
    """Prefill ``prompts`` into ``slots`` (default 0..), then ``steps``
    decode steps feeding ``feed[i][j]``. Returns, per row, the logits at
    the last prompt position and at every fed token, and the cache."""
    sv = ModelRegistry().load("m", model)
    if kv is None:
        kv = KVCache.for_model(model, eng.slots, eng.ladder.max_batch_size)
    slots = list(range(len(prompts))) if slots is None else slots
    out = [[] for _ in prompts]
    for i, p in enumerate(prompts):      # one batch a prompt: own rung
        logits, _ = eng.prefill(sv, kv, [p], [slots[i]])
        out[i].append(logits[0])
    for j in range(steps):
        tokens = np.zeros(eng.slots, np.int32)
        positions = np.zeros(eng.slots, np.int32)
        active = np.zeros(eng.slots, bool)
        for i, s in enumerate(slots):
            tokens[s], positions[s], active[s] = (feed[i][j],
                                                  kv.lengths[s], True)
        logits, _ = eng.decode(sv, kv, tokens, positions, active)
        for i, s in enumerate(slots):
            kv.lengths[s] += 1
            out[i].append(logits[s])
    return [np.stack(o) for o in out], kv


def _reference_rows(cfg, prompts, feed, steps):
    got = []
    for p, f in zip(prompts, feed):
        full = np.concatenate([p, f[:steps]])[None]
        pad = (-full.shape[1]) % 8
        ref = np.asarray(fam.ref_forward(
            cfg, SEED, np.pad(full, ((0, 0), (0, pad)))))[0]
        got.append(ref[len(p) - 1:len(p) + steps])
    return got


def _traffic(n=13):
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 256, k).astype(np.int32) for k in (5, 12, 14)]
    return prompts, [rng.randint(0, 256, n).astype(np.int32)
                     for _ in prompts]


@pytest.mark.parametrize("policy", ["reference", "pallas"])
def test_prefill_then_decode_through_the_cache(cut, policy):
    """Prompts of 5, 12 and 14 tokens pad to the rung 16 (none a
    multiple of the chunk); each row's state stops at its last real
    token, and 13 decode steps carry it on (the rung climbs to 32)."""
    cfg, model = cut
    prompts, feed = _traffic()
    config = KernelConfig.off() if policy == "reference" else \
        KernelConfig(decode_attention=True, grouped_matmul=True)
    with kernels.use(config):
        before = kernels.dispatch.taken_in_thread("ssm_decode")
        got, kv = _serve_logits(model, _engine((16, 32)), prompts, 13, feed)
        taken = kernels.dispatch.taken_in_thread("ssm_decode") - before
    assert (taken > 0) == (policy == "pallas")
    kinds = [e[0] for e in kv.layout]
    assert kinds == ["state", "none", "state", "kv", "none", "state",
                     "none"]
    assert kv.entries[0]["ssm"].shape == (4, 2, 16, 32)
    assert kv.entries[0]["ssm"].dtype == jnp.float32
    assert kv.entries[0]["conv"].shape == (4, 3, 4 * 16 + 2 * 2 * 16)
    assert kv.entries[1] == {} and kv.entries[3]["k"].shape == (4, 2, 16, 32)
    assert kv.kind_bytes() == {
        "window": 0, "global": 2 * 4 * 2 * 16 * 32 * 4,
        "state": 3 * 4 * (2 * 16 * 32 + 3 * 128) * 4}
    for g, r in zip(got, _reference_rows(cfg, prompts, feed, 13)):
        np.testing.assert_allclose(g, r, atol=ATOL, rtol=0)


def test_the_cells_bf16_policy_stays_within_its_tolerance():
    """bfloat16 weights, activations, K/V and convolution tail, the
    state float32: logits against the float32 reference."""
    cfg = tiny(held=4, offset=4)
    model = build(cfg, "bfloat16")
    prompts, feed = _traffic()
    got, kv = _serve_logits(model, _engine((16, 32)), prompts, 13, feed)
    assert kv.entries[0]["ssm"].dtype == jnp.float32
    assert kv.entries[0]["conv"].dtype == jnp.bfloat16
    assert kv.entries[3]["k"].dtype == jnp.bfloat16
    worst = 0.0
    for g, r in zip(got, _reference_rows(cfg, prompts, feed, 13)):
        worst = max(worst, float(np.abs(g.astype(np.float32) - r).max()))
    assert 1e-3 < worst < ATOL_BF16, worst


# -------------------------------- (c) a rung prefilled in two chunks

def test_a_rung_prefilled_in_two_chunks_carries_the_state(cut):
    """``prefill_chunk`` 8 at rung 16: the second call starts from the
    entry the first left (offset 8), the convolution's tail with it."""
    cfg, model = cut
    prompts, feed = _traffic(4)
    got, _ = _serve_logits(model, _engine((16, 32), chunk=8), prompts[1:],
                           4, feed[1:])
    for g, r in zip(got, _reference_rows(cfg, prompts[1:], feed[1:], 4)):
        np.testing.assert_allclose(g, r, atol=ATOL, rtol=0)


# ------------------------------------- (d) a slot's state starts from zero

def test_a_reused_slot_starts_from_zero_and_leaves_its_neighbour(cut):
    """Slot 0 serves a long request and is freed; while it stands free,
    slot 1's request decodes on (the free slot computes garbage); then
    a shorter request takes slot 0. Both equal the reference."""
    cfg, model = cut
    rng = np.random.RandomState(2)
    eng = _engine((16, 32))
    long_p, short_p, other_p = (rng.randint(0, 256, n).astype(np.int32)
                                for n in (15, 6, 9))
    feed = [rng.randint(0, 256, 12).astype(np.int32) for _ in range(3)]
    _, kv = _serve_logits(model, eng, [long_p], 12, feed[:1], slots=[0])
    assert float(jnp.abs(kv.entries[0]["ssm"][0]).max()) > 0
    kv.lengths[0] = 0                       # freed; nothing is zeroed
    other, kv = _serve_logits(model, eng, [other_p], 6, feed[2:],
                              slots=[1], kv=kv)
    short, kv = _serve_logits(model, eng, [short_p], 12, feed[1:2],
                              slots=[0], kv=kv)
    np.testing.assert_allclose(
        short[0], _reference_rows(cfg, [short_p], feed[1:2], 12)[0],
        atol=ATOL, rtol=0)
    np.testing.assert_allclose(
        other[0], _reference_rows(cfg, [other_p], feed[2:], 6)[0],
        atol=ATOL, rtol=0)


# ------------------------------------------------ (e) the chunked scan

def _recurrence(x, dt, a, bmat, cmat, s0):
    """Token by token, in float64 numpy."""
    b, s, h, p = x.shape
    g, n = bmat.shape[2:]
    state = np.zeros((b, h, p, n)) if s0 is None else np.array(s0, float)
    y = np.zeros((b, s, h, p))
    for t in range(s):
        bh = np.repeat(bmat[:, t], h // g, axis=1)
        ch = np.repeat(cmat[:, t], h // g, axis=1)
        state = (np.exp(dt[:, t] * a)[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None]
                 * bh[:, :, None, :])
        y[:, t] = np.einsum("bhpn,bhn->bhp", state, ch)
    return y, state


@pytest.mark.parametrize("length,real", [(21, 21), (24, 17), (8, 3)])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_scan_equals_the_recurrence(length, real, carried):
    """Across chunk edges (chunk 8), from a carried state or from zero,
    and with padding masked (``dt`` 0 past ``real`` tokens): outputs at
    the real tokens and the final state."""
    rng = np.random.RandomState(length + real)
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = rng.randn(b, length, h, p)
    dt = np.where(np.arange(length)[None, :, None] < real,
                  rng.uniform(0.001, 0.5, (b, length, h)), 0.0)
    a = -rng.uniform(1, 16, h)
    bm, cm = rng.randn(b, length, g, n), rng.randn(b, length, g, n)
    s0 = rng.randn(b, h, p, n) if carried else None
    f32 = lambda t: None if t is None else jnp.asarray(t, jnp.float32)
    y, final = ssm.ssd_scan(f32(x), f32(dt), f32(a), f32(bm), f32(cm),
                            f32(s0), chunk=8)
    want_y, want_s = _recurrence(x, dt, a, bm, cm, s0)
    np.testing.assert_allclose(np.asarray(y)[:, :real], want_y[:, :real],
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(final), want_s, atol=1e-4,
                               rtol=1e-4)


def test_packed_state_round_trips():
    s = jnp.arange(2 * 8 * 16 * 4, dtype=jnp.float32).reshape(2, 8, 16, 4)
    packed = ssm.pack_state(s, groups=2)
    assert packed.shape == (2, 2, 4, 64)          # e = 4 heads a row
    np.testing.assert_array_equal(ssm.unpack_state(packed, 8, 2), s)


# ------------------------------------------------ (f) the decode kernel

def _pallas_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _pallas_calls(inner)
    return found


@pytest.mark.parametrize("hq,groups,n,lanes", [(4, 2, 16, 32),
                                               (16, 2, 8, 128),
                                               (2, 2, 16, 32)])
def test_ssm_decode_kernel_equals_the_plain_form_and_aliases(hq, groups,
                                                             n, lanes):
    from bigdl_tpu.kernels.ssm_decode import ssm_decode_pallas

    rng = np.random.RandomState(hq)
    slots = 3
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    state = f32(rng.randn(slots, hq, n, lanes))
    dec = f32(rng.uniform(0, 1, (slots, hq, lanes))).at[1].set(0.0)
    dtx = f32(rng.randn(slots, hq, lanes))
    bc = f32(rng.randn(slots, n, 2 * groups))
    want_y, want_s = ssm.decode_step_reference(state, dec, dtx, bc)
    y, new = ssm_decode_pallas(state, dec, dtx, bc, interpret=True)
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(new, want_s, atol=1e-6, rtol=1e-6)
    # a row whose decay is 0 starts anew: nothing of the old state left
    np.testing.assert_allclose(
        new[1], bc[1, :, :groups].T.repeat(hq // groups, 0)[:, :, None]
        * dtx[1][:, None, :], atol=1e-6)
    (call,) = _pallas_calls(jax.make_jaxpr(
        lambda *a: ssm_decode_pallas(*a, interpret=True))(
            state, dec, dtx, bc).jaxpr)
    assert call.params["name"] == "bigdl_ssm_decode"
    assert tuple(call.params["input_output_aliases"]) == ((0, 1),)


def test_ssm_decode_dispatch_declines_what_it_cannot_take():
    state = jnp.zeros((2, 4, 16, 32), jnp.float32)
    vec, bc = jnp.zeros((2, 4, 32)), jnp.zeros((2, 16, 4))
    with kernels.use(KernelConfig.off()):
        assert kernels.ssm_decode_step(state, vec, vec, bc) is None
    with kernels.use(KernelConfig(decode_attention=True)):
        assert kernels.ssm_decode_step(state, vec, vec, bc) is not None
        assert kernels.ssm_decode_step(state.astype(jnp.bfloat16), vec, vec,
                                  bc) is None
    # compiled, half a lane tile is refused before Mosaic would
    with kernels.use(KernelConfig(decode_attention=True, interpret=False)):
        assert kernels.ssm_decode_step(state, vec, vec, bc) is None


# ------------------------------------------- (g) the four shares add up

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0..3, 4..7, 8..11, 12..15 of an expert
    layer: their routed parts, plus the shared expert counted once,
    equal the uncut reference layer; and the program computes each
    share's part."""
    whole = tiny(held=16, offset=0)
    lp = fam.make_layer(whole, SEED, 1)
    u = jnp.asarray(np.random.RandomState(3).randn(24, 64), jnp.float32)
    shared, routed = fam.moe_parts(whole, "f32", lp, u)
    total = jnp.zeros_like(routed)
    for off in (0, 4, 8, 12):
        cfg = tiny(held=4, offset=off)
        part = dict(lp, e_up=lp["e_up"][off:off + 4],
                    e_down=lp["e_down"][off:off + 4])
        sh, rt = fam.moe_parts(cfg, "f32", part, u)
        np.testing.assert_allclose(sh, shared, atol=1e-6)
        total = total + rt
        layer = fam.build_program_model(cfg).blocks[1].mlp
        got, _ = layer.apply(fam.program_layer(part)["mlp"],
                             layer.initial_state(), u[None])
        np.testing.assert_allclose(got[0], sh + rt, atol=ATOL, rtol=0)
    np.testing.assert_allclose(shared + total, shared + routed, atol=ATOL,
                               rtol=0)
    assert float(jnp.abs(routed).max()) > 10 * ATOL


# --------------------------------------------- (h) the expert layer

def test_latent_ungated_relu2_experts_equal_a_per_token_loop():
    layer = MoE(24, 40, 6, top_k=3, activation="relu2", gated=False,
                scoring="sigmoid", router_bias=True, route_scale=5.0,
                shared_size=56, latent_size=16)
    params = layer.init(jax.random.PRNGKey(0))
    assert set(params) == {"router", "router_bias", "w_up", "w_down",
                           "shared", "w_lat_in", "w_lat_out"}
    assert set(params["shared"]) == {"w_up", "w_down"}
    assert params["w_up"].shape == (6, 16, 40)
    assert params["shared"]["w_up"].shape == (24, 56)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 9, 24), jnp.float32)
    got, _ = layer.apply(params, layer.initial_state(), x)
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    relu2 = lambda t: np.square(np.maximum(t, 0.0))
    for t in range(9):
        u = np.asarray(x[0, t], np.float64)
        s = 1.0 / (1.0 + np.exp(-(u @ p["router"])))
        chosen = np.argsort(-(s + p["router_bias"]))[:3]
        w = s[chosen] / (s[chosen].sum() + 1e-20) * 5.0
        lat = u @ p["w_lat_in"]
        r = sum(wj * (relu2(lat @ p["w_up"][e]) @ p["w_down"][e])
                for wj, e in zip(w, chosen))
        want = r @ p["w_lat_out"] + relu2(u @ p["shared"]["w_up"]) \
            @ p["shared"]["w_down"]
        np.testing.assert_allclose(got[0, t], want, atol=1e-4, rtol=1e-4)


def test_an_expert_layer_built_as_before_is_unchanged():
    """The gated silu layer with a gated shared expert, as the Trinity
    pattern builds it: the same leaves from the same keys, the same
    result as its equations."""
    layer = MoE(24, 40, 4, top_k=2, activation="silu", gated=True,
                scoring="sigmoid", router_experts=8, expert_offset=2,
                router_bias=True, route_scale=2.448, shared_size=40)
    params = layer.init(jax.random.PRNGKey(1))
    assert set(params) == {"router", "router_bias", "w_up", "w_gate",
                           "w_down", "shared"}
    assert set(params["shared"]) == {"w_gate", "w_up", "w_down"}
    assert params["w_up"].shape == (4, 24, 40)
    ks = jax.random.split(jax.random.split(jax.random.PRNGKey(1), 5)[4], 3)
    np.testing.assert_array_equal(
        params["shared"]["w_gate"], jax.random.uniform(
            ks[0], (24, 40), jnp.float32, -24 ** -0.5, 24 ** -0.5))
    x = jnp.asarray(np.random.RandomState(1).randn(1, 7, 24), jnp.float32)
    got, _ = layer.apply(params, layer.initial_state(), x)
    _, idx, w = layer.route(params, x[0])
    silu = jax.nn.silu
    want = (silu(x[0] @ params["shared"]["w_gate"])
            * (x[0] @ params["shared"]["w_up"])) @ params["shared"]["w_down"]
    for t in range(7):
        for j in range(2):
            e = int(idx[t, j]) - 2
            if 0 <= e < 4:
                hid = silu(x[0, t] @ params["w_gate"][e]) \
                    * (x[0, t] @ params["w_up"][e])
                want = want.at[t].add(w[t, j] * (hid @ params["w_down"][e]))
    np.testing.assert_allclose(got[0], want, atol=1e-4, rtol=1e-4)


# ------------------------------- (i) served, and what it records

def test_generation_service_serves_it_and_records_the_state(cut):
    cfg, model = cut
    prompt = np.random.RandomState(4).randint(0, 256, 11).astype(np.int32)
    svc = GenerationService(config=GenerationConfig(
        slots=2, max_len=32, length_buckets=[16, 32], max_new_tokens=8))
    telemetry.tracer().clear()
    telemetry.enable()
    try:
        svc.load("lm", model)
        tokens = list(svc.generate("lm", prompt).result(120))
    finally:
        telemetry.disable()
        svc.shutdown()
    full = np.concatenate([prompt, tokens])[None]
    ref = np.asarray(fam.ref_forward(
        cfg, SEED, np.pad(full, ((0, 0), (0, (-full.shape[1]) % 8)))))[0]
    served = ref[np.arange(10, 18), tokens]
    assert np.all(ref[10:18].max(-1) - served < 1e-3)
    records = [s.args for s in telemetry.tracer().spans()
               if s.name == "serving/ssm/step"]
    telemetry.tracer().clear()
    row = 2 * 16 * 32 * 4 + 3 * 128 * 4          # one slot-layer's arrays
    assert {"kind": "prefill", "slot_layers": 3,
            "state_bytes": 2 * 3 * row} in records
    decodes = [r for r in records if r["kind"] == "decode"]
    assert len(decodes) == 7 and all(
        r == {"kind": "decode", "slot_layers": 3,
              "state_bytes": 2 * 3 * row} for r in decodes)
    gauge = telemetry.registry().get("serving/cache/state_bytes")
    assert gauge.value(model="lm") == 3 * 2 * row
    # what the family's comparison reads: one slot's recurrent state, in
    # bytes, at least the float32 state of the three layers
    assert telemetry.registry().get("serving/cache/slots").value(
        model="lm") == 2
    assert fam.served_state_bytes() == 3 * row >= 3 * fam.state_bytes(cfg)
    # a model served beside it that keeps no state does not hide it
    telemetry.registry().get("serving/cache/slots").set(4, model="other")
    assert fam.served_state_bytes() == 3 * row


def test_a_state_array_named_like_keys_or_values_is_refused():
    """The engine cuts the arrays named ``k`` and ``v`` at a column, so
    a recurrent entry may not use those names; and the bytes by kind
    count a layer that keeps nothing under no kind."""
    with pytest.raises(ValueError, match="named 'k' or 'v'"):
        KVCache(2, 8, [("state", (("k", (4,), None),))])
    kv = KVCache(2, 8, [("none",), ("kv", 1, 4, 8),
                        ("state", (("s", (4,), "float32"),))], "float32")
    assert kv.recurrent and kv.state_layers == 1
    assert kv.kind_bytes() == {"window": 0, "global": 2 * 2 * 4 * 8 * 4,
                               "state": 2 * 4 * 4}


# --------------------------------------------- (j) what refuses it

def test_prefix_cache_and_verify_refuse_a_recurrent_model_by_type(cut):
    _, model = cut
    svc = GenerationService(config=GenerationConfig(
        slots=2, max_len=32, length_buckets=[16, 32], max_new_tokens=4,
        prefix_cache_bytes=1 << 20))
    try:
        with pytest.raises(RecurrentStateError, match="snapshot"):
            svc.load("lm", model)
    finally:
        svc.shutdown()
    eng = _engine((16, 32))
    sv = ModelRegistry().load("m", model)
    with pytest.raises(RecurrentStateError, match="snapshot"):
        eng.verify_program(sv, 16)
    from bigdl_tpu.fleet.prefix import PrefixCache
    from bigdl_tpu.fleet.speculative import (SpeculativeConfig,
                                             SpeculativeDecoder)

    kv = KVCache.for_model(model, 2, 32)
    with pytest.raises(RecurrentStateError, match="snapshot"):
        PrefixCache.extract(kv, 0, 16)
    with pytest.raises(RecurrentStateError, match="snapshot"):
        SpeculativeDecoder(model, model, config=SpeculativeConfig(
            slots=2, max_len=32, length_buckets=[16, 32]))
    assert issubclass(RecurrentStateError, ValueError)


# ------------------------ (l) the configuration and the cell are the issue's

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3-super-ep4.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    cfg, row = _config(), _catalog_row()
    entry = next(c for c in _bench()["configs"]
                 if c["name"] == "nemotron3-super-ep4")
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["file"] == "benchmarks/configs/nemotron3-super-ep4.json"
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    for key, value in row["config"].items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert cfg["published"]["n_routed_experts"] == 512 \
        == cfg["deployment"]["router_experts"]
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (11, "MEMEMEM*EME", 128,
                                                 32768, 0)
    assert row["config"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert cfg["deployment"]["chips_sharing_a_layer"] == 4
    assert "NOT built" in cfg["departures"]["multi_token_prediction"]
    for block in ("published", "deployment", "assumed", "departures",
                  "reference"):
        assert cfg[block]


def test_a_fixed_router_seed_fixes_the_router_alone():
    """``deployment.router_seed`` (the cell's configuration sets it):
    two runs' seeds draw the same router matrix and bias and other
    leaves everywhere else, for the program and the reference alike."""
    cfg = tiny(held=4, offset=4)
    assert _config()["deployment"]["router_seed"] == 20261004
    fixed = dict(cfg, deployment=dict(cfg["deployment"], router_seed=9))
    one, two = fam.make_layer(fixed, 1, 1), fam.make_layer(fixed, 2, 1)
    free = fam.make_layer(cfg, 1, 1)
    for name in one:
        same = name in ("router", "router_bias")
        assert np.array_equal(one[name], two[name]) == same, name
        assert np.array_equal(one[name], free[name]) == (not same), name
    block = fam.make_program_params(fixed, 2)["block_1"]["mlp"]
    assert np.array_equal(block["router"], one["router"])
    assert np.array_equal(block["w_up"], two["e_up"])


def test_the_arithmetic_of_the_cut():
    cfg = _config()
    assert round(fam.param_count(cfg) / 1e6) == 4648
    assert fam.state_bytes(cfg) == 128 * 64 * 128 * 4
    assert fam.expert_bytes(cfg, 2) == 2 * 1024 * 2688 * 2
    assert fam.kv_read_bytes(cfg, 1, 2) == 1024
    whole = dict(cfg, num_hidden_layers=88, n_routed_experts=512,
                 vocab_size=131072, hybrid_override_pattern=cfg[
                     "published"]["hybrid_override_pattern"])
    assert round(fam.param_count(whole) / 1e9, 2) == 120.67


def test_the_cell_is_the_issues():
    bench = _bench()
    cell = next(w for w in bench["workloads"]
                if w["name"] == "nemotron3s_ep4_serve_sessions")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron3-super-ep4", "closed_sessions128", 1)
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    lists = ("serve_tokens_per_s", "mfu_pct.serve", "device_idle_pct.serve",
             "engine_host_pct.serve", "moe_experts_roofline",
             "moe_experts_touched_pct.serve",
             "moe_pairs_per_expert_max.serve", "gqa_decode_attn_roofline",
             "decode_kv_valid_share.serve",
             "decode_kv_write_in_kernel_share.serve", "ssm_decode_roofline",
             "ssm_state_gb_per_step.serve",
             "prefill_cache_unread_share.serve",          # PR 37's
             "host_launch_pct.serve", "host_emit_pct.serve",
             "host_admit_pct.serve")                      # PR 38's
    by = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    listed = {n for n, m in by.items() if cell["name"] in m.get(
        "workloads", ())}
    assert listed == set(lists)
    assert all(by[n]["workloads"][-1] == cell["name"] for n in lists)
    new = [by["ssm_decode_roofline"], by["ssm_state_gb_per_step.serve"]]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"],
             m["moves"]) for m in new] == [
        ("ssm_decode_roofline", "%", "higher", "device_trace", "kernels",
         "serve_tokens_per_s"),
        ("ssm_state_gb_per_step.serve", "GB", "lower", "program_counter",
         "cache", "serve_tokens_per_s")]
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "closed_sessions128.json")) as f:
        t = json.load(f)
    assert (t["driver"], t["clients"], t["slots"], t["max_len"],
            t["length_buckets"]) == ("serve_closed", 128, 128, 4096,
                                     [2048, 4096])
    assert t["prompt_len"] == {"dist": "uniform", "lo": 1536, "hi": 2048}
    assert t["new_tokens"] == {"dist": "uniform", "lo": 1024, "hi": 1536}
    assert (t["shared_prefix"], t["pool"], t["shape_seed"],
            t["warm_seconds"], t["trace_seconds"], t["check_requests"]) \
        == (0, 4096, 20261004, 12.0, 10.0, 8)
    assert (t["weights_dtype"], t["kv_dtype"]) == ("bfloat16", "bfloat16")
    assert {"bf16", "int8", "fp8", "state_bf16"} <= set(t["controls"])
    # the state's stated type, which the comparison holds a slot's bytes to
    assert _config()["mamba_ssm_cache_dtype"] == "float32"
