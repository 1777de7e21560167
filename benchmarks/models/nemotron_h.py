"""nemotron_h family (NVIDIA Nemotron-H / Nemotron 3): the program's
model built through its public API, the plain reference, and the
required operations and bytes.

The same three parts as ``afmoe.py``, for a decoder whose layers are ONE
mixer or ONE feed-forward part each:

- ``build_program_model`` / ``make_program_params``: the system under
  test (``bigdl_tpu.models.PatternDecoderLM``) and its parameter layout.
  The only place that imports the program.
- ``init_layer`` / ``ref_*``: seeded weights, one layer at a time, and
  the plain float32 reference in straightforward ``jax.numpy`` (products
  at ``highest``). Imports nothing of the program.
- ``serve_flops_*`` / ``kv_read_bytes`` / ``state_bytes`` /
  ``expert_*``: what the algorithm requires of THIS chip, from shapes.

The equations (``config.json`` and the family's public
``modeling_nemotron_h.py``; every point taken from the latter is under
``assumed`` in the configuration's file). RMSNorm, no biases but the
convolution's, no embedding scale, untied head. ``hybrid_override_pattern``
names each layer: ``M`` Mamba-2, ``E`` experts, ``*`` attention.

- every layer: ``x <- x + f(RMSNorm(x))``; after the last, RMSNorm and
  the head.
- ``M`` (``Hm`` heads of ``P``, ``G`` groups of ``N``, ``K`` taps, ``C =
  Hm P + 2 G N`` convolution channels): ``[z | xBC | dt] = u W_in``;
  ``xBC_t <- silu(b + sum_j w_j * xBC_{t-K+1+j})`` (zeros before the
  sequence); ``x [Hm, P]``, ``B [G, N]``, ``C [G, N]``, head ``i`` on
  group ``i // (Hm / G)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y * silu(z))`` with the
  mean square over each group's ``Hm P / G`` channels; ``out = y
  W_out``. The reference runs the recurrence TOKEN BY TOKEN.
- ``*``: ``q = u Wq`` (H heads of d), ``k, v = u Wk, u Wv`` (Hkv heads),
  causal soft-max of ``q k^T / sqrt(d)`` over the whole prefix, query
  head ``i`` on K/V head ``i // (H / Hkv)``, ``out = o Wo``. No rotary,
  no other positional term.
- ``E``: ``s = sigmoid(u Wr)`` in float32 over the router's published
  width; the ``top_k`` largest of ``s + b``; weights ``s[chosen] / (sum
  + 1e-20) x routed_scaling_factor`` (without ``b``); ``l = u W_lat_in``;
  ``r = sum_j w_j relu(l U_j)^2 D_j`` (no gate matrix); ``out = r
  W_lat_out + relu(u U_s)^2 D_s``.

**The share.** The configuration holds ``n_routed_experts`` of the
router's ``deployment.router_experts`` experts, the ids from
``deployment.expert_offset`` on: the router scores all of them, and the
chosen experts that are not held add nothing, here and in the program
alike. ``vocab_size`` is the slice of the vocabulary held.
``deployment.router_seed``, where the configuration gives one, draws
every expert layer's router matrix and bias from that constant in every
run and all other leaves from the run's seed (which experts a step
touches follows the router's columns, and with them the step's time).

Required operations of this chip, per token: ``2 x`` (the matrices a
token meets: a Mamba layer's two projections, an attention layer's
four, an expert layer's router, latent projections, shared expert and
``top_k x held / router width`` routed experts, the expectation) + the
scan's ``6 Hm P N`` a Mamba layer + attention ``4 H d c``; the head
once a prompt and once a decoded token. Soft-max, norms, the
convolution, the gate and the router's top-k are not counted.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.models.afmoe import _rms, _round_bf16
from benchmarks.models.gpt2 import _mm, seed_key

# ------------------------------------------------------------- sizes


def dims(cfg) -> dict:
    dep = cfg["deployment"]
    L = int(cfg["num_hidden_layers"])
    hm, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return {
        "L": L, "h": int(cfg["hidden_size"]),
        "kinds": str(cfg["hybrid_override_pattern"])[:L],
        "H": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "Hm": hm, "P": p, "G": g, "N": n, "K": int(cfg["conv_kernel"]),
        "inner": hm * p, "C": hm * p + 2 * g * n,
        "chunk": int(cfg["chunk_size"]),
        "lat": int(cfg["moe_latent_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["moe_shared_expert_intermediate_size"]),
        "E": int(cfg["n_routed_experts"]), "Er": int(dep["router_experts"]),
        "off": int(dep.get("expert_offset", 0)),
        "rseed": dep.get("router_seed"),
        "k": int(cfg["num_experts_per_tok"]), "V": int(cfg["vocab_size"]),
        "eps": float(cfg["layer_norm_epsilon"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm": bool(cfg.get("norm_topk_prob", True)),
        "dt": (float(cfg["time_step_min"]), float(cfg["time_step_max"]),
               float(cfg["time_step_floor"])),
        "std": float(cfg.get("initializer_range", 0.02)),
    }


def _count(z, kind: str) -> int:
    return sum(1 for c in z["kinds"] if c == kind)


def mamba_params(cfg) -> int:
    """W_in ``[h, inner + C + Hm]`` and W_out ``[inner, h]``."""
    z = dims(cfg)
    return z["h"] * (z["inner"] + z["C"] + z["Hm"]) + z["inner"] * z["h"]


def attn_params(cfg) -> int:
    """Wq, Wo ``[h, H d]`` and Wk, Wv ``[h, Hkv d]``."""
    z = dims(cfg)
    return z["h"] * z["d"] * (2 * z["H"] + 2 * z["Hkv"])


def expert_params(cfg) -> int:
    """One routed expert: up and down, in the latent space."""
    z = dims(cfg)
    return 2 * z["lat"] * z["Fe"]


def expert_bytes(cfg, itemsize: int) -> int:
    return expert_params(cfg) * itemsize


def pair_flops(cfg) -> float:
    """One token through one routed expert."""
    return 2.0 * expert_params(cfg)


def moe_shared_params(cfg) -> int:
    """What every token meets in an expert layer outside the routed
    experts: router, both latent projections, the shared expert."""
    z = dims(cfg)
    return z["h"] * (z["Er"] + 2 * z["lat"] + 2 * z["Fs"])


def matmul_params_token(cfg) -> float:
    """Weights one token is multiplied by on this chip, without the
    head, the routed experts at their expected share."""
    z = dims(cfg)
    return float(
        _count(z, "M") * mamba_params(cfg)
        + _count(z, "*") * attn_params(cfg)
        + _count(z, "E") * (moe_shared_params(cfg) + z["k"] * z["E"]
                            / z["Er"] * expert_params(cfg)))


def param_count(cfg) -> int:
    """Every parameter held here (norms, convolutions, biases too)."""
    z = dims(cfg)
    m = mamba_params(cfg) + (z["K"] + 1) * z["C"] + 3 * z["Hm"] \
        + z["inner"] + z["h"]
    a = attn_params(cfg) + z["h"]
    e = moe_shared_params(cfg) + z["Er"] + z["h"] \
        + z["E"] * expert_params(cfg)
    return (2 * z["V"] * z["h"] + z["h"] + _count(z, "M") * m
            + _count(z, "*") * a + _count(z, "E") * e)


def _token_flops(cfg) -> float:
    """One token through the layers, attention's context term apart."""
    z = dims(cfg)
    return (2.0 * matmul_params_token(cfg)
            + 6.0 * z["Hm"] * z["P"] * z["N"] * _count(z, "M"))


def serve_flops_per_token(cfg, context: int) -> float:
    """One decoded token at context ``context``: the layers, attention
    over the prefix in the attention layers, and the head."""
    z = dims(cfg)
    return (_token_flops(cfg) + 2.0 * z["V"] * z["h"]
            + 4.0 * z["H"] * z["d"] * context * _count(z, "*"))


def serve_flops_span(cfg, first: int, last: int) -> float:
    """Prompt tokens at positions first..last-1, each attending its own
    prefix, and the head ONCE."""
    z = dims(cfg)
    n = max(0, last - first)
    att = (first + 1 + last) * n / 2.0           # sum of p + 1
    return (_token_flops(cfg) * n
            + 4.0 * z["H"] * z["d"] * att * _count(z, "*")
            + (2.0 * z["V"] * z["h"] if n else 0.0))


def kv_read_bytes(cfg, context: int, kv_itemsize: int) -> float:
    """Bytes of cached keys and values one decoded token must read at
    context ``context``: the attention layers' alone (a Mamba layer
    keeps no positions)."""
    z = dims(cfg)
    return (float(context) * 2 * z["Hkv"] * z["d"] * kv_itemsize
            * _count(z, "*"))


def state_bytes(cfg, dtype=None) -> int:
    """Bytes of ONE Mamba layer's recurrent state for ONE slot, ``Hm x P
    x N`` of the type the configuration states (``mamba_ssm_cache_dtype``,
    float32): what a decode step must read once and write once a live
    slot and layer (the convolution's ``K - 1`` inputs, 1.5% of it, are
    not counted). ``dtype`` asks for another type's bytes."""
    z = dims(cfg)
    kept = np.dtype(dtype or cfg.get("mamba_ssm_cache_dtype", "float32"))
    return z["Hm"] * z["P"] * z["N"] * kept.itemsize


# ------------------------------------------------- weights from a seed

def layer_shapes(cfg, kind: str) -> dict:
    """Leaf shapes of a layer of ``kind`` (``M``, ``E`` or ``*``)."""
    z = dims(cfg)
    h, d = z["h"], z["d"]
    if kind == "M":
        return {"norm": (h,), "w_in": (h, z["inner"] + z["C"] + z["Hm"]),
                "conv_w": (z["K"], z["C"]), "conv_b": (z["C"],),
                "dt_bias": (z["Hm"],), "A_log": (z["Hm"],),
                "D": (z["Hm"],), "gate_norm": (z["inner"],),
                "w_out": (z["inner"], h)}
    if kind == "*":
        return {"norm": (h,), "wq": (h, z["H"] * d),
                "wk": (h, z["Hkv"] * d), "wv": (h, z["Hkv"] * d),
                "wo": (z["H"] * d, h)}
    if kind == "E":
        return {"norm": (h,), "router": (h, z["Er"]),
                "router_bias": (z["Er"],),
                "w_lat_in": (h, z["lat"]), "w_lat_out": (z["lat"], h),
                "s_up": (h, z["Fs"]), "s_down": (z["Fs"], h),
                "e_up": (z["E"], z["lat"], z["Fe"]),
                "e_down": (z["E"], z["Fe"], z["lat"])}
    raise ValueError(f"layer kind {kind!r}")


def _draw(z, name, key, shape):
    """One leaf, float32 on bfloat16's grid. Matrices: normal(0, std).
    Norm weights: 1 + 0.1 normal. The router's bias: 0.02 normal. The
    mixer's own leaves as the modelling code initialises them: ``A_log =
    log(uniform 1..16)``, ``D = 1``, ``dt_bias`` the inverse soft-plus
    of a log-uniform step in ``time_step_min..max`` floored at
    ``time_step_floor``, the convolution uniform in ``+-1/sqrt(K)`` (a
    0.02 normal would leave x, B and C near zero and the state
    unread)."""
    if name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "D":
        x = jnp.ones(shape, jnp.float32)
    elif name == "dt_bias":
        lo, hi, floor = z["dt"]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name in ("conv_w", "conv_b"):
        s = 1.0 / math.sqrt(z["K"])
        x = jax.random.uniform(key, shape, jnp.float32, -s, s)
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        if "norm" in name:
            x = 1.0 + 0.1 * x
        elif name == "router_bias":
            x = 0.02 * x
        else:
            x = z["std"] * x
    return _round_bf16(x)


def init_layer(cfg, key, i, kind: str) -> dict:
    """Layer ``i``'s leaves from ``key`` (traced inside a jitted call;
    ``i`` may be traced)."""
    shapes, z = layer_shapes(cfg, kind), dims(cfg)
    lk = jax.random.fold_in(key, i)
    # ``deployment.router_seed``: the router's matrix and bias from that
    # seed in every run, all other leaves from the run's
    rk = lk if z["rseed"] is None else jax.random.fold_in(
        seed_key(z["rseed"]), i)
    return {n: _draw(z, n, jax.random.fold_in(
        rk if n in ("router", "router_bias") else lk, j), shapes[n])
        for j, n in enumerate(sorted(shapes))}


def init_ends(cfg, key) -> dict:
    """Embedding, final norm and head."""
    z = dims(cfg)
    k = jax.random.fold_in(key, 1_000_003)
    return {"embed": _draw(z, "embed", jax.random.fold_in(k, 0),
                           (z["V"], z["h"])),
            "norm_f": _draw(z, "norm_f", jax.random.fold_in(k, 1),
                            (z["h"],)),
            "lm_head": _draw(z, "lm_head", jax.random.fold_in(k, 2),
                             (z["h"], z["V"]))}


def _cfg_key(cfg):
    """A hashable form of what shapes and draws depend on."""
    z = dims(cfg)
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple))
                         else v) for k, v in z.items()))


_CFGS = {}


def _remember(cfg):
    key = _cfg_key(cfg)
    _CFGS[key] = cfg
    return key


@functools.lru_cache(maxsize=None)
def _layer_maker(cfg_key, kind, dtype_name):
    cfg, dtype = _CFGS[cfg_key], jnp.dtype(dtype_name)
    return jax.jit(lambda key, i: jax.tree.map(
        lambda a: a.astype(dtype), init_layer(cfg, key, i, kind)))


@functools.lru_cache(maxsize=None)
def _ends_maker(cfg_key, dtype_name):
    cfg, dtype = _CFGS[cfg_key], jnp.dtype(dtype_name)
    return jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(dtype), init_ends(cfg, key)))


def make_layer(cfg, seed, i, dtype="float32"):
    kind = dims(cfg)["kinds"][i]
    return _layer_maker(_remember(cfg), kind, dtype)(seed_key(seed),
                                                     jnp.int32(i))


def make_ends(cfg, seed, dtype="float32"):
    return _ends_maker(_remember(cfg), dtype)(seed_key(seed))


# --------------------------------------------- the program under test

def build_program_model(cfg):
    """``PatternDecoderLM`` at the configuration's sizes, through the
    constructor a user calls."""
    from bigdl_tpu.models import PatternDecoderLM

    z = dims(cfg)
    pattern = [{"M": ("ssm", "none"), "E": ("none", "experts"),
                "*": ("global", "none")}[c] for c in z["kinds"]]
    lo, hi, floor = z["dt"]
    return PatternDecoderLM(
        z["V"], hidden_size=z["h"], pattern=pattern, num_heads=z["H"],
        num_kv_heads=z["Hkv"], head_dim=z["d"], ffn_size=0, window=0,
        rope_layers="none", norm_eps=z["eps"],
        max_len=int(cfg["max_position_embeddings"]),
        expert_size=z["Fe"], shared_size=z["Fs"],
        router_experts=z["Er"], local_experts=(z["off"], z["E"]),
        top_k=z["k"], route_scale=z["scale"], route_norm=z["norm"],
        block_style="prenorm", qk_norm=False, attn_gate=False,
        expert_activation="relu2", expert_gated=False,
        latent_size=z["lat"],
        ssm=dict(num_heads=z["Hm"], head_dim=z["P"], state_size=z["N"],
                 groups=z["G"], conv_kernel=z["K"], chunk=z["chunk"],
                 dt_min=lo, dt_max=hi, dt_floor=floor))


def program_layer(lp: dict) -> dict:
    """One reference-layout layer in ``PatternDecoderLM``'s layout."""
    if "w_in" in lp:
        return {"norm_in": {"weight": lp["norm"]},
                "ssm": {**{n: lp[n] for n in
                           ("w_in", "conv_w", "conv_b", "dt_bias", "A_log",
                            "D", "w_out")}, "norm": lp["gate_norm"]}}
    if "wq" in lp:
        return {"norm_in": {"weight": lp["norm"]},
                "attn": {n: lp[n] for n in ("wq", "wk", "wv", "wo")}}
    return {"norm_pre_mlp": {"weight": lp["norm"]},
            "mlp": {"router": lp["router"],
                    "router_bias": lp["router_bias"],
                    "w_lat_in": lp["w_lat_in"],
                    "w_lat_out": lp["w_lat_out"],
                    "w_up": lp["e_up"], "w_down": lp["e_down"],
                    "shared": {"w_up": lp["s_up"],
                               "w_down": lp["s_down"]}}}


def make_program_params(cfg, seed, dtype="float32"):
    """Weights on the device in the type they are used in, laid out for
    the program; one jitted call a layer."""
    ends = make_ends(cfg, seed, dtype)
    out = {"embed": ends["embed"], "lm_head": ends["lm_head"],
           "norm_f": {"weight": ends["norm_f"]}}
    for i in range(dims(cfg)["L"]):
        out[f"block_{i}"] = program_layer(make_layer(cfg, seed, i, dtype))
    return out


# ------------------------------------------------ the plain reference

#: modes whose activations are bfloat16 (products round their operands
#: to it and sum in float32); ``state_bf16`` also keeps the recurrent
#: state in bfloat16 between tokens
_BF16_MODES = ("bf16", "state_bf16")


def _mode_mm(mode):
    return "bf16" if mode in _BF16_MODES else mode


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mamba(cfg, mode, lp, u):
    """One row ``u [S, h]`` through the mixer, the recurrence token by
    token (``lax.scan`` over positions, no chunks)."""
    z = dims(cfg)
    S = u.shape[0]
    hm, p, g, n, k = z["Hm"], z["P"], z["G"], z["N"], z["K"]
    mm = _mode_mm(mode)
    f32 = jnp.float32
    zxd = _mm(u, lp["w_in"], mm)
    gate = zxd[:, :z["inner"]]
    xbc = zxd[:, z["inner"]:z["inner"] + z["C"]]
    dt = jax.nn.softplus(zxd[:, z["inner"] + z["C"]:].astype(f32)
                         + lp["dt_bias"].astype(f32))          # [S, Hm]
    a = -jnp.exp(lp["A_log"].astype(f32))
    ext = jnp.concatenate([jnp.zeros((k - 1, z["C"]), xbc.dtype), xbc])
    conv = lp["conv_b"].astype(f32)
    for j in range(k):
        conv = conv + lp["conv_w"][j].astype(f32) * ext[j:j + S].astype(f32)
    xbc = jax.nn.silu(conv).astype(u.dtype)
    x = xbc[:, :z["inner"]].reshape(S, hm, p)
    bm = xbc[:, z["inner"]:z["inner"] + g * n].reshape(S, g, n)
    cm = xbc[:, z["inner"] + g * n:].reshape(S, g, n)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        bh = jnp.repeat(b_t.astype(f32), hm // g, axis=0)      # [Hm, N]
        ch = jnp.repeat(c_t.astype(f32), hm // g, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t.astype(f32))[:, :, None]
                 * bh[:, None, :])
        if mode == "state_bf16":
            state = _round_bf16(state)
        return state, jnp.sum(state * ch[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((hm, p, n), f32), (x, bm, cm, dt))
    y = (y + lp["D"].astype(f32)[:, None] * x.astype(f32)).astype(u.dtype)
    v = (y.reshape(S, z["inner"]) * jax.nn.silu(gate)).astype(f32)
    v = v.reshape(S, g, z["inner"] // g)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + z["eps"])
    v = v.reshape(S, z["inner"]).astype(u.dtype) * lp["gate_norm"]
    return _mm(v, lp["w_out"], mm)


def _attention(cfg, mode, lp, u, block_q: int):
    """One row ``u [S, h]`` (S a multiple of ``block_q``), blocked over
    queries so that no ``[H, S, S]`` scores are held."""
    z = dims(cfg)
    S = u.shape[0]
    H, Hkv, d = z["H"], z["Hkv"], z["d"]
    G = H // Hkv
    mm = _mode_mm(mode)
    prec = None if mm == "bf16" else "highest"
    q = _mm(u, lp["wq"], mm).reshape(S // block_q, block_q, Hkv, G, d)
    k = _mm(u, lp["wk"], mm).reshape(S, Hkv, d)
    v = _mm(u, lp["wv"], mm).reshape(S, Hkv, d)
    pos = jnp.arange(S)
    pb = pos.reshape(S // block_q, block_q)

    def block(args):
        qi, pi = args                               # [Bq,Hkv,G,d], [Bq]
        sc = jnp.einsum("qngd,knd->ngqk", qi, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
        sc = jnp.where((pos[None, :] <= pi[:, None])[None, None], sc,
                       -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return jnp.einsum("ngqk,knd->qngd", w, v, precision=prec)

    o = jax.lax.map(block, (q, pb)).reshape(S, H * d)
    return _mm(o, lp["wo"], mm)


def route(cfg, lp, u):
    """The router over its published width: the chosen experts' ids
    ``[S, k]`` and their weights. Float32 at ``highest`` whatever the
    mode: the router is kept in float32 by the model."""
    z = dims(cfg)
    s = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32),
                                  lp["router"].astype(jnp.float32),
                                  precision="highest"))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           z["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if z["norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * z["scale"]


def moe_parts(cfg, mode, lp, u, offset=None):
    """``(shared(u), the held experts' routed part)`` for ``u [S, h]``,
    each in the hidden space. ``lp['e_*']`` hold the experts ``offset ..
    offset + E - 1`` of the router's numbering; every held expert runs
    over every token and the combine weights zero the rest - the plain
    form."""
    z = dims(cfg)
    off = z["off"] if offset is None else offset
    E = lp["e_up"].shape[0]
    mm = _mode_mm(mode)
    idx, w = route(cfg, lp, u)
    comb = jnp.sum(jnp.where(((idx - off)[..., None] == jnp.arange(E)),
                             w[..., None], 0.0), axis=1)   # [S, E]
    lat = _mm(u, lp["w_lat_in"], mm)

    def one(acc, ew):
        eu, ed, c = ew
        return acc + c[:, None].astype(lat.dtype) * _mm(
            _relu2(_mm(lat, eu, mm)), ed, mm), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                             (lp["e_up"], lp["e_down"], comb.T))
    shared = _mm(_relu2(_mm(u, lp["s_up"], mm)), lp["s_down"], mm)
    return shared, _mm(routed, lp["w_lat_out"], mm)


def ref_layer(cfg, kind, lp, x, mode="f32", block_q=None):
    """One layer of ``kind`` over rows ``x [R, S, h]``."""
    z = dims(cfg)
    bq = block_q or math.gcd(x.shape[1], 512)

    def row(xr):
        u = _rms(xr, lp["norm"], z["eps"])
        if kind == "M":
            return xr + _mamba(cfg, mode, lp, u)
        if kind == "*":
            return xr + _attention(cfg, mode, lp, u, bq)
        shared, routed = moe_parts(cfg, mode, lp, u)
        return xr + shared + routed

    return jax.lax.map(row, x)


def ref_embed(cfg, ends, tokens):
    return ends["embed"][tokens]


def ref_head(cfg, ends, x, mode="f32"):
    """Logits ``[.., V]`` (float32) of hidden rows ``x [.., h]``."""
    z = dims(cfg)
    y = _rms(x, ends["norm_f"], z["eps"])
    return _mm(y, ends["lm_head"], _mode_mm(mode)).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_key, kind, mode):
    cfg = _CFGS[cfg_key]
    return jax.jit(lambda lp, x: ref_layer(cfg, kind, lp, x, mode))


def _low(mode):
    if mode in _BF16_MODES:
        return lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t)
    return lambda t: t


def ref_hidden(cfg, seed, tokens, mode="f32"):
    """Final hidden rows ``[R, S, h]`` (before the last norm), one
    layer's weights at a time."""
    ck = _remember(cfg)
    low = _low(mode)
    x = low(ref_embed(cfg, make_ends(cfg, seed),
                      jnp.asarray(tokens, jnp.int32)))
    for i, kind in enumerate(dims(cfg)["kinds"]):
        lp = low(make_layer(cfg, seed, i))
        x = _layer_fn(ck, kind, mode)(lp, x)
        del lp
    return x


def ref_forward(cfg, seed, tokens, mode="f32"):
    """Logits ``[R, S, V]``: for the tests' small sizes."""
    x = ref_hidden(cfg, seed, tokens, mode)
    return ref_head(cfg, _low(mode)(make_ends(cfg, seed)), x, mode)


#: the type a control keeps the recurrent state in, where that is not
#: the configuration's
_CONTROL_STATE_DTYPE = {"state_bf16": "bfloat16"}


def served_state_bytes() -> float:
    """Bytes of recurrent state ONE slot of the served cache holds: the
    program's always-on gauges ``serving/cache/state_bytes`` over
    ``serving/cache/slots`` (``generation/service.py`` sets both from
    the arrays it allocated for the cache the window ran on), read in
    this process; a model of the process that keeps no recurrent state
    is passed over. 0 where the program published nothing."""
    from bigdl_tpu import telemetry

    reg = telemetry.registry()
    total, slots = (reg.get("serving/cache/state_bytes"),
                    reg.get("serving/cache/slots"))
    if total is None or slots is None:
        return 0.0
    return min((total.value(**at) / slots.value(**at)
                for at in slots.label_sets()
                if slots.value(**at) > 0 and total.value(**at) > 0),
               default=0.0)


def ref_token_gaps(cfg, seed, rows, mode="f32"):
    """For the served-model comparison (``gpt2.ref_token_gaps`` has the
    contract): one reference forward over each ``prompt + served``, the
    gap ``best - logit[token]`` at EVERY served position, for the served
    tokens and for the tokens a ``mode``-precision forward puts first.

    **The gaps are cut off at ``reference.gap_quantile`` of all served
    positions**, pooled over the checked requests, so what the driver
    reads as ``gap_max`` is that quantile: a bfloat16 hidden state sends
    a few percent of tokens to another expert than the float32 router's
    22nd (a flip is worth 1/22 of a layer's routed part), which the
    largest single gap counts and the quantile does not.

    **The state's bytes.** The configuration states the recurrent
    state's type (``mamba_ssm_cache_dtype``), and no number computed
    from tokens, nor the state's own values, tells a state kept in
    bfloat16 from bfloat16 activations (PERF.md section 2). So the type
    is held to as bytes: a slot of the served cache must hold at least
    ``state_bytes(cfg)`` a Mamba layer (:func:`served_state_bytes`; for
    a control, the bytes of the type it keeps its state in). Where it
    holds less, every gap of that list comes back NaN and the run
    cannot be ``correct``; both numbers go to standard error."""
    quantile = float(cfg.get("reference", {}).get("gap_quantile", 1.0))
    longest = max(len(p) + len(o) for p, o in rows)
    step = 512 if longest > 512 else 64
    width = -(-longest // step) * step
    toks = np.zeros((len(rows), width), np.int32)
    nxt = np.zeros((len(rows), width), np.int32)
    for r, (p, o) in enumerate(rows):
        full = np.concatenate([p, o]).astype(np.int32)
        toks[r, :len(full)] = full
        nxt[r, :len(full) - 1] = full[1:]       # position j predicts j + 1
    x_ref = ref_hidden(cfg, seed, toks, "f32")
    x_low = x_ref if mode == "f32" else ref_hidden(cfg, seed, toks, mode)
    ends = make_ends(cfg, seed)

    @jax.jit
    def gaps(ends, x_ref, x_low, nxt):
        low_ends = _low(mode)(ends)

        def row(args):
            xr, xl, n = args
            ref = ref_head(cfg, ends, xr)
            best = jnp.max(ref, axis=-1)
            g = best - jnp.take_along_axis(ref, n[:, None], -1)[:, 0]
            if mode == "f32":
                return g, g
            pick = jnp.argmax(ref_head(cfg, low_ends, xl, mode), axis=-1)
            return g, best - jnp.take_along_axis(ref, pick[:, None],
                                                 -1)[:, 0]
        return jax.lax.map(row, (x_ref, x_low, nxt))

    gs, gl = jax.device_get(gaps(ends, x_ref, x_low, jnp.asarray(nxt)))
    spans = [(len(p) - 1, len(p) + len(o) - 1) for p, o in rows]
    out_served = [np.asarray(gs[r, lo:hi], np.float64)
                  for r, (lo, hi) in enumerate(spans)]
    out_low = None if mode == "f32" else [
        np.asarray(gl[r, lo:hi], np.float64)
        for r, (lo, hi) in enumerate(spans)]

    def cut(lists):
        """Every list cut off at the quantile of ALL its positions."""
        if lists is None or quantile >= 1.0:
            return lists
        q = np.quantile(np.concatenate(lists), quantile)
        return [np.minimum(g, q) for g in lists]

    stated = _count(dims(cfg), "M") * state_bytes(cfg)
    held = {"served": served_state_bytes()}
    if mode != "f32":
        held[mode] = _count(dims(cfg), "M") * state_bytes(
            cfg, _CONTROL_STATE_DTYPE.get(mode))
    pool = np.concatenate(out_served)
    print(f"nemotron_h reference ({mode}): largest gap {pool.max():.4f}, "
          f"the {quantile:g}-quantile of {pool.size} served positions "
          f"{np.quantile(pool, min(quantile, 1.0)):.4f}; recurrent state "
          f"a slot, bytes: {held}, stated at least {stated}",
          file=sys.stderr)
    nan = lambda lists: [np.full(len(g), np.nan) for g in lists]
    out_served = cut(out_served) if held["served"] >= stated \
        else nan(out_served)
    if out_low is not None:
        out_low = cut(out_low) if held[mode] >= stated else nan(out_low)
    return out_served, out_low
