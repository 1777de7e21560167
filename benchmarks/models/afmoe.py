"""afmoe family (Arcee Trinity): the program's model built through its
public API, the plain reference, and the required operations and bytes.

The same three parts as ``gpt2.py``, for a decoder whose layers differ:

- ``build_program_model`` / ``make_program_params``: the system under
  test (``bigdl_tpu.models.PatternDecoderLM``) and its parameter layout.
  The only place that imports the program.
- ``init_layer`` / ``ref_*``: seeded weights, one layer at a time, and
  the plain float32 reference in straightforward ``jax.numpy`` (products
  at ``highest``). Imports nothing of the program.
- ``serve_flops_*`` / ``kv_read_bytes`` / ``expert_*``: what the
  algorithm requires of THIS chip, from shapes alone.

The equations (``config.json`` and the family's public
``modeling_afmoe.py``; every departure is under ``assumed`` in the
configuration's file). RMSNorm everywhere, no biases.

- ``x0 = embed[ids] * sqrt(h)``; final RMSNorm; untied head ``[h, V]``.
- a layer: ``a = x + norm_post_attn(attn(norm_in(x)))``;
  ``y = a + norm_post_mlp(mlp(norm_pre_mlp(a)))``.
- ``attn(u)``: ``q = u Wq`` (H heads of d), ``k = u Wk``, ``v = u Wv``
  (Hkv heads of d), ``g = u Wg``; q and k through an RMSNorm over the
  head; rotary on q and k in window layers only (global layers carry no
  positions); causal soft-max attention, scale ``1/sqrt(d)``, each K/V
  head shared by H/Hkv query heads, window layers see the last
  ``sliding_window`` positions, the query's own included;
  ``out = (o * sigmoid(g)) Wo``.
- dense ``mlp(u) = (silu(u Wgate) * (u Wup)) Wdown``.
- expert ``mlp(u)``: ``s = sigmoid(u Wr)`` in float32 over the router's
  published width; the ``top_k`` experts with the largest ``s + b``;
  weights ``w = s[chosen]`` (without ``b``), normalised, times
  ``route_scale``; ``shared(u) + sum_j w_j expert_j(u)``.

**The share.** The configuration holds ``num_experts`` of the router's
``deployment.router_experts`` experts, the ids from
``deployment.expert_offset`` on: the router scores all of them, and the
chosen experts that are not held add nothing, here and in the program
alike. ``vocab_size`` is the slice of the vocabulary held.

Required operations of this chip, per token: ``2 x`` (the weights
outside the routed experts + ``top_k x held / router width`` routed
experts a layer, the expectation) + attention ``4 x H d x`` (``min(c,
window)`` per window layer, ``c`` per global one); the head once a
prompt and once a decoded token. Soft-max, norms, rotary, the gate and
the router's top-k are not counted.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.models.gpt2 import _mm, seed_key

# ------------------------------------------------------------- sizes


def dims(cfg) -> dict:
    dep = cfg["deployment"]
    L = int(cfg["num_hidden_layers"])
    return {
        "L": L, "h": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["moe_intermediate_size"])
        * int(cfg.get("num_shared_experts", 1)),
        "E": int(cfg["num_experts"]), "Er": int(dep["router_experts"]),
        "off": int(dep.get("expert_offset", 0)),
        "k": int(cfg["num_experts_per_tok"]), "V": int(cfg["vocab_size"]),
        "W": int(cfg["sliding_window"]),
        "dense": int(cfg["num_dense_layers"]),
        "window": [t == "sliding_attention" for t in cfg["layer_types"][:L]],
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "scale": float(cfg["route_scale"]),
        "norm": bool(cfg.get("route_norm", True)),
        "std": float(cfg.get("initializer_range", 0.02)),
    }


def attn_params(cfg) -> int:
    """Wq, Wg, Wo ``[h, H d]`` and Wk, Wv ``[h, Hkv d]``."""
    z = dims(cfg)
    return z["h"] * z["d"] * (3 * z["H"] + 2 * z["Hkv"])


def expert_params(cfg) -> int:
    """One routed expert: gate, up, down."""
    z = dims(cfg)
    return 3 * z["h"] * z["Fe"]


def expert_bytes(cfg, itemsize: int) -> int:
    return expert_params(cfg) * itemsize


def pair_flops(cfg) -> float:
    """One token through one routed expert."""
    return 2.0 * expert_params(cfg)


def matmul_params_token(cfg) -> float:
    """Weights one token is multiplied by on this chip, without the
    head: attention and the dense FFN or the shared expert and router of
    every layer, plus the expected share of routed experts."""
    z = dims(cfg)
    n = z["L"] * attn_params(cfg)
    n += z["dense"] * 3 * z["h"] * z["F"]
    moe = z["L"] - z["dense"]
    n += moe * (3 * z["h"] * z["Fs"] + z["h"] * z["Er"])
    n += moe * z["k"] * z["E"] / z["Er"] * expert_params(cfg)
    return float(n)


def param_count(cfg) -> int:
    """Every parameter held here (norms included)."""
    z = dims(cfg)
    moe = z["L"] - z["dense"]
    norms = z["L"] * (4 * z["h"] + 2 * z["d"]) + z["h"]
    return (2 * z["V"] * z["h"] + z["L"] * attn_params(cfg)
            + z["dense"] * 3 * z["h"] * z["F"]
            + moe * (3 * z["h"] * z["Fs"] + z["h"] * z["Er"] + z["Er"]
                     + z["E"] * expert_params(cfg)) + norms)


def _attended(z, context: int) -> float:
    """Keys one query at context ``context`` attends, summed over the
    layers."""
    return float(sum(min(context, z["W"]) if w else context
                     for w in z["window"]))


def serve_flops_per_token(cfg, context: int) -> float:
    """One decoded token at context ``context``: the layers, attention
    over what each layer keeps, and the head."""
    z = dims(cfg)
    return (2.0 * (matmul_params_token(cfg) + z["V"] * z["h"])
            + 4.0 * z["H"] * z["d"] * _attended(z, context))


def serve_flops_span(cfg, first: int, last: int) -> float:
    """Prompt tokens at positions first..last-1, each attending its own
    prefix (inside the window, in window layers), and the head ONCE:
    only the last position's logits are needed."""
    z = dims(cfg)
    n = max(0, last - first)
    att = sum(_attended(z, p + 1) for p in range(first, last))
    return (2.0 * matmul_params_token(cfg) * n
            + 4.0 * z["H"] * z["d"] * att
            + (2.0 * z["V"] * z["h"] if n else 0.0))


def kv_read_bytes(cfg, context: int, kv_itemsize: int) -> float:
    """Bytes of cached keys and values one decoded token must read at
    context ``context``: 2 x Hkv x d x bytes a column, ``min(c,
    window)`` columns in a window layer, ``c`` in a global one."""
    z = dims(cfg)
    return _attended(z, context) * 2 * z["Hkv"] * z["d"] * kv_itemsize


# ------------------------------------------------- weights from a seed

def _round_bf16(x):
    """Round float32 to the values bfloat16 holds (an ``astype`` pair
    is a rounding the TPU compiler may leave out)."""
    return jax.lax.reduce_precision(x, 8, 7)


def layer_shapes(cfg, dense: bool) -> dict:
    """Leaf shapes of a layer with the dense FFN, or with experts."""
    z = dims(cfg)
    h, d = z["h"], z["d"]
    s = {"norm_in": (h,), "norm_post_attn": (h,), "norm_pre_mlp": (h,),
         "norm_post_mlp": (h,), "q_norm": (d,), "k_norm": (d,),
         "wq": (h, z["H"] * d), "wk": (h, z["Hkv"] * d),
         "wv": (h, z["Hkv"] * d), "wg": (h, z["H"] * d),
         "wo": (z["H"] * d, h)}
    if dense:
        s.update(w_gate=(h, z["F"]), w_up=(h, z["F"]), w_down=(z["F"], h))
    else:
        s.update(router=(h, z["Er"]), router_bias=(z["Er"],),
                 s_gate=(h, z["Fs"]), s_up=(h, z["Fs"]),
                 s_down=(z["Fs"], h),
                 e_gate=(z["E"], h, z["Fe"]), e_up=(z["E"], h, z["Fe"]),
                 e_down=(z["E"], z["Fe"], h))
    return s


def _draw(name, key, shape, std):
    """One leaf, float32 on bfloat16's grid. Matrices: normal(0, std).
    Norm weights: 1 + 0.1 normal, so that a norm in the wrong place
    shows. The router's bias: 0.02 normal - of the size of the gaps
    between the largest scores, so that it changes which experts win."""
    x = jax.random.normal(key, shape, jnp.float32)
    if "norm" in name:
        x = 1.0 + 0.1 * x
    elif name == "router_bias":
        x = 0.02 * x
    else:
        x = std * x
    return _round_bf16(x)


def init_layer(cfg, key, i, dense: bool) -> dict:
    """Layer ``i``'s leaves from ``key`` (traced inside a jitted call;
    ``i`` may be traced, ``dense`` says which kind of layer it is)."""
    shapes = layer_shapes(cfg, dense)
    std = dims(cfg)["std"]
    lk = jax.random.fold_in(key, i)
    return {n: _draw(n, jax.random.fold_in(lk, j), shapes[n], std)
            for j, n in enumerate(sorted(shapes))}


def init_ends(cfg, key) -> dict:
    """Embedding, final norm and head."""
    z = dims(cfg)
    k = jax.random.fold_in(key, 1_000_003)
    return {"embed": _draw("embed", jax.random.fold_in(k, 0),
                           (z["V"], z["h"]), z["std"]),
            "norm_f": _draw("norm_f", jax.random.fold_in(k, 1),
                            (z["h"],), z["std"]),
            "lm_head": _draw("lm_head", jax.random.fold_in(k, 2),
                             (z["h"], z["V"]), z["std"])}


def _cfg_key(cfg):
    """A hashable form of what shapes depend on."""
    z = dims(cfg)
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in z.items()))


_CFGS = {}


def _remember(cfg):
    key = _cfg_key(cfg)
    _CFGS[key] = cfg
    return key


@functools.lru_cache(maxsize=None)
def _layer_maker(cfg_key, dense, dtype_name):
    cfg, dtype = _CFGS[cfg_key], jnp.dtype(dtype_name)
    return jax.jit(lambda key, i: jax.tree.map(
        lambda a: a.astype(dtype), init_layer(cfg, key, i, dense)))


@functools.lru_cache(maxsize=None)
def _ends_maker(cfg_key, dtype_name):
    cfg, dtype = _CFGS[cfg_key], jnp.dtype(dtype_name)
    return jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(dtype), init_ends(cfg, key)))


def make_layer(cfg, seed, i, dtype="float32"):
    dense = i < dims(cfg)["dense"]
    return _layer_maker(_remember(cfg), dense, dtype)(seed_key(seed),
                                                      jnp.int32(i))


def make_ends(cfg, seed, dtype="float32"):
    return _ends_maker(_remember(cfg), dtype)(seed_key(seed))


# --------------------------------------------- the program under test

def build_program_model(cfg):
    """``PatternDecoderLM`` at the configuration's sizes, through the
    constructor a user calls."""
    from bigdl_tpu.models import PatternDecoderLM

    z = dims(cfg)
    pattern = [("window" if w else "global",
                "dense" if i < z["dense"] else "experts")
               for i, w in enumerate(z["window"])]
    return PatternDecoderLM(
        z["V"], hidden_size=z["h"], pattern=pattern, num_heads=z["H"],
        num_kv_heads=z["Hkv"], head_dim=z["d"], ffn_size=z["F"],
        window=z["W"], rope_theta=z["theta"], norm_eps=z["eps"],
        max_len=int(cfg["max_position_embeddings"]),
        expert_size=z["Fe"], shared_size=z["Fs"],
        router_experts=z["Er"], local_experts=(z["off"], z["E"]),
        top_k=z["k"], route_scale=z["scale"], route_norm=z["norm"],
        embed_scale=math.sqrt(z["h"]) if cfg.get("mup_enabled") else 1.0)


def program_layer(lp: dict) -> dict:
    """One reference-layout layer in ``PatternDecoderLM``'s layout."""
    blk = {n: {"weight": lp[n]} for n in
           ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")}
    blk["attn"] = {n: lp[n] for n in
                   ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm")}
    if "router" in lp:
        blk["mlp"] = {
            "router": lp["router"], "router_bias": lp["router_bias"],
            "w_gate": lp["e_gate"], "w_up": lp["e_up"],
            "w_down": lp["e_down"],
            "shared": {"w_gate": lp["s_gate"], "w_up": lp["s_up"],
                       "w_down": lp["s_down"]}}
    else:
        blk["mlp"] = {n: lp[n] for n in ("w_gate", "w_up", "w_down")}
    return blk


def make_program_params(cfg, seed, dtype="float32"):
    """Weights on the device in the type they are used in, laid out for
    the program; one jitted call a layer, so that no more than a
    layer's float32 draws are live beside what is kept."""
    ends = make_ends(cfg, seed, dtype)
    out = {"embed": ends["embed"], "lm_head": ends["lm_head"],
           "norm_f": {"weight": ends["norm_f"]}}
    for i in range(dims(cfg)["L"]):
        out[f"block_{i}"] = program_layer(make_layer(cfg, seed, i, dtype))
    return out


# ------------------------------------------------ the plain reference

def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _rope(x, positions, theta):
    """Rotary embedding, the half-split form of the family's modeling
    file: ``x cos + rotate_half(x) sin``. ``x`` is ``[S, heads, d]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]      # [S, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (x * cos + rot * sin).astype(x.dtype)


def _attention(cfg, mode, lp, u, window: bool, block_q: int):
    """One row ``u [S, h]`` (S a multiple of ``block_q``), blocked over
    queries so that no ``[H, S, S]`` scores are held."""
    z = dims(cfg)
    S = u.shape[0]
    H, Hkv, d = z["H"], z["Hkv"], z["d"]
    G = H // Hkv
    prec = None if mode == "bf16" else "highest"
    q = _mm(u, lp["wq"], mode).reshape(S, H, d)
    k = _mm(u, lp["wk"], mode).reshape(S, Hkv, d)
    v = _mm(u, lp["wv"], mode).reshape(S, Hkv, d)
    g = _mm(u, lp["wg"], mode)
    q = _rms(q, lp["q_norm"], z["eps"])
    k = _rms(k, lp["k_norm"], z["eps"])
    pos = jnp.arange(S)
    if window:
        q, k = _rope(q, pos, z["theta"]), _rope(k, pos, z["theta"])
    qb = q.reshape(S // block_q, block_q, Hkv, G, d)
    pb = pos.reshape(S // block_q, block_q)

    def block(args):
        qi, pi = args                               # [Bq,Hkv,G,d], [Bq]
        sc = jnp.einsum("qngd,knd->ngqk", qi, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
        ok = pos[None, :] <= pi[:, None]
        if window:
            ok = ok & (pi[:, None] - pos[None, :] < z["W"])
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return jnp.einsum("ngqk,knd->qngd", w, v, precision=prec)

    o = jax.lax.map(block, (qb, pb)).reshape(S, H * d)
    return _mm(o * jax.nn.sigmoid(g), lp["wo"], mode)


def _gated(u, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(u, wg, mode)) * _mm(u, wu, mode), wd, mode)


def route(cfg, lp, u, offset=None, held=None):
    """The router over its published width: the chosen experts' ids
    ``[S, k]``, their weights, and the margin (in ``s + b``) by which the
    part of the experts ``offset .. offset + held - 1`` is decided: the
    least by which an expert of those would have to move to enter the
    chosen set (the last chosen over the best of them left out) or to
    leave it (the least of them chosen over the first left out). +inf
    where neither can happen. Float32 at ``highest`` whatever the mode:
    the router is kept in float32 by the model."""
    z = dims(cfg)
    off = z["off"] if offset is None else offset
    E = z["E"] if held is None else held
    s = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32),
                                  lp["router"].astype(jnp.float32),
                                  precision="highest"))
    sb = s + lp["router_bias"].astype(jnp.float32)
    top, idx = jax.lax.top_k(sb, z["k"] + 1)
    w = jnp.take_along_axis(s, idx[:, :z["k"]], axis=-1)
    if z["norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    ids = jnp.arange(sb.shape[-1])
    here = (ids >= off) & (ids < off + E)
    chosen = jnp.any(idx[:, :z["k"], None] == ids, axis=1)      # [S, Er]
    last_in, first_out = top[:, z["k"] - 1], top[:, z["k"]]
    enter = last_in - jnp.max(jnp.where(here & ~chosen, sb, -jnp.inf), -1)
    leave = jnp.min(jnp.where(here & chosen, sb, jnp.inf), -1) - first_out
    return idx, w * z["scale"], jnp.minimum(enter, leave)


def moe_parts(cfg, mode, lp, u, offset=None):
    """``(shared(u), sum over the held experts chosen)`` for ``u
    [S, h]``, and ``route``'s margin ``[S]`` by which the held experts'
    part is decided. ``lp['e_*']`` hold the
    experts ``offset .. offset + E - 1`` of the router's numbering; every
    held expert runs over every token and the combine weights zero the
    rest - the plain form."""
    z = dims(cfg)
    off = z["off"] if offset is None else offset
    E = lp["e_gate"].shape[0]
    idx, w, margin = route(cfg, lp, u, off, E)
    local = idx[:, :z["k"]] - off                          # [S, k]
    comb = jnp.sum(jnp.where((local[..., None] == jnp.arange(E)),
                             w[..., None], 0.0), axis=1)   # [S, E]

    def one(acc, ew):
        eg, eu, ed, c = ew
        return acc + c[:, None].astype(u.dtype) * _gated(u, eg, eu, ed,
                                                         mode), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (lp["e_gate"], lp["e_up"], lp["e_down"], comb.T))
    shared = _gated(u, lp["s_gate"], lp["s_up"], lp["s_down"], mode)
    return shared, routed, margin


def ref_layer(cfg, window, lp, x, mode="f32", block_q=None):
    """One layer (a window layer or a global one; dense or experts by
    the leaves of ``lp``) over rows ``x [R, S, h]``; also the least
    routing margin ``[R, S]`` (+inf in a dense layer)."""
    z = dims(cfg)
    S = x.shape[1]
    bq = block_q or math.gcd(S, 512)

    def row(xr):
        u = _rms(xr, lp["norm_in"], z["eps"])
        a = xr + _rms(_attention(cfg, mode, lp, u, window, bq),
                      lp["norm_post_attn"], z["eps"])
        u = _rms(a, lp["norm_pre_mlp"], z["eps"])
        if "router" in lp:
            shared, routed, margin = moe_parts(cfg, mode, lp, u)
            m = shared + routed
        else:
            m = _gated(u, lp["w_gate"], lp["w_up"], lp["w_down"], mode)
            margin = jnp.full((S,), jnp.inf, jnp.float32)
        return a + _rms(m, lp["norm_post_mlp"], z["eps"]), margin

    return jax.lax.map(row, x)


def ref_embed(cfg, ends, tokens):
    z = dims(cfg)
    scale = math.sqrt(z["h"]) if cfg.get("mup_enabled") else 1.0
    return ends["embed"][tokens] * scale


def ref_head(cfg, ends, x, mode="f32"):
    """Logits ``[.., V]`` (float32) of hidden rows ``x [.., h]``."""
    z = dims(cfg)
    y = _rms(x, ends["norm_f"], z["eps"])
    return _mm(y, ends["lm_head"], mode).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_key, window, mode):
    cfg = _CFGS[cfg_key]
    return jax.jit(lambda lp, x: ref_layer(cfg, window, lp, x, mode))


def ref_hidden(cfg, seed, tokens, mode="f32"):
    """Final hidden rows ``[R, S, h]`` (before the last norm) and the
    least routing margin ``[R, S]``, one layer's weights at a time."""
    ck = _remember(cfg)
    ends = make_ends(cfg, seed)
    low = (lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t)) \
        if mode == "bf16" else (lambda t: t)
    x = low(ref_embed(cfg, ends, jnp.asarray(tokens, jnp.int32)))
    margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    for i in range(dims(cfg)["L"]):
        lp = low(make_layer(cfg, seed, i))
        x, m = _layer_fn(ck, dims(cfg)["window"][i], mode)(lp, x)
        margin = jnp.minimum(margin, m)
        del lp
    return x, margin


def ref_forward(cfg, seed, tokens, mode="f32"):
    """Logits ``[R, S, V]``: for the tests' small sizes."""
    x, _ = ref_hidden(cfg, seed, tokens, mode)
    ends = make_ends(cfg, seed)
    if mode == "bf16":
        ends = jax.tree.map(lambda a: a.astype(jnp.bfloat16), ends)
    return ref_head(cfg, ends, x, mode)


def _margin_report(rows, gaps, margin) -> str:
    """By hand, for setting ``route_tie_margin``: the largest gap that
    each margin would keep, and the eight largest gaps with their
    margin, their request and how far into its served tokens they fall -
    a gap that sits on a small margin is a token sent to another expert,
    not a loss of precision."""
    at = [(r, j, len(o)) for r, (p, o) in enumerate(rows)
          for j in range(len(o))]
    g = np.concatenate([np.asarray(gaps[r, len(p) - 1:len(p) + len(o) - 1])
                        for r, (p, o) in enumerate(rows)])
    m = np.concatenate([margin[r, len(p) - 1:len(p) + len(o) - 1]
                        for r, (p, o) in enumerate(rows)])
    sweep = {t: (int((m >= t).sum()),
                 round(float(g[m >= t].max(initial=0.0)), 4))
             for t in (0.0, 0.001, 0.002, 0.003, 0.005, 0.01)}
    worst = [(round(float(g[i]), 4), round(float(m[i]), 4)) + at[i]
             for i in np.argsort(-g)[:8]]
    return (f"margin -> (kept, largest gap): {sweep}; eight largest gaps "
            f"(gap, margin, request, token, of): {worst}; median margin: "
            f"{float(np.median(m)) if m.size else None}")


def ref_token_gaps(cfg, seed, rows, mode="f32"):
    """For the served-model comparison (``gpt2.ref_token_gaps`` has the
    contract): one reference forward over each ``prompt + served``, the
    gap ``best - logit[token]`` at every served position, for the served
    tokens and for the tokens a ``mode``-precision forward puts first.

    **The gaps are cut off at the ``reference.gap_quantile``** (0.99 in
    the cell) **of all kept positions**, so what the driver reads as
    ``gap_max`` is the 99th percentile of the gaps over the checked
    requests' kept positions. A bfloat16 forward differs from the
    float32 one by about two hundredths of a logit at most positions,
    and by tenths at the few where rounding sends a token to another
    expert than the reference's, beyond the margin below (margins up to
    0.005 seen on the chip; the reference's own bfloat16 control reads
    the same, PERF.md section 2): the largest single gap measures how
    often that happens to fall in a run, a high quantile measures the
    precision - a control in int8 or float8 moves every position and
    fails it by an order of magnitude. The positions are pooled, not
    taken a request at a time: the first requests of a run are cut
    short by the harness (48-81 kept positions in a traced run), where
    one flip is more than a hundredth of the request.

    **Positions set aside.** Where a held expert lies within
    ``reference.route_tie_margin`` of entering or of leaving the chosen
    set (``route``'s margin, in the REFERENCE's own float32 ``s + b``:
    the last chosen over the best held expert left out, the least held
    expert chosen over the first left out - not only the last chosen
    against the first left out: with both of those held elsewhere the
    third in line, held here, enters as easily), rounding decides
    whether this chip computes that expert for the token, and the logits
    there say nothing of the precision. Those
    positions are left out of both lists, by the reference's margin
    alone - nothing of the program's output is looked at. Their share is
    written to standard error, and if it passes
    ``reference.set_aside_share_limit`` every gap comes back NaN, so the
    run cannot be ``correct`` (the driver's list of checks is fixed:
    this is how the share is held to its limit)."""
    z = dims(cfg)
    rule = cfg.get("reference", {})
    tie, share_limit = (float(rule.get("route_tie_margin", 0.0)),
                        float(rule.get("set_aside_share_limit", 1.0)))
    quantile = float(rule.get("gap_quantile", 1.0))
    longest = max(len(p) + len(o) for p, o in rows)
    step = 1024 if longest > 1024 else 64
    width = -(-longest // step) * step
    R = len(rows)
    toks = np.zeros((R, width), np.int32)
    nxt = np.zeros((R, width), np.int32)
    for r, (p, o) in enumerate(rows):
        full = np.concatenate([p, o]).astype(np.int32)
        toks[r, :len(full)] = full
        nxt[r, :len(full) - 1] = full[1:]       # position j predicts j + 1
    x_ref, margin = ref_hidden(cfg, seed, toks, "f32")
    x_low = x_ref if mode == "f32" else ref_hidden(cfg, seed, toks, mode)[0]
    ends = make_ends(cfg, seed)

    @jax.jit
    def gaps(ends, x_ref, x_low, nxt):
        low_ends = jax.tree.map(lambda a: a.astype(jnp.bfloat16), ends) \
            if mode == "bf16" else ends

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
    margin = np.asarray(jax.device_get(margin))
    out_served, out_low, kept, seen = [], [], 0, 0
    for r, (p, o) in enumerate(rows):
        lo, hi = len(p) - 1, len(p) + len(o) - 1
        keep = margin[r, lo:hi] >= tie
        seen, kept = seen + (hi - lo), kept + int(keep.sum())
        out_served.append(np.asarray(gs[r, lo:hi], np.float64)[keep])
        out_low.append(None if mode == "f32"
                       else np.asarray(gl[r, lo:hi], np.float64)[keep])
    raw = max((float(g.max()) for g in out_served if len(g)), default=0.0)
    by_request = max((float(np.quantile(g, quantile)) for g in out_served
                      if len(g)), default=0.0)
    if quantile < 1.0:
        # every list is cut off at the quantile of ALL kept positions

        def cut(lists):
            pool = [g for g in lists if g is not None and len(g)]
            if not pool:
                return lists
            q = np.quantile(np.concatenate(pool), quantile)
            return [g if g is None else np.minimum(g, q) for g in lists]
        out_served, out_low = cut(out_served), cut(out_low)
    share = 1.0 - kept / max(seen, 1)
    if mode == "f32":
        cut_max = max((float(g.max()) for g in out_served if len(g)),
                      default=0.0)
        print(f"afmoe reference: largest gap kept {raw:.4f}, the "
              f"{quantile:g}-quantile of the kept {cut_max:.4f} (largest "
              f"of the requests' own: {by_request:.4f}; kept a request: "
              f"{[len(g) for g in out_served]}); "
              + _margin_report(rows, gs, margin), file=sys.stderr)
    print(f"afmoe reference ({mode}): set aside {seen - kept} of {seen} "
          f"served positions ({100 * share:.2f}%, limit "
          f"{100 * share_limit:.0f}%) for a routing margin under {tie}",
          file=sys.stderr)
    if share > share_limit or kept == 0:
        nan = lambda a: None if a is None else np.full(
            max(len(a), 1), np.nan)
        out_served = [nan(a) for a in out_served]
        out_low = [nan(a) for a in out_low]
    return out_served, out_low
