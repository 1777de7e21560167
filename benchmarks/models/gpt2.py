"""GPT-2 family: the program's model built through its public API, the
plain reference, and the required operations and bytes.

Everything the benchmark needs to know about one model family lives
here, so a later family is a new file. Three parts:

- ``build_program_model`` / ``program_params``: the system under test
  (``bigdl_tpu.models.TransformerLM``) and the layout its parameters
  take. The only place that imports the program.
- ``init_stacked`` / ``ref_*``: seeded weights made on the device in
  one jitted call, and the plain float32 reference (forward, loss,
  gradients, Adam) in straightforward ``jax.numpy``. Imports nothing of
  the program and takes nothing the program made.
- ``*_flops_*`` / ``kv_read_bytes``: what the algorithm requires, from
  shapes alone.

Required operations (say so beside every number that uses them): per
token the matrix products cost ``2 x (12 L h^2 + V h)`` multiply-adds
forward (QKV, output, two FFN products, and the tied output head) and
three times that with the backward pass; attention's two products cost
``4 L h c`` forward at context ``c`` — counted WITHOUT the causal
saving, so a kernel that skips the masked half can pass 100% of this
count only if it also beats the dense peak. Embedding gathers, layer
norms, soft-max and GELU are not counted.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------- sizes

def dims(cfg) -> dict:
    h = int(cfg["n_embd"])
    return {"L": int(cfg["n_layer"]), "h": h, "H": int(cfg["n_head"]),
            "d": h // int(cfg["n_head"]),
            "F": int(cfg.get("n_inner") or 4 * h),
            "V": int(cfg["vocab_size"]), "P": int(cfg["n_positions"])}


def matmul_params(cfg) -> int:
    """Weights that take part in a matrix product per token: 12 L h^2
    (for F = 4h; in general 4 h^2 + 2 h F a layer) plus the tied head."""
    z = dims(cfg)
    return z["L"] * (4 * z["h"] ** 2 + 2 * z["h"] * z["F"]) \
        + z["V"] * z["h"]


def param_count(cfg) -> int:
    z = dims(cfg)
    per_layer = 4 * z["h"] ** 2 + 4 * z["h"] + 2 * z["h"] * z["F"] \
        + z["F"] + z["h"] + 4 * z["h"]
    return z["V"] * z["h"] + z["P"] * z["h"] + z["L"] * per_layer \
        + 2 * z["h"]


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward, attention without the causal saving."""
    z = dims(cfg)
    return 6.0 * matmul_params(cfg) + 12.0 * z["L"] * z["h"] * seq


def train_flops_per_sample(cfg, seq: int) -> float:
    return train_flops_per_token(cfg, seq) * seq


def serve_flops_per_token(cfg, context: int) -> float:
    """One token processed forward at context length ``context`` (a
    prompt token at position p has context p + 1)."""
    z = dims(cfg)
    return 2.0 * matmul_params(cfg) + 4.0 * z["L"] * z["h"] * context


def serve_flops_span(cfg, first: int, last: int) -> float:
    """Tokens at positions first..last-1 (0-based), each attending its
    own prefix: sum of ``serve_flops_per_token(p + 1)``."""
    z = dims(cfg)
    n = max(0, last - first)
    ctx_sum = (last * (last + 1) - first * (first + 1)) / 2.0
    return 2.0 * matmul_params(cfg) * n + 4.0 * z["L"] * z["h"] * ctx_sum


def kv_read_bytes(cfg, context: int, kv_itemsize: int) -> float:
    """Bytes of cached keys and values one decoded token must read at
    context ``context``: c x 2 x L x h x bytes(kv dtype)."""
    z = dims(cfg)
    return float(context) * 2 * z["L"] * z["h"] * kv_itemsize


# ------------------------------------------------- weights from a seed

def seed_key(seed: int):
    """Seeds run past 2**31; fold the high bits in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_stacked(cfg, key, dtype=jnp.float32):
    """GPT-2's published initialisation (normal, ``initializer_range``;
    residual projections scaled by 1/sqrt(2 L); zero biases; unit layer
    norms), the per-layer leaves stacked on a leading ``[L]`` axis.
    Traced inside one jitted call by the two functions below."""
    z = dims(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    L, h, F = z["L"], z["h"], z["F"]
    ks = jax.random.split(key, 8)

    def normal(k, shape, scale=1.0):
        return (jax.random.normal(k, shape, jnp.float32)
                * (std * scale)).astype(dtype)

    res = 1.0 / math.sqrt(2.0 * L)
    zeros = lambda *s: jnp.zeros(s, dtype)
    ones = lambda *s: jnp.ones(s, dtype)
    layers = {
        "ln1_w": ones(L, h), "ln1_b": zeros(L, h),
        "wq": normal(ks[0], (L, h, h)), "bq": zeros(L, h),
        "wk": normal(ks[1], (L, h, h)), "bk": zeros(L, h),
        "wv": normal(ks[2], (L, h, h)), "bv": zeros(L, h),
        "wo": normal(ks[3], (L, h, h), res), "bo": zeros(L, h),
        "ln2_w": ones(L, h), "ln2_b": zeros(L, h),
        "w_up": normal(ks[4], (L, h, F)), "b_up": zeros(L, F),
        "w_down": normal(ks[5], (L, F, h), res), "b_down": zeros(L, h),
    }
    return {"wte": normal(ks[6], (z["V"], h)),
            "wpe": normal(ks[7], (z["P"], h)),
            "lnf_w": ones(h), "lnf_b": zeros(h), "layers": layers}


_PROGRAM_LEAVES = {  # stacked leaf -> path inside TransformerLM block
    "ln1_w": ("ln1", "weight"), "ln1_b": ("ln1", "bias"),
    "wq": ("attn", "wq"), "bq": ("attn", "bq"),
    "wk": ("attn", "wk"), "bk": ("attn", "bk"),
    "wv": ("attn", "wv"), "bv": ("attn", "bv"),
    "wo": ("attn", "wo"), "bo": ("attn", "bo"),
    "ln2_w": ("ln2", "weight"), "ln2_b": ("ln2", "bias"),
    "w_up": ("mlp", "w_up"), "b_up": ("mlp", "b_up"),
    "w_down": ("mlp", "w_down"), "b_down": ("mlp", "b_down"),
}


def program_params(stacked):
    """The same numbers in ``TransformerLM``'s parameter layout."""
    L = stacked["layers"]["wq"].shape[0]
    out = {"embed": stacked["wte"], "pos_embed": stacked["wpe"],
           "ln_f": {"weight": stacked["lnf_w"], "bias": stacked["lnf_b"]}}
    for i in range(L):
        blk = {"ln1": {}, "attn": {}, "ln2": {}, "mlp": {}}
        for name, (mod, leaf) in _PROGRAM_LEAVES.items():
            blk[mod][leaf] = stacked["layers"][name][i]
        out[f"block_{i}"] = blk
    return out


def flatten_program(tree) -> dict:
    """Program-layout tree -> {flat name: leaf}."""
    out = {"embed": tree["embed"], "pos_embed": tree["pos_embed"],
           "ln_f/weight": tree["ln_f"]["weight"],
           "ln_f/bias": tree["ln_f"]["bias"]}
    i = 0
    while f"block_{i}" in tree:
        for m, l in _PROGRAM_LEAVES.values():
            out[f"block_{i}/{m}/{l}"] = tree[f"block_{i}"][m][l]
        i += 1
    return out


def stacked_norms(tree) -> dict:
    """Per-leaf L2 norms of a stacked-layout tree under the program's
    flat names (a stacked leaf gives one norm per layer)."""
    f32 = lambda a: a.astype(jnp.float32)
    out = {"embed": jnp.linalg.norm(f32(tree["wte"])),
           "pos_embed": jnp.linalg.norm(f32(tree["wpe"])),
           "ln_f/weight": jnp.linalg.norm(f32(tree["lnf_w"])),
           "ln_f/bias": jnp.linalg.norm(f32(tree["lnf_b"]))}
    for name, (m, l) in _PROGRAM_LEAVES.items():
        a = f32(tree["layers"][name])
        per = jnp.sqrt(jnp.sum(a.reshape(a.shape[0], -1) ** 2, axis=1))
        for i in range(a.shape[0]):
            out[f"block_{i}/{m}/{l}"] = per[i]
    return out


@functools.lru_cache(maxsize=None)
def _make_fn(cfg_items, layout, dtype_name):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(dtype_name)

    def make(key):
        st = init_stacked(cfg, key, dtype)
        return program_params(st) if layout == "program" else st
    return jax.jit(make)


def _cfg_items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


def make_program_params(cfg, seed, dtype="float32"):
    """Weights on the device, one jitted call, the type they are used
    in, laid out for the program."""
    return _make_fn(_cfg_items(cfg), "program", dtype)(seed_key(seed))


def make_stacked_params(cfg, seed, dtype="float32"):
    return _make_fn(_cfg_items(cfg), "stacked", dtype)(seed_key(seed))


def param_change_norms(cfg, seed, program_tree) -> dict:
    """||p - p0|| per leaf for a program-layout tree against the seed's
    initial weights, computed on the device, returned as floats."""
    @jax.jit
    def norms(p0, p):
        return jax.tree.map(
            lambda a, b: jnp.linalg.norm(
                (b.astype(jnp.float32) - a.astype(jnp.float32)).ravel()),
            p0, p)
    p0 = make_program_params(cfg, seed)
    out = jax.device_get(norms(p0, program_tree))
    return {k: float(v) for k, v in flatten_program(out).items()}


# --------------------------------------------- the program under test

def build_program_model(cfg):
    """``TransformerLM`` at the configuration's sizes, through the
    constructor a user calls."""
    from bigdl_tpu.models import TransformerLM

    z = dims(cfg)
    return TransformerLM(z["V"], hidden_size=z["h"], num_layers=z["L"],
                         num_heads=z["H"], ffn_size=z["F"],
                         max_len=z["P"],
                         tie_embeddings=bool(cfg.get(
                             "tie_word_embeddings", True)))


# ------------------------------------------------ the plain reference

def _quant_int8(x, axis):
    """Symmetric max-abs int8 fake-quantisation along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _quant_float8(exponent_bits, mantissa_bits):
    """Max-abs scaled float8 fake-quantisation along ``axis``, rounded
    by ``lax.reduce_precision``: a pair of ``astype`` there and back is
    a rounding the TPU compiler may leave out (it did, in most places,
    for bfloat16 there and back; PERF.md section 2)."""
    bias = 2 ** (exponent_bits - 1) - 1
    top = (2.0 - 2.0 ** -mantissa_bits) * 2.0 ** bias    # largest finite

    def quant(x, axis):
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
        s = jnp.where(s == 0, 1.0, s)
        return jax.lax.reduce_precision(x / s, exponent_bits,
                                        mantissa_bits) * s
    return quant


def _low_mm(quant, quant_grad):
    """``x [..., a] @ w [a, b]`` as a low-precision recipe computes it:
    both operands of the forward product quantised by ``quant``, and in
    the backward pass the incoming gradient quantised by ``quant_grad``
    before its two products with the (quantised) saved operands. Sums
    are float32, as the chip accumulates."""
    @jax.custom_vjp
    def mm(x, w):
        return jnp.matmul(quant(x, -1), quant(w, 0), precision="highest")

    def fwd(x, w):
        xq, wq = quant(x, -1), quant(w, 0)
        return jnp.matmul(xq, wq, precision="highest"), (xq, wq)

    def bwd(saved, g):
        xq, wq = saved
        gq = quant_grad(g, -1)
        dx = jnp.matmul(gq, wq.T, precision="highest")
        dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                        gq.reshape(-1, gq.shape[-1]), precision="highest")
        return dx, dw
    mm.defvjp(fwd, bwd)
    return mm


def _keep(x, axis):
    return x


# the controls' matrix products: int8 operands in the forward product
# and gradients left as they come (quantising them row-wise to int8
# zeroes every soft-max gradient but the target's: an artefact, not a
# precision; PERF.md section 2); float8 by the usual recipe, e4m3
# forward and e5m2 for gradients
_LOW_MM = {
    "int8": _low_mm(_quant_int8, _keep),
    "fp8": _low_mm(_quant_float8(4, 3), _quant_float8(5, 2)),
}


def _mm(x, w, mode):
    """x [..., a] @ w [a, b] at the reference's or a control's
    precision."""
    if mode in _LOW_MM:
        return _LOW_MM[mode](x, w)
    if mode == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16),
                          w.astype(jnp.bfloat16)).astype(jnp.bfloat16)
    return jnp.matmul(x, w, precision="highest")


def _ln(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    return ((x32 - mu) / jnp.sqrt(var + eps)).astype(x.dtype) * w + b


def _block(cfg, mode, x, lp):
    z = dims(cfg)
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    b, s, h = x.shape
    H, d = z["H"], z["d"]
    prec = None if mode == "bf16" else "highest"
    y = _ln(x, lp["ln1_w"], lp["ln1_b"], eps)
    split = lambda t: t.reshape(b, s, H, d).transpose(0, 2, 1, 3)
    q = split(_mm(y, lp["wq"], mode) + lp["bq"])
    k = split(_mm(y, lp["wk"], mode) + lp["bk"])
    v = split(_mm(y, lp["wv"], mode) + lp["bv"])
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec,
                    preferred_element_type=jnp.float32) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask, sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    a = jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=prec)
    a = a.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + _mm(a, lp["wo"], mode) + lp["bo"]
    y = _ln(x, lp["ln2_w"], lp["ln2_b"], eps)
    y = jax.nn.gelu(_mm(y, lp["w_up"], mode) + lp["b_up"],
                    approximate=True)   # gelu_new
    return x + _mm(y, lp["w_down"], mode) + lp["b_down"]


def ref_forward(cfg, sp, tokens, mode="f32"):
    """Logits ``[B, S, V]`` of the stacked-layout weights ``sp`` over
    int tokens ``[B, S]``. ``mode``: ``"f32"`` is the reference
    (float32, matrix products at ``highest``); ``"bf16"``, ``"int8"`` and
    ``"fp8"`` are the controls."""
    s = tokens.shape[1]
    x = sp["wte"][tokens] + sp["wpe"][:s][None]
    blk = jax.checkpoint(functools.partial(_block, cfg, mode))
    x, _ = jax.lax.scan(lambda c, lp: (blk(c, lp), None), x,
                        sp["layers"])
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    x = _ln(x, sp["lnf_w"], sp["lnf_b"], eps)
    if mode in _LOW_MM:
        return _mm(x, sp["wte"].T, mode)
    if mode == "bf16":
        return jnp.matmul(x, sp["wte"].T).astype(jnp.float32)
    return jnp.matmul(x, sp["wte"].T, precision="highest")


def ref_loss(cfg, sp, tokens, targets, mode="f32"):
    logits = ref_forward(cfg, sp, tokens, mode).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def ref_train(cfg, seed, batches, lr, *, mode="f32", beta1=0.9,
              beta2=0.999, eps=1e-8, fault=None):
    """Follow ``len(batches)`` Adam steps from the seed's weights.
    ``batches`` is int ``[steps, B, S + 1]``. Returns the per-step
    losses, the per-leaf norms of the first gradient and of the total
    parameter change, as floats under the program's flat names.

    ``mode`` is the precision of the matrix products (``ref_forward``),
    or ``"bf16_masters"``: float32 products, but the weights themselves
    kept in bfloat16 between steps, no float32 master copy.

    ``fault`` plants one of the faults the comparison must catch:
    ``"half_batch"`` (the second half of every batch left out, the mean
    taken over the rest) or ``"frozen"`` (a step that returns its state
    unchanged)."""
    steps = batches.shape[0]
    if mode == "bf16_masters":
        mode = "f32"
        store = lambda t: jax.tree.map(
            lambda a: jax.lax.reduce_precision(a, 8, 7), t)
    else:
        store = lambda t: t

    def step(carry, xs):
        p, m, v = carry
        toks, t = xs
        if fault == "half_batch":
            toks = toks[: max(1, toks.shape[0] // 2)]
        loss, g = jax.value_and_grad(
            lambda q: ref_loss(cfg, q, toks[:, :-1], toks[:, 1:], mode))(p)
        m2 = jax.tree.map(lambda a, b: beta1 * a + (1 - beta1) * b, m, g)
        v2 = jax.tree.map(lambda a, b: beta2 * a + (1 - beta2) * b * b,
                          v, g)
        tf = t.astype(jnp.float32)
        mc = 1.0 / (1.0 - beta1 ** tf)
        vc = 1.0 / (1.0 - beta2 ** tf)
        p2 = jax.tree.map(
            lambda a, mm, vv: a - lr * (mm * mc) / (jnp.sqrt(vv * vc) + eps),
            p, m2, v2)
        p2 = store(p2)
        if fault == "frozen":
            p2, m2, v2 = p, m, v
        return (p2, m2, v2), (loss, g)

    @jax.jit
    def run(key, batches):
        p0 = init_stacked(cfg, key)
        zeros = jax.tree.map(jnp.zeros_like, p0)
        carry = (store(p0), zeros, zeros)
        losses, g1 = [], None
        # a python loop, not a scan: the gradient of step 1 is kept
        # and the others dropped without stacking 'steps' copies
        for i in range(steps):
            carry, (loss, g) = step(carry, (batches[i], jnp.int32(i + 1)))
            losses.append(loss)
            if i == 0:
                g1 = stacked_norms(g)
        change = stacked_norms(jax.tree.map(lambda a, b: a - b,
                                            carry[0], p0))
        return jnp.stack(losses), g1, change

    losses, g1, change = jax.device_get(
        run(seed_key(seed), jnp.asarray(batches, jnp.int32)))
    return ([float(x) for x in losses],
            {k: float(v) for k, v in g1.items()},
            {k: float(v) for k, v in change.items()})


def ref_token_gaps(cfg, seed, rows, mode="f32", block_rows=4):
    """For the served-model comparison. ``rows`` is a list of
    ``(prompt, served)`` int arrays. One reference forward over each
    ``prompt + served``; returns, per row, the reference's logits gap
    ``best - logit[token]`` at every served position for (a) the served
    tokens and (b) the tokens a ``mode``-precision forward of the same
    inputs puts first (the control; ``None`` when ``mode == "f32"``).
    Rows run in blocks padded to the block's longest (causal, so
    right-padding changes nothing to its left)."""
    sp = make_stacked_params(cfg, seed)

    @functools.partial(jax.jit, static_argnames=("m",))
    def gaps(sp, toks, served_next, m):
        ref = ref_forward(cfg, sp, toks, "f32")
        best = jnp.max(ref, axis=-1)
        g_served = best - jnp.take_along_axis(
            ref, served_next[..., None], axis=-1)[..., 0]
        if m == "f32":
            return g_served, g_served
        low_sp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), sp) \
            if m == "bf16" else sp
        low = ref_forward(cfg, low_sp, toks, m)
        pick = jnp.argmax(low, axis=-1)
        g_low = best - jnp.take_along_axis(
            ref, pick[..., None], axis=-1)[..., 0]
        return g_served, g_low

    out_served, out_low = [], []
    for i in range(0, len(rows), block_rows):
        blk = rows[i:i + block_rows]
        # one shape, so one program: every block is as wide as the
        # learned positions reach
        width = dims(cfg)["P"]
        toks = np.zeros((block_rows, width), np.int32)
        nxt = np.zeros((block_rows, width), np.int32)
        for r, (p, o) in enumerate(blk):
            full = np.concatenate([p, o]).astype(np.int32)
            toks[r, :len(full)] = full
            # position j predicts token j + 1
            nxt[r, :len(full) - 1] = full[1:]
        gs, gl = jax.device_get(gaps(sp, toks, nxt, mode))
        for r, (p, o) in enumerate(blk):
            lo, hi = len(p) - 1, len(p) + len(o) - 1
            out_served.append(np.asarray(gs[r, lo:hi], np.float64))
            out_low.append(None if mode == "f32"
                           else np.asarray(gl[r, lo:hi], np.float64))
    return out_served, out_low
