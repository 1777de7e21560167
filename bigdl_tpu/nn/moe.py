"""Mixture-of-Experts with expert parallelism (net-new vs the reference —
its closest machinery is MixtureTable, nn/MixtureTable.scala, which blends
pre-computed expert outputs locally; this layer adds the full top-k routed
MoE with the expert dim shardable over a mesh axis).

Design (TPU-first): experts are ONE stacked weight tensor [E, ...]. The
layer is told which experts it holds (``expert_offset``, ``num_experts``
of the router's ``router_experts``): it routes every token over ALL of
the router's experts, keeps the token-expert pairs that fall on its own,
sorts them by expert, lays every expert's run out on whole row tiles and
computes the runs as grouped matrix products
(:func:`bigdl_tpu.kernels.grouped_matmul`) — dropless, static shapes
(the row count is the worst case, every pair local), and no expert is
computed for a token that was not routed to it. What experts held
elsewhere would add is left out: on one device of an expert-parallel
group this IS the layer's local part, the exchange that brings other
devices' tokens is the caller's. With every expert held (the default)
it is the whole layer. Sharding the E dim over a mesh axis under GSPMD
still works: XLA inserts the collectives the gathers need.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import AUX_LOSS_KEY, Module
from bigdl_tpu.utils.engine import Engine

#: state leaf ``float32[3]``: experts touched (of those held), local
#: token-expert pairs, the most pairs on one expert — of the last call.
#: The decode engine returns it with the logits (docs/telemetry.md)
MOE_STATS_KEY = "moe_stats"

_ACTIVATIONS = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
                "silu": jax.nn.silu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def gated_ffn(params, x, activation: str = "silu"):
    """``(act(x Wgate) * (x Wup)) Wdown``, no biases: the dense FFN of
    a gated decoder and an expert layer's shared expert. Without a
    ``w_gate`` leaf the plain form ``act(x Wup) Wdown``."""
    act = _ACTIVATIONS[activation]
    up = x @ params["w_up"]
    hid = act(x @ params["w_gate"]) * up if "w_gate" in params else act(up)
    return hid @ params["w_down"]


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def dispatch_tile(pairs: int, router_experts: int, dtype) -> int:
    """Rows of one dispatch tile: about the pairs an expert expects
    (``pairs / router_experts``), at least one vector tile of ``dtype``
    and at most 128 — a decode step's few pairs pad to 8 or 16 rows an
    expert, a wide prefill fills the MXU's rows."""
    from bigdl_tpu.kernels.common import sublanes

    return min(128, max(sublanes(dtype),
                        _pow2_at_least(pairs // max(router_experts, 1))))


def dispatch_plan(idx, offset: int, held: int, tile_m: int):
    """Where every token-expert pair goes. ``idx [T, k]`` are the
    router's expert ids; pairs on ``offset .. offset + held - 1`` are
    sorted by expert and every expert's run starts on a tile boundary.

    Returns ``row_token [M]`` (the token each dispatch row reads; rows
    that hold no pair read token 0 and are never combined), ``pair_row
    [T, k]`` (the row of each pair; ``M`` = none, for a pair on an
    expert held elsewhere), ``tile_expert [M / tile_m]``, ``num_tiles
    [1]`` and ``counts [held]``. ``M = (ceil(T k / tile_m) + held)
    tile_m`` covers every pair landing here."""
    t, k = idx.shape
    pairs = t * k
    m = (-(-pairs // tile_m) + held) * tile_m
    local = idx.reshape(pairs).astype(jnp.int32) - offset
    eid = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(eid, stable=True)
    sorted_eid = eid[order]
    counts = jnp.bincount(eid, length=held + 1)[:held].astype(jnp.int32)
    tiles = (counts + tile_m - 1) // tile_m
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile_m          # of each expert's run
    run_start = jnp.cumsum(counts) - counts          # in the sorted pairs
    e = jnp.minimum(sorted_eid, held - 1)
    row = row_start[e] + jnp.arange(pairs, dtype=jnp.int32) - run_start[e]
    row = jnp.where(sorted_eid < held, row, m)       # m: dropped
    row_token = jnp.zeros((m,), jnp.int32).at[row].set(
        (order // k).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((pairs,), jnp.int32).at[order].set(row)
    num_tiles = tile_end[-1:]
    tile = jnp.arange(m // tile_m, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, tile, side="right"), held - 1)
    # a dead tile names the last live tile's expert: its weight block
    # index then stands still and nothing is fetched for it
    last = tile_expert[jnp.maximum(num_tiles[0] - 1, 0)]
    tile_expert = jnp.where(tile < num_tiles[0], tile_expert, last)
    return (row_token, pair_row.reshape(t, k),
            tile_expert.astype(jnp.int32), num_tiles.astype(jnp.int32),
            counts)


def routed_experts(params, x, idx, weights, *, offset: int = 0,
                   router_experts: int = None, activation="gelu"):
    """``sum_j weights[t, j] expert_{idx[t, j]}(x[t])`` over the pairs
    whose expert is held here (``params['w_up']`` is ``[held, h, f]``;
    with ``params['w_gate']`` the expert is the gated form ``(act(x
    Wgate) * (x Wup)) Wdown``, else ``act(x Wup) Wdown``). ``x [T, h]``,
    ``idx`` / ``weights`` ``[T, k]``. Returns ``(out [T, h], stats)``,
    ``stats`` the ``MOE_STATS_KEY`` triple."""
    from bigdl_tpu import kernels

    held = params["w_up"].shape[0]
    t, k = idx.shape
    act = _ACTIVATIONS[activation]
    tile_m = dispatch_tile(t * k, router_experts or held, x.dtype)
    with jax.named_scope("moe/dispatch"):
        row_token, pair_row, tile_expert, num_tiles, counts = \
            dispatch_plan(idx, offset, held, tile_m)
        xs = x[row_token]
    with jax.named_scope("moe/experts"):
        gmm = lambda a, w: kernels.grouped_matmul(
            a, w, tile_expert, num_tiles, tile_m=tile_m)
        hid = gmm(xs, params["w_up"])
        if "w_gate" in params:
            hid = act(gmm(xs, params["w_gate"])) * hid
        else:
            hid = act(hid)
        ys = gmm(hid, params["w_down"])
    with jax.named_scope("moe/combine"):
        # a pair held elsewhere reads past the end: filled with zero
        per_pair = ys.at[pair_row].get(mode="fill", fill_value=0)
        out = jnp.einsum("tkh,tk->th", per_pair,
                         weights.astype(per_pair.dtype))
    stats = jnp.stack([jnp.sum(counts > 0), jnp.sum(counts),
                       jnp.max(counts)]).astype(jnp.float32)
    return out.astype(x.dtype), stats


class MoE(Module):
    """Top-k routed mixture of expert FFNs over [B, S, E_model] input.

    ``num_experts`` experts are held here, the ids ``expert_offset ..``
    of the router's ``router_experts`` (default: all of them). Two
    routers, set by the caller: ``scoring="softmax"`` (probabilities
    over the router's width; the chosen ones renormalised) and
    ``scoring="sigmoid"`` (independent scores in float32; chosen by
    ``score + router_bias``, weighted by the score alone, normalised
    when ``route_norm``, times ``route_scale``). ``gated`` experts are
    ``(act(x Wgate) * (x Wup)) Wdown``, the others ``act(x Wup) Wdown``;
    ``shared_size`` adds one FFN of that width, of the experts' form,
    that every token passes through. ``latent_size``: the routed experts
    live in a narrower space between two shared projections, ``(sum_j
    w_j expert_j(x W_lat_in)) W_lat_out`` with experts ``[latent, f]`` /
    ``[f, latent]``; the router and the shared expert read ``x`` itself.

    The load-balancing loss (Switch-style) is stored in the state pytree
    under the reserved ``AUX_LOSS_KEY`` leaf so the training loop adds
    ``aux_loss_weight * state[AUX_LOSS_KEY]`` to the objective.
    """

    def __init__(self, hidden_size: int, ffn_size: int, num_experts: int,
                 top_k: int = 2, activation: str = "gelu", *,
                 gated: bool = False, scoring: str = "softmax",
                 router_experts: int = None, expert_offset: int = 0,
                 router_bias: bool = False, route_norm: bool = True,
                 route_scale: float = 1.0, shared_size: int = 0,
                 latent_size: int = 0):
        super().__init__()
        if activation not in _ACTIVATIONS:
            raise ValueError(f"activation {activation!r}: one of "
                             f"{sorted(_ACTIVATIONS)}")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring must be softmax|sigmoid, "
                             f"got {scoring}")
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size
        self.num_experts = num_experts
        self.router_experts = router_experts or num_experts
        self.expert_offset = expert_offset
        if not 0 <= expert_offset <= self.router_experts - num_experts:
            raise ValueError(
                f"experts {expert_offset}..{expert_offset + num_experts - 1}"
                f" lie outside the router's {self.router_experts}")
        self.top_k = min(top_k, self.router_experts)
        self.activation = activation
        self.gated = gated
        self.scoring = scoring
        self.router_bias = router_bias
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.shared_size = shared_size
        self.latent_size = latent_size

    def init(self, rng):
        dtype = Engine.default_dtype()
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        s_in = 1.0 / math.sqrt(self.hidden_size)
        s_ffn = 1.0 / math.sqrt(self.ffn_size)
        e, h, f = self.num_experts, self.hidden_size, self.ffn_size
        # the width the experts read and write
        w = self.latent_size or h
        s_w = 1.0 / math.sqrt(w)
        p = {
            "router": jax.random.uniform(
                k1, (h, self.router_experts), dtype, -s_in, s_in),
            "w_up": jax.random.uniform(k2, (e, w, f), dtype, -s_w, s_w),
            "w_down": jax.random.uniform(k3, (e, f, w), dtype,
                                         -s_ffn, s_ffn),
        }
        if self.gated:
            p["w_gate"] = jax.random.uniform(k4, (e, w, f), dtype,
                                             -s_w, s_w)
        if self.router_bias:
            p["router_bias"] = jnp.zeros((self.router_experts,), dtype)
        if self.shared_size:
            ks = jax.random.split(k5, 3)
            g, s_sh = self.shared_size, 1.0 / math.sqrt(self.shared_size)
            p["shared"] = {
                "w_up": jax.random.uniform(ks[1], (h, g), dtype,
                                           -s_in, s_in),
                "w_down": jax.random.uniform(ks[2], (g, h), dtype,
                                             -s_sh, s_sh)}
            if self.gated:
                p["shared"]["w_gate"] = jax.random.uniform(
                    ks[0], (h, g), dtype, -s_in, s_in)
        if self.latent_size:
            kl = jax.random.split(jax.random.fold_in(k5, 1), 2)
            p["w_lat_in"] = jax.random.uniform(kl[0], (h, w), dtype,
                                               -s_in, s_in)
            p["w_lat_out"] = jax.random.uniform(kl[1], (w, h), dtype,
                                                -s_w, s_w)
        return p

    def initial_state(self):
        return {AUX_LOSS_KEY: jnp.zeros((), jnp.float32),
                "expert_frac": jnp.zeros((self.router_experts,),
                                         jnp.float32),
                MOE_STATS_KEY: jnp.zeros((3,), jnp.float32)}

    def route(self, params, x):
        """``x [T, h]`` -> the router's scores ``[T, E_r]``, the chosen
        ids ``[T, k]`` and their combine weights ``[T, k]``."""
        if self.scoring == "sigmoid":
            # the router is a float32 island: which experts win hangs
            # on differences far below bfloat16's step
            x32 = x.astype(jnp.float32)  # bigdl: disable=implicit-upcast-in-trace
            router = params["router"].astype(jnp.float32)  # bigdl: disable=implicit-upcast-in-trace
            logits = jnp.dot(x32, router)
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(x @ params["router"], axis=-1)
        choice = scores
        if "router_bias" in params:
            choice = scores + params["router_bias"].astype(scores.dtype)
        _, idx = jax.lax.top_k(choice, self.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if self.route_norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        if self.route_scale != 1.0:
            w = w * self.route_scale
        return scores, idx, w

    def apply(self, params, state, input, *, training=False, rng=None,
              token_mask=None):
        """``token_mask`` (bool ``[B, S]``): tokens that are padding or
        sit in a free decode slot take no expert — no pair, no product,
        no count (their output is the shared expert's alone, and is
        never read)."""
        b, s, h = input.shape                         # [B,S,Em]
        x = input.reshape(b * s, h)
        with jax.named_scope("moe/router"):
            scores, idx, w = self.route(params, x)
            if token_mask is not None:
                # an id past the router's width is held by no one
                idx = jnp.where(token_mask.reshape(b * s, 1), idx,
                                self.router_experts)
        xe = x
        if "w_lat_in" in params:
            with jax.named_scope("moe/latent"):
                xe = x @ params["w_lat_in"]
        out, stats = routed_experts(
            params, xe, idx, w, offset=self.expert_offset,
            router_experts=self.router_experts,
            activation=self.activation)
        if "w_lat_out" in params:
            with jax.named_scope("moe/latent"):
                out = out @ params["w_lat_out"]
        if "shared" in params:
            with jax.named_scope("moe/shared"):
                out = out + gated_ffn(params["shared"], x, self.activation)
        # Switch-transformer load-balance loss: E * sum_e f_e * P_e
        frac_routed = jnp.mean(
            jax.nn.one_hot(idx[..., 0], self.router_experts,
                           dtype=jnp.float32), axis=0)
        mean_prob = jnp.mean(scores, axis=0)
        aux = self.router_experts * jnp.sum(frac_routed * mean_prob)
        # expert utilization (top-1 routing fraction per expert) rides
        # the state so tools/convergence can report load balance
        # aux loss + telemetry fractions are sanctioned f32 islands
        # (summed into the loss / read by convergence tooling)
        return out.reshape(b, s, h), {
            AUX_LOSS_KEY: aux.astype(jnp.float32),  # bigdl: disable=implicit-upcast-in-trace
            "expert_frac": frac_routed.astype(jnp.float32),  # bigdl: disable=implicit-upcast-in-trace
            MOE_STATS_KEY: stats}
