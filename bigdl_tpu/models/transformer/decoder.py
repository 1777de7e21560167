"""A decoder-only LM built from a per-layer pattern — for the decoders
whose layers are not all alike: window and global attention mixed in
one stack, dense and routed-expert FFNs, each layer's kinds read from a
configuration.

``pattern`` is one ``(attention kind, ffn kind)`` pair per layer:
attention ``"window"`` (a query sees the last ``window`` positions; its
cache entry is a ring of ``window`` columns) or ``"global"`` (the whole
prefix); ffn ``"dense"`` (the gated form ``(silu(x Wgate) * (x Wup))
Wdown``) or ``"experts"`` (:class:`bigdl_tpu.nn.moe.MoE`: sigmoid
router with a stored bias, gated experts, one shared expert, the
experts held here a slice of the router's width).

Every layer is the sandwich block ``a = x + norm(attn(norm(x)))``,
``y = a + norm(ffn(norm(a)))`` with RMSNorm,
:class:`~bigdl_tpu.nn.attention.GroupedQueryAttention` (grouped K/V
heads, q/k norm, an output gate; rotary positions on the layer kinds
``rope_layers`` names, none on the others), an embedding scale and an
untied head. It shares :class:`TransformerLM`'s entry points —
``apply(params, state, tokens, cache=, positions=, attend_len=)`` — so
the generation engine serves both through the same programs, and adds
what a stack of unlike layers must tell the engine: ``cache_layout``
(each layer's K/V heads, head size and columns) and ``cache_dtype``.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.moe import MoE, gated_ffn
from bigdl_tpu.nn.module import Module, adopt_or_init, adopt_state
from bigdl_tpu.nn.norm import RMSNorm
from bigdl_tpu.utils.engine import Engine

_ATTN_KINDS = ("window", "global")
_FFN_KINDS = ("dense", "experts")


class GatedFeedForward(Module):
    """``(silu(x Wgate) * (x Wup)) Wdown``, no biases."""

    def __init__(self, hidden_size: int, ffn_size: int):
        super().__init__()
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size

    def init(self, rng):
        dtype = Engine.default_dtype()
        k1, k2, k3 = jax.random.split(rng, 3)
        h, f = self.hidden_size, self.ffn_size
        s1, s2 = 1.0 / math.sqrt(h), 1.0 / math.sqrt(f)
        return {"w_gate": jax.random.uniform(k1, (h, f), dtype, -s1, s1),
                "w_up": jax.random.uniform(k2, (h, f), dtype, -s1, s1),
                "w_down": jax.random.uniform(k3, (f, h), dtype, -s2, s2)}

    def forward_fn(self, params, input, *, training=False, rng=None):
        return gated_ffn(params, input)


class PatternBlock(Module):
    """One sandwich-norm layer of the kinds ``(attn_kind, ffn_kind)``."""

    def __init__(self, hidden_size: int, attn: GroupedQueryAttention,
                 mlp: Module, norm_eps: float):
        super().__init__()
        self.attn, self.mlp = attn, mlp
        self.norms = {n: RMSNorm(hidden_size, eps=norm_eps) for n in
                      ("norm_in", "norm_post_attn", "norm_pre_mlp",
                       "norm_post_mlp")}

    def init(self, rng):
        ks = jax.random.split(rng, 6)
        p = {"attn": adopt_or_init(self.attn, ks[0]),
             "mlp": adopt_or_init(self.mlp, ks[1])}
        for k, (n, m) in zip(ks[2:], sorted(self.norms.items())):
            p[n] = adopt_or_init(m, k)
        return p

    def initial_state(self):
        return {"mlp": adopt_state(self.mlp)}

    def _norm(self, params, name, x):
        with jax.named_scope("norm"):
            return self.norms[name].forward_fn(params[name], x)

    def apply(self, params, state, input, *, training=False, rng=None,
              cache=None, positions=None, attend_len=None, valid=None,
              token_mask=None, fresh=False):
        h = self._norm(params, "norm_in", input)
        if cache is None:
            h = self.attn.forward_fn(params["attn"], h, training=training)
        else:
            h, cache = self.attn.forward_fn(
                params["attn"], h, training=training, cache=cache,
                positions=positions, attend_len=attend_len, valid=valid,
                fresh=fresh)
        a = input + self._norm(params, "norm_post_attn", h)
        h = self._norm(params, "norm_pre_mlp", a)
        with jax.named_scope("mlp"):
            routed = {"token_mask": token_mask} \
                if isinstance(self.mlp, MoE) else {}
            h, mlp_state = self.mlp.apply(params["mlp"],
                                          state.get("mlp", {}), h,
                                          training=training, **routed)
        y = a + self._norm(params, "norm_post_mlp", h)
        if cache is None:
            return y, {"mlp": mlp_state}
        return y, {"mlp": mlp_state}, cache


class PatternDecoderLM(Module):
    """Decoder-only LM over int32 token ids [B, S] -> logits, its
    layers given by ``pattern`` (module docstring).

    ``local_experts = (offset, count)`` says which of the router's
    ``router_experts`` experts this instance holds (default: all): the
    share one device of an expert-parallel group serves.

    ``apply(..., logits_at=)`` (int ``[B]``, with ``cache=``): the
    logits of that one new position a row come back, ``[B, 1, V]``, and
    the new tokens past it are padding — a prefill needs one row of
    logits, and a ring must not take padding in. ``live=`` (bool
    ``[B]``): rows that are padding or free decode slots; their tokens,
    like those past ``logits_at``, are routed to no expert. ``fresh=``
    (static): a one-shot prefill, every offset 0 — the new tokens
    attend only each other (``GroupedQueryAttention``).
    ``scoreless_prefill(rung)`` tells the engine whether that one shot
    holds no ``[rung, rung]`` scores."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 pattern: Sequence[Tuple[str, str]], num_heads: int,
                 num_kv_heads: int, head_dim: int, ffn_size: int, *,
                 window: int, max_len: int = 2048,
                 rope_theta: float = 10000.0,
                 rope_layers: str = "window", norm_eps: float = 1e-5,
                 expert_size: int = 0, shared_size: int = 0,
                 router_experts: int = 0,
                 local_experts: Optional[Tuple[int, int]] = None,
                 top_k: int = 2, route_scale: float = 1.0,
                 route_norm: bool = True, embed_scale: float = 1.0):
        super().__init__()
        if rope_layers not in ("window", "global", "all", "none"):
            raise ValueError(f"rope_layers={rope_layers!r}")
        for a, f in pattern:
            if a not in _ATTN_KINDS or f not in _FFN_KINDS:
                raise ValueError(f"layer kinds ({a!r}, {f!r}): attention "
                                 f"{_ATTN_KINDS}, ffn {_FFN_KINDS}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.pattern = [tuple(p) for p in pattern]
        self.num_layers = len(self.pattern)
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.window = window
        self.max_len = max_len
        self.embed_scale = embed_scale
        offset, held = local_experts or (0, router_experts)
        self.blocks = []
        for a, f in self.pattern:
            rope = rope_layers == "all" or rope_layers == a
            attn = GroupedQueryAttention(
                hidden_size, num_heads, num_kv_heads, head_dim,
                window=window if a == "window" else None,
                rope_theta=rope_theta if rope else None,
                norm_eps=norm_eps)
            if f == "experts":
                mlp = MoE(hidden_size, expert_size, held, top_k, "silu",
                          gated=True, scoring="sigmoid",
                          router_experts=router_experts,
                          expert_offset=offset, router_bias=True,
                          route_norm=route_norm, route_scale=route_scale,
                          shared_size=shared_size)
            else:
                mlp = GatedFeedForward(hidden_size, ffn_size)
            self.blocks.append(PatternBlock(hidden_size, attn, mlp,
                                            norm_eps))
        self.norm_f = RMSNorm(hidden_size, eps=norm_eps)

    # ---- what the generation engine asks of a stack of unlike layers
    def cache_layout(self, max_len: int):
        """``[(kv heads, head dim, columns), ...]``, one per layer: a
        window layer keeps a ring of ``window`` columns, a global one
        every position."""
        return [(self.num_kv_heads, self.head_dim,
                 blk.attn.cache_columns(max_len)) for blk in self.blocks]

    def scoreless_prefill(self, rung: int) -> bool:
        """Whether every layer can prefill ``rung`` fresh tokens in one
        shot without materialised attention scores."""
        return all(blk.attn.scoreless(rung) for blk in self.blocks)

    def cache_dtype(self):
        """The cache holds keys and values in the type the loaded
        weights compute them in."""
        if self._params is not None:
            return self._params["embed"].dtype
        return Engine.default_dtype()

    def init(self, rng):
        dtype = Engine.default_dtype()
        keys = jax.random.split(rng, self.num_layers + 3)
        s = 1.0 / math.sqrt(self.hidden_size)
        p = {"embed": jax.random.normal(
                 keys[0], (self.vocab_size, self.hidden_size), dtype) * s,
             "lm_head": jax.random.normal(
                 keys[1], (self.hidden_size, self.vocab_size), dtype) * s,
             "norm_f": adopt_or_init(self.norm_f, keys[2])}
        for i, blk in enumerate(self.blocks):
            p[f"block_{i}"] = adopt_or_init(blk, keys[3 + i])
        return p

    def initial_state(self):
        return {f"block_{i}": adopt_state(blk)
                for i, blk in enumerate(self.blocks)}

    def apply(self, params, state, input, *, training=False, rng=None,
              cache=None, positions=None, attend_len=None,
              logits_at=None, live=None, fresh=False):
        tokens = input.astype(jnp.int32)
        with jax.named_scope("embed"):
            x = params["embed"][tokens]
            if self.embed_scale != 1.0:
                x = x * jnp.asarray(self.embed_scale, x.dtype)
        valid = None if logits_at is None \
            else logits_at.astype(jnp.int32) + 1
        token_mask = None
        if valid is not None:
            token_mask = jnp.arange(tokens.shape[1])[None] < valid[:, None]
        if live is not None:
            token_mask = live[:, None] if token_mask is None \
                else token_mask & live[:, None]
            token_mask = jnp.broadcast_to(token_mask, tokens.shape)
        new_state, new_k, new_v = {}, [], []
        for i, blk in enumerate(self.blocks):
            bp, bs = params[f"block_{i}"], state.get(f"block_{i}", {})
            if cache is None:
                x, st = blk.apply(bp, bs, x, training=training,
                                  token_mask=token_mask)
            else:
                x, st, layer_cache = blk.apply(
                    bp, bs, x, training=training,
                    cache={"k": cache["k"][i], "v": cache["v"][i]},
                    positions=positions, attend_len=attend_len,
                    valid=valid, token_mask=token_mask, fresh=fresh)
                new_k.append(layer_cache["k"])
                new_v.append(layer_cache["v"])
            new_state[f"block_{i}"] = st
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        with jax.named_scope("norm"):
            x = self.norm_f.forward_fn(params["norm_f"], x)
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x, params["lm_head"],
                             preferred_element_type=jnp.float32)
        if cache is None:
            return logits, new_state
        return logits, new_state, {"k": tuple(new_k), "v": tuple(new_v)}
