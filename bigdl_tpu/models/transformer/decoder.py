"""A decoder-only LM built from a per-layer pattern — for the decoders
whose layers are not all alike: window and global attention and
state-space mixers in one stack, dense and routed-expert FFNs, each
layer's kinds read from a configuration.

``pattern`` is one ``(mixer kind, ffn kind)`` pair per layer. Mixer:
``"window"`` (attention; a query sees the last ``window`` positions;
its cache entry is a ring of ``window`` columns), ``"global"``
(attention over the whole prefix), ``"ssm"`` (a Mamba-2 mixer,
:class:`bigdl_tpu.nn.ssm.Mamba2Mixer`, whose entry is a recurrent state
with no time axis) or ``"none"``. FFN: ``"dense"`` (the gated form
``(silu(x Wgate) * (x Wup)) Wdown``), ``"experts"``
(:class:`bigdl_tpu.nn.moe.MoE`: sigmoid router with a stored bias, one
shared expert, the experts held here a slice of the router's width;
gated or not, in a latent space or not, by the constructor's
arguments) or ``"none"``.

``block_style`` says how a layer's parts sit on the residual stream:
``"sandwich"`` is ``a = x + norm(mixer(norm(x)))``, ``y = a +
norm(ffn(norm(a)))``; ``"prenorm"`` is ``x + f(norm(x))`` for each part
the layer has. All norms RMSNorm. Attention is
:class:`~bigdl_tpu.nn.attention.GroupedQueryAttention` (grouped K/V
heads; q/k norm and an output gate unless switched off; rotary
positions on the layer kinds ``rope_layers`` names, none on the
others), with an embedding scale and an untied head. It shares
:class:`TransformerLM`'s entry points — ``apply(params, state, tokens,
cache=, positions=, attend_len=)`` — so the generation engine serves
both through the same programs, and adds what a stack of unlike layers
must tell the engine: ``cache_layout`` (each layer's entry and its
kind) and ``cache_dtype``.
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
from bigdl_tpu.nn.ssm import Mamba2Mixer
from bigdl_tpu.utils.engine import Engine

_MIXER_KINDS = ("window", "global", "ssm", "none")
_FFN_KINDS = ("dense", "experts", "none")
_STYLES = ("sandwich", "prenorm")


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
    """One layer: a mixer (attention or a state-space mixer; params
    under ``attn`` / ``ssm``), an FFN (``mlp``), or both, each under
    the norms its ``style`` puts round it."""

    def __init__(self, hidden_size: int, mixer: Optional[Module],
                 mlp: Optional[Module], norm_eps: float,
                 style: str = "sandwich"):
        super().__init__()
        self.mixer, self.mlp, self.style = mixer, mlp, style
        self.mixer_key = "ssm" if isinstance(mixer, Mamba2Mixer) else "attn"
        names = []
        if mixer is not None:
            names += ["norm_in"] + ["norm_post_attn"] * (style == "sandwich")
        if mlp is not None:
            names += ["norm_pre_mlp"] \
                + ["norm_post_mlp"] * (style == "sandwich")
        self.norms = {n: RMSNorm(hidden_size, eps=norm_eps) for n in names}

    def init(self, rng):
        ks = jax.random.split(rng, 6)
        p = {}
        if self.mixer is not None:
            p[self.mixer_key] = adopt_or_init(self.mixer, ks[0])
        if self.mlp is not None:
            p["mlp"] = adopt_or_init(self.mlp, ks[1])
        for k, (n, m) in zip(ks[2:], sorted(self.norms.items())):
            p[n] = adopt_or_init(m, k)
        return p

    def initial_state(self):
        return {} if self.mlp is None else {"mlp": adopt_state(self.mlp)}

    def _norm(self, params, name, x):
        if name not in self.norms:        # the prenorm style has no post
            return x
        with jax.named_scope("norm"):
            return self.norms[name].forward_fn(params[name], x)

    def apply(self, params, state, input, *, training=False, rng=None,
              cache=None, positions=None, attend_len=None, valid=None,
              token_mask=None, fresh=False):
        """``cache`` is this layer's entry (``{}`` for a layer that
        keeps nothing), returned as the third value when given."""
        a, new_state = input, {}
        if self.mixer is not None:
            h = self._norm(params, "norm_in", input)
            mp = params[self.mixer_key]
            if cache is None:
                h = self.mixer.forward_fn(mp, h, training=training)
            else:
                h, cache = self.mixer.forward_fn(
                    mp, h, training=training, cache=cache,
                    positions=positions, attend_len=attend_len,
                    valid=valid, fresh=fresh)
            a = input + self._norm(params, "norm_post_attn", h)
        y = a
        if self.mlp is not None:
            h = self._norm(params, "norm_pre_mlp", a)
            with jax.named_scope("mlp"):
                routed = {"token_mask": token_mask} \
                    if isinstance(self.mlp, MoE) else {}
                h, mlp_state = self.mlp.apply(params["mlp"],
                                              state.get("mlp", {}), h,
                                              training=training, **routed)
            new_state = {"mlp": mlp_state}
            y = a + self._norm(params, "norm_post_mlp", h)
        if cache is None:
            return y, new_state
        return y, new_state, cache


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
                 route_norm: bool = True, embed_scale: float = 1.0,
                 block_style: str = "sandwich", qk_norm: bool = True,
                 attn_gate: bool = True, expert_activation: str = "silu",
                 expert_gated: bool = True, latent_size: int = 0,
                 ssm: Optional[dict] = None):
        super().__init__()
        if rope_layers not in ("window", "global", "all", "none"):
            raise ValueError(f"rope_layers={rope_layers!r}")
        if block_style not in _STYLES:
            raise ValueError(f"block_style={block_style!r}: {_STYLES}")
        for a, f in pattern:
            if (a not in _MIXER_KINDS or f not in _FFN_KINDS
                    or a == f == "none"):
                raise ValueError(f"layer kinds ({a!r}, {f!r}): mixer "
                                 f"{_MIXER_KINDS}, ffn {_FFN_KINDS}, "
                                 "at least one of them")
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
            mixer = mlp = None
            if a == "ssm":
                mixer = Mamba2Mixer(hidden_size, norm_eps=norm_eps,
                                    **(ssm or {}))
            elif a != "none":
                rope = rope_layers == "all" or rope_layers == a
                mixer = GroupedQueryAttention(
                    hidden_size, num_heads, num_kv_heads, head_dim,
                    window=window if a == "window" else None,
                    rope_theta=rope_theta if rope else None,
                    qk_norm=qk_norm, gate=attn_gate, norm_eps=norm_eps)
            if f == "experts":
                mlp = MoE(hidden_size, expert_size, held, top_k,
                          expert_activation, gated=expert_gated,
                          scoring="sigmoid",
                          router_experts=router_experts,
                          expert_offset=offset, router_bias=True,
                          route_norm=route_norm, route_scale=route_scale,
                          shared_size=shared_size, latent_size=latent_size)
            elif f == "dense":
                mlp = GatedFeedForward(hidden_size, ffn_size)
            self.blocks.append(PatternBlock(hidden_size, mixer, mlp,
                                            norm_eps, block_style))
        self.norm_f = RMSNorm(hidden_size, eps=norm_eps)

    # ---- what the generation engine asks of a stack of unlike layers
    def cache_layout(self, max_len: int):
        """One entry a layer, each naming its kind: ``("kv", kv heads,
        head dim, columns)`` for attention (a window layer keeps a ring
        of ``window`` columns, a global one every position),
        ``("state", ((name, shape, dtype), ...))`` for a state-space
        mixer's recurrent arrays, ``("none",)`` for a layer that keeps
        nothing."""
        out = []
        for (a, _), blk in zip(self.pattern, self.blocks):
            if a == "ssm":
                out.append(("state", blk.mixer.cache_arrays()))
            elif a == "none":
                out.append(("none",))
            else:
                out.append(("kv", self.num_kv_heads, self.head_dim,
                            blk.mixer.cache_columns(max_len)))
        return out

    def scoreless_prefill(self, rung: int) -> bool:
        """Whether every layer that attends can prefill ``rung`` fresh
        tokens in one shot without materialised attention scores."""
        return all(blk.mixer.scoreless(rung) for blk in self.blocks
                   if blk.mixer_key == "attn" and blk.mixer is not None)

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
        new_state, new_cache = {}, []
        for i, blk in enumerate(self.blocks):
            bp, bs = params[f"block_{i}"], state.get(f"block_{i}", {})
            if cache is None:
                x, st = blk.apply(bp, bs, x, training=training,
                                  token_mask=token_mask)
            else:
                x, st, entry = blk.apply(
                    bp, bs, x, training=training, cache=cache[i],
                    positions=positions, attend_len=attend_len,
                    valid=valid, token_mask=token_mask, fresh=fresh)
                new_cache.append(entry)
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
        return logits, new_state, tuple(new_cache)
