"""Transformer LM — the long-context/distributed flagship family (net-new
capability beyond the reference's RNN LM, models/rnn/SimpleRNN.scala; built
TPU-first so dp/tp/sp/ep shardings are part of the model definition).

``TransformerLM.sharding_rules(model_axis=..., expert_axis=...)`` returns
param-path → PartitionSpec rules (megatron-style: attention QKV
column-parallel, O row-parallel; FFN up column / down row; embeddings
vocab-parallel; MoE experts over the expert axis). Feed them to
``bigdl_tpu.parallel.shard_params`` / ``Optimizer(sharding_rules=...)`` and
XLA inserts the collectives.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.attention import MultiHeadAttention, scoreless
from bigdl_tpu.nn.moe import MoE
from bigdl_tpu.nn.module import (AUX_LOSS_KEY, Module, adopt_or_init,
                                  adopt_state)
from bigdl_tpu.nn.norm import LayerNorm
from bigdl_tpu.utils.engine import Engine


class FeedForward(Module):
    def __init__(self, hidden_size: int, ffn_size: int,
                 activation: str = "gelu"):
        super().__init__()
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size
        self.activation = activation

    def init(self, rng):
        dtype = Engine.default_dtype()
        k1, k2 = jax.random.split(rng)
        s1 = 1.0 / math.sqrt(self.hidden_size)
        s2 = 1.0 / math.sqrt(self.ffn_size)
        return {"w_up": jax.random.uniform(
                    k1, (self.hidden_size, self.ffn_size), dtype, -s1, s1),
                "b_up": jnp.zeros((self.ffn_size,), dtype),
                "w_down": jax.random.uniform(
                    k2, (self.ffn_size, self.hidden_size), dtype, -s2, s2),
                "b_down": jnp.zeros((self.hidden_size,), dtype)}

    def forward_fn(self, params, input, *, training=False, rng=None):
        act = jax.nn.gelu if self.activation == "gelu" else jax.nn.relu
        h = act(input @ params["w_up"] + params["b_up"])
        return h @ params["w_down"] + params["b_down"]


class TransformerBlock(Module):
    """Pre-norm block: x + MHA(LN(x)); x + FFN/MoE(LN(x))."""

    def __init__(self, hidden_size: int, num_heads: int, ffn_size: int,
                 dropout: float = 0.0, causal: bool = True,
                 ring_axis: Optional[str] = None, sp_impl: str = "ring",
                 mesh=None, moe_experts: int = 0, moe_top_k: int = 2):
        super().__init__()
        self.ln1 = LayerNorm(hidden_size)
        self.attn = MultiHeadAttention(hidden_size, num_heads,
                                       dropout=dropout, causal=causal,
                                       ring_axis=ring_axis,
                                       sp_impl=sp_impl, mesh=mesh)
        self.ln2 = LayerNorm(hidden_size)
        if moe_experts > 0:
            self.mlp = MoE(hidden_size, ffn_size, moe_experts, moe_top_k)
        else:
            self.mlp = FeedForward(hidden_size, ffn_size)
        self.moe_experts = moe_experts

    def init(self, rng):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        return {"ln1": adopt_or_init(self.ln1, k1),
                "attn": adopt_or_init(self.attn, k2),
                "ln2": adopt_or_init(self.ln2, k3),
                "mlp": adopt_or_init(self.mlp, k4)}

    def initial_state(self):
        return {"mlp": adopt_state(self.mlp)}

    def apply(self, params, state, input, *, training=False, rng=None,
              cache=None, positions=None, attend_len=None, attn_mask=None,
              attn_segments=None, fresh=False):
        r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
        # named scopes (here and in nn.attention) are the by-role
        # vocabulary a device trace's op names carry: docs/telemetry.md
        with jax.named_scope("norm"):
            h = self.ln1.forward_fn(params["ln1"], input)
        if cache is None:
            h = self.attn.forward_fn(params["attn"], h, training=training,
                                     rng=r1, mask=attn_mask,
                                     segments=attn_segments)
        else:
            if attn_mask is not None or attn_segments is not None:
                raise ValueError(
                    "segment masks are not supported on the KV-cached "
                    "decode path (pack training slabs, not decode steps)")
            # incremental decode: the attention writes this block's K/V
            # rows at `positions` and returns the updated cache
            h, cache = self.attn.forward_fn(
                params["attn"], h, training=training, rng=r1,
                cache=cache, positions=positions, attend_len=attend_len,
                fresh=fresh)
        x = input + h
        with jax.named_scope("norm"):
            h = self.ln2.forward_fn(params["ln2"], x)
        with jax.named_scope("mlp"):
            h, mlp_state = self.mlp.apply(params["mlp"],
                                          state.get("mlp", {}), h,
                                          training=training, rng=r2)
        if cache is None:
            return x + h, {"mlp": mlp_state}
        return x + h, {"mlp": mlp_state}, cache


class TransformerLM(Module):
    """Decoder-only LM over int32 token ids [B, S] -> logits [B, S, V].

    Also accepts the PACKED 3-plane input convention the datapipe
    produces (``bigdl_tpu.datapipe.packing``): a list/Table of
    ``[tokens, segment_ids, positions]``, each ``[B, S]`` int — rows
    hold several documents head-to-tail, attention is restricted to
    same-segment (and causal) pairs, and positional embeddings gather
    at the per-document ``positions`` (restarting at 0), so the packed
    forward is per-token exact against running each document alone.
    Segment id 0 marks padding; its logits are garbage by design (mask
    their targets with the criterion's ``ignore_index``).

    **Served** by the generation engine through the contract of
    ``generation/engine.py``: ``apply(..., cache=, positions=,
    attend_len=)`` is one KV-cached step returning ``(logits, state,
    cache)``; ``logits_at=`` (int ``[B]``): the logits of that one new
    position a row come back, ``[B, 1, V]`` - a prefill multiplies one
    row by the head, not every position's; ``live=`` (bool ``[B]``):
    rows that are padding or free decode slots - accepted and unused,
    no layer here lets one row's tokens touch another's; ``fresh=``
    (static): a one-shot prefill, every offset 0 - the new tokens
    attend only each other (``nn.attention.cached_attention``)."""

    def __init__(self, vocab_size: int, hidden_size: int = 512,
                 num_layers: int = 6, num_heads: int = 8,
                 ffn_size: Optional[int] = None, max_len: int = 2048,
                 dropout: float = 0.0, ring_axis: Optional[str] = None,
                 sp_impl: str = "ring", mesh=None,
                 moe_experts: int = 0, moe_every: int = 2,
                 tie_embeddings: bool = True):
        super().__init__()
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_size = ffn_size or 4 * hidden_size
        self.max_len = max_len
        self.dropout = dropout
        self.ring_axis = ring_axis
        self.moe_experts = moe_experts
        self.tie_embeddings = tie_embeddings
        self.blocks = [
            TransformerBlock(
                hidden_size, num_heads, self.ffn_size, dropout=dropout,
                causal=True, ring_axis=ring_axis, sp_impl=sp_impl,
                mesh=mesh,
                moe_experts=(moe_experts if moe_experts
                             and (i % moe_every == moe_every - 1) else 0))
            for i in range(num_layers)]
        self.ln_f = LayerNorm(hidden_size)

    # ---- what the generation engine asks of a decoder it serves
    def cache_layout(self, max_len: int):
        """``[("kv", kv heads, head dim, columns), ...]``, one per
        layer: every layer keeps every position of all its heads."""
        return [("kv", self.num_heads, self.hidden_size // self.num_heads,
                 max_len)] * self.num_layers

    def cache_dtype(self):
        """None: the cache takes the engine's default type."""
        return None

    def scoreless_prefill(self, rung: int) -> bool:
        """Whether ``rung`` fresh tokens prefill in one shot without
        materialised attention scores (never at GPT-2's head of 64)."""
        return scoreless(rung, self.hidden_size // self.num_heads)

    def init(self, rng):
        dtype = Engine.default_dtype()
        keys = jax.random.split(rng, self.num_layers + 4)
        s = 1.0 / math.sqrt(self.hidden_size)
        p = {"embed": jax.random.normal(
                 keys[0], (self.vocab_size, self.hidden_size), dtype) * s,
             "pos_embed": jax.random.normal(
                 keys[1], (self.max_len, self.hidden_size), dtype) * s,
             "ln_f": adopt_or_init(self.ln_f, keys[2])}
        for i, blk in enumerate(self.blocks):
            p[f"block_{i}"] = adopt_or_init(blk, keys[3 + i])
        if not self.tie_embeddings:
            p["lm_head"] = jax.random.normal(
                keys[-1], (self.hidden_size, self.vocab_size), dtype) * s
        return p

    def initial_state(self):
        return {f"block_{i}": adopt_state(blk)
                for i, blk in enumerate(self.blocks)}

    def apply(self, params, state, input, *, training=False, rng=None,
              cache=None, positions=None, attend_len=None,
              logits_at=None, live=None, fresh=False):
        from bigdl_tpu.utils.table import Table
        seg = None
        packed_pos = None
        if isinstance(input, Table):
            input = [input[i] for i in range(1, input.length() + 1)]
        if isinstance(input, (list, tuple)):
            if len(input) != 3:
                raise ValueError(
                    "packed TransformerLM input must be [tokens, "
                    f"segment_ids, positions]; got {len(input)} planes")
            if cache is not None:
                raise ValueError(
                    "packed 3-plane input is a training/scoring layout; "
                    "the KV-cached decode path takes plain token ids")
            tokens, segment_ids, packed_pos = input
            # same-document attention only: the raw [B, S] plane rides
            # down as attn_segments — nn.attention derives the
            # [B, 1, Sq, Sk] equality mask for the einsum path (one
            # derivation site) and hands the plane itself to the
            # pallas flash kernel when enabled
            seg = segment_ids.astype(jnp.int32)
            tokens = tokens.astype(jnp.int32)
        else:
            tokens = input.astype(jnp.int32)
        b, s = tokens.shape
        with jax.named_scope("embed"):
            if cache is None:
                if packed_pos is None:
                    x = (params["embed"][tokens]
                         + params["pos_embed"][:s][None])
                else:
                    # per-document positions (restart at 0 per segment)
                    # so a packed document sees the same positional
                    # embeddings it would alone in a row
                    idx = jnp.clip(packed_pos.astype(jnp.int32), 0,
                                   self.max_len - 1)
                    x = params["embed"][tokens] + params["pos_embed"][idx]
            else:
                # incremental decode: row b's S tokens sit at absolute
                # positions positions[b] .. positions[b]+S-1 (clip
                # keeps a free-slot row's garbage offset from faulting
                # the gather; its output is never read)
                idx = jnp.clip(
                    positions.astype(jnp.int32)[:, None]
                    + jnp.arange(s)[None], 0, self.max_len - 1)
                x = params["embed"][tokens] + params["pos_embed"][idx]
        keys = (jax.random.split(rng, self.num_layers)
                if rng is not None else [None] * self.num_layers)
        new_state, new_cache = {}, []
        for i, blk in enumerate(self.blocks):
            if cache is None:
                # attn_segments only rides along for packed inputs:
                # the plain path keeps the bare apply signature
                # (shapecheck interceptors and custom blocks see no
                # new kwarg). The raw segment-id plane travels instead
                # of a prebuilt [B,1,S,S] mask — nn.attention derives
                # the equality mask for the einsum path and feeds the
                # plane to the pallas flash kernel when enabled.
                mask_kw = {} if seg is None \
                    else {"attn_segments": seg}
                x, st = blk.apply(params[f"block_{i}"],
                                  state.get(f"block_{i}", {}), x,
                                  training=training, rng=keys[i],
                                  **mask_kw)
            else:
                # each layer owns its entry: handed in and collected
                # as it is, so nothing of the cache is sliced or stacked
                x, st, entry = blk.apply(
                    params[f"block_{i}"], state.get(f"block_{i}", {}), x,
                    training=training, rng=keys[i], cache=cache[i],
                    positions=positions, attend_len=attend_len,
                    fresh=fresh)
                new_cache.append(entry)
            new_state[f"block_{i}"] = st
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        with jax.named_scope("norm"):
            x = self.ln_f.forward_fn(params["ln_f"], x)
        with jax.named_scope("lm_head"):
            if self.tie_embeddings:
                logits = x @ params["embed"].T
            else:
                logits = x @ params["lm_head"]
        if cache is None:
            return logits, new_state
        return logits, new_state, tuple(new_cache)

    def aux_loss(self, state) -> jnp.ndarray:
        """Total MoE load-balance loss across blocks."""
        total = jnp.zeros((), jnp.float32)
        for st in state.values():
            mlp = st.get("mlp", {}) if isinstance(st, dict) else {}
            if AUX_LOSS_KEY in mlp:
                total = total + mlp[AUX_LOSS_KEY]
        return total

    # ---- sharding (megatron-style rules consumed by parallel.shard_params)
    def sharding_rules(self, model_axis: str = "model",
                       expert_axis: Optional[str] = None):
        from jax.sharding import PartitionSpec as P
        e_ax = expert_axis or model_axis
        # matched in order by parallel.shard_params; a rule only applies
        # when its spec rank matches the leaf rank, so the 3-D stacked
        # expert weights pick the expert-parallel rule and the 2-D dense
        # FFN weights the megatron one.
        return [
            # pos_embed before embed: spec_for uses re.search and an
            # unanchored "embed" would swallow "pos_embed"
            ("pos_embed", P()),
            (r"(^|/)embed$", P(model_axis, None)),   # vocab-parallel
            ("lm_head", P(None, model_axis)),
            (r"block_\d+/attn/w[qkv]", P(None, model_axis)),  # column
            (r"block_\d+/attn/b[qkv]", P(model_axis)),
            (r"block_\d+/attn/wo", P(model_axis, None)),      # row
            (r"block_\d+/attn/bo", P()),
            # MoE stacked experts [E, ., .]: shard the expert dim (EP)
            (r"block_\d+/mlp/w_up", P(e_ax, None, None)),
            (r"block_\d+/mlp/w_down", P(e_ax, None, None)),
            # dense FFN (megatron column/row)
            (r"block_\d+/mlp/w_up", P(None, model_axis)),
            (r"block_\d+/mlp/b_up", P(model_axis)),
            (r"block_\d+/mlp/w_down", P(model_axis, None)),
            (r"block_\d+/mlp/b_down", P()),
            (r"block_\d+/mlp/router", P()),
            (r"block_\d+/ln\d", P()),
            ("ln_f", P()),
        ]
