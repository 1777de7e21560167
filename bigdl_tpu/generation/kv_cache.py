"""Preallocated, shape-bucketed KV cache + host-side slot accounting.

The decode engine's whole memory story is ONE allocation per model
version: per layer one K and one V array ``[slots, heads, head_dim,
columns]`` (``columns`` is ``max_len``, already padded to the top rung
of the service's length ladder, or a sliding-window layer's ring), an
explicit per-slot ``lengths`` vector, and
a host-side alloc/free bitmap. Requests *occupy slots* — admission is a
bitmap ``alloc()``, eviction a ``free()`` — so continuous batching never
reshapes or reallocates device memory, which is exactly what keeps the
decode program count bounded (every step runs at the same
``[slots, ...]`` shapes; see docs/serving.md "Generation").

Time is the LAST axis because that is the form the chip stores and the
decode kernel reads: ``head_dim`` 64 on the 128 lanes would pad every
tile 2x, so the compiler kept a ``[.., max_len, head_dim]`` array
time-minor anyway and transposed a whole layer on each side of the
kernel, every step. One array per layer, written in place by the
donated programs, leaves no copy of the cache in a decode step.
"""
from __future__ import annotations

from typing import FrozenSet, List, Optional

import numpy as np


class SlotAllocator:
    """Host-side alloc/free bitmap over a cache's request slots.

    Single-owner accounting (the :class:`~bigdl_tpu.generation.loop.
    DecodeLoop` driver thread): ``alloc`` hands out the lowest free
    slot, ``free`` returns it, and both assert the never-double-assign
    invariant loudly instead of letting two generations silently share
    cache rows."""

    def __init__(self, slots: int):
        if slots < 1:
            raise ValueError(f"need >= 1 slots, got {slots}")
        self.slots = slots
        self._free: List[int] = list(range(slots - 1, -1, -1))
        self._live: set = set()

    @property
    def free_count(self) -> int:
        """Slots currently available for admission."""
        return len(self._free)

    @property
    def live(self) -> FrozenSet[int]:
        """The slots currently owned by in-flight generations."""
        return frozenset(self._live)

    def alloc(self) -> int:
        """Claim the lowest free slot; raises when the cache is full
        (the driver checks ``free_count`` first — admission under a
        full cache queues, it never drops)."""
        if not self._free:
            raise RuntimeError("KV cache is full (no free slots)")
        slot = self._free.pop()
        assert slot not in self._live, \
            f"slot {slot} double-assigned (allocator corrupted)"
        self._live.add(slot)
        return slot

    def free(self, slot: int) -> None:
        """Return a slot to the pool; freeing a slot that is not live
        is an accounting bug and raises."""
        if slot not in self._live:
            raise RuntimeError(
                f"freeing slot {slot} which is not live "
                f"(live={sorted(self._live)})")
        self._live.discard(slot)
        self._free.append(slot)


class KVCache:
    """One model version's preallocated decode cache.

    ``k``/``v`` are tuples of ``layers`` device arrays threaded
    (donated) through every prefill/decode program call; ``lengths`` is
    the explicit host-side int32 vector of per-slot sequence lengths (=
    the next write position), and ``allocator`` the slot bitmap. A freed
    slot's columns are NOT zeroed: every position a future occupant can
    attend is re-written (prompt region by its prefill, each generated
    position by the decode step that produces it) before the
    length-masked causal mask ever exposes it.

    **Entries of a declared kind per layer.** ``layout`` is one ``(kv
    heads, head dim, columns)`` triple a layer; layer ``i``'s arrays are
    ``[slots, heads_i, head_dim_i, columns_i]``. A layer that attends
    its whole prefix keeps ``columns = max_len`` (position ``p`` at
    column ``p``); a sliding-window layer keeps a RING of ``window``
    columns (position ``p`` at column ``p mod window``;
    ``nn.attention.cached_attention`` owns that rule). The layout comes
    from the model: ``cache_layout(max_len)``, the cache's one entry
    point in the engine's contract (``generation/engine.py``)."""

    def __init__(self, slots: int, max_len: int, layout, dtype=None):
        import jax.numpy as jnp

        from bigdl_tpu.utils.engine import Engine

        self.slots = slots
        self.max_len = max_len
        self.dtype = dtype if dtype is not None else Engine.default_dtype()
        self.layout = self._layout(layout, max_len)
        self.layers = len(self.layout)
        # of the first layer's entry: K/V heads, which a grouped-query
        # model has fewer of than the query heads it declares
        self.heads, self.head_dim = self.layout[0][:2]
        self.k = tuple(jnp.zeros((slots,) + e, self.dtype)
                       for e in self.layout)
        self.v = tuple(jnp.zeros((slots,) + e, self.dtype)
                       for e in self.layout)
        self.lengths = np.zeros((slots,), np.int32)
        self.allocator = SlotAllocator(slots)

    @staticmethod
    def _layout(layout, max_len: int) -> tuple:
        layout = tuple(tuple(int(n) for n in e) for e in layout)
        if not layout or any(
                len(e) != 3 or not 1 <= e[2] <= max_len for e in layout):
            raise ValueError(
                f"cache layout {layout} does not describe layers of at "
                f"most {max_len} columns")
        return layout

    @classmethod
    def _model_geometry(cls, model, max_len: int) -> tuple:
        """``(layout, dtype)`` as the model declares them
        (``cache_layout(max_len)``, ``cache_dtype()``), checked against
        its positional bound — ONE derivation shared by
        :meth:`for_model` and :meth:`spec_for_model`, so the verified
        program shapes can never drift from the allocated ones."""
        if max_len > int(model.max_len):
            raise ValueError(
                f"cache max_len={max_len} exceeds the model's positional "
                f"table ({model.max_len})")
        layout = cls._layout(model.cache_layout(max_len), max_len)
        if len(layout) != int(model.num_layers):
            raise ValueError(
                f"cache layout {layout} does not describe "
                f"{model.num_layers} layers")
        return layout, model.cache_dtype()

    @classmethod
    def for_model(cls, model, slots: int, max_len: int,
                  dtype=None) -> "KVCache":
        """Size a cache from what a decoder model declares, e.g. a
        :class:`~bigdl_tpu.models.transformer.TransformerLM`.
        ``dtype`` overrides what the model declares."""
        layout, declared = cls._model_geometry(model, max_len)
        return cls(slots, max_len, layout,
                   dtype if dtype is not None else declared)

    @classmethod
    def spec_for_model(cls, model, slots: int, max_len: int,
                       dtype=None):
        """The ``(k, v)`` buffers :meth:`for_model` would allocate
        (same derivation, same validation), as tuples of ``layers``
        ``jax.ShapeDtypeStruct`` — nothing touches a device. The
        static program verifier lowers the engine's prefill/decode
        jits over these instead of a live cache."""
        import jax

        from bigdl_tpu.utils.engine import Engine

        layout, declared = cls._model_geometry(model, max_len)
        dt = dtype if dtype is not None else (
            declared if declared is not None else Engine.default_dtype())
        spec = tuple(jax.ShapeDtypeStruct((slots,) + e, dt)
                     for e in layout)
        return spec, spec

    @property
    def uniform(self) -> bool:
        """Every layer's entry has the same shape (one kind)."""
        return len(set(self.layout)) == 1

    def kind_bytes(self) -> dict:
        """Device bytes by kind of entry: ``window`` (rings shorter
        than ``max_len``) and ``global`` (every position kept)."""
        out = {"window": 0, "global": 0}
        for e, a, b in zip(self.layout, self.k, self.v):
            kind = "window" if e[2] < self.max_len else "global"
            out[kind] += int(a.nbytes) + int(b.nbytes)
        return out

    def occupancy(self) -> float:
        """Live-slot fraction (the ``cache_occupancy`` gauge)."""
        return 1.0 - self.allocator.free_count / self.slots

    def live_lengths(self) -> np.ndarray:
        """Lengths of the live slots only (host view)."""
        live = sorted(self.allocator.live)
        return self.lengths[live] if live else np.zeros((0,), np.int32)

    def nbytes(self) -> int:
        """Device bytes held by the K and V buffers."""
        return sum(int(a.nbytes) for a in self.k + self.v)

    def __repr__(self) -> str:
        return (f"KVCache(L={self.layers} slots={self.slots} "
                f"H={self.heads} T={self.max_len} D={self.head_dim} "
                f"{np.dtype(self.dtype).name}, "
                f"live={len(self.allocator.live)})")
