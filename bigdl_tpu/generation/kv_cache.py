"""Preallocated, shape-bucketed KV cache + host-side slot accounting.

The decode engine's whole memory story is ONE allocation per model
version: per layer one K and one V array ``[slots, heads, head_dim,
max_len]`` (``max_len`` already padded to the top rung of the
service's length ladder), an explicit per-slot ``lengths`` vector, and
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

    ``k``/``v`` are tuples of ``layers`` device arrays ``[slots, heads,
    head_dim, max_len]`` threaded (donated) through every
    prefill/decode program call; ``lengths`` is the explicit host-side
    int32 vector of per-slot sequence lengths (= the next write
    position), and ``allocator`` the slot bitmap. A freed slot's
    columns are NOT zeroed: every position a future occupant can attend
    is re-written (prompt region by its prefill, each generated
    position by the decode step that produces it) before the
    length-masked causal mask ever exposes it."""

    def __init__(self, layers: int, slots: int, heads: int, max_len: int,
                 head_dim: int, dtype=None):
        import jax.numpy as jnp

        from bigdl_tpu.utils.engine import Engine

        self.layers = layers
        self.slots = slots
        self.heads = heads
        self.max_len = max_len
        self.head_dim = head_dim
        self.dtype = dtype if dtype is not None else Engine.default_dtype()
        shape = (slots, heads, head_dim, max_len)
        self.k = tuple(jnp.zeros(shape, self.dtype)
                       for _ in range(layers))
        self.v = tuple(jnp.zeros(shape, self.dtype)
                       for _ in range(layers))
        self.lengths = np.zeros((slots,), np.int32)
        self.allocator = SlotAllocator(slots)

    @classmethod
    def _model_geometry(cls, model, slots: int, max_len: int) -> tuple:
        """The ``(layers, slots, heads, max_len, head_dim)`` cache
        geometry (the constructor's arguments) a decoder model's
        declared geometry (``num_layers``/``num_heads``/``head_dim`` or
        ``hidden_size``) implies — ONE
        derivation (and positional-table bound) shared by
        :meth:`for_model` and :meth:`spec_for_model`, so the verified
        program shapes can never drift from the allocated ones."""
        layers = int(model.num_layers)
        heads = int(model.num_heads)
        head_dim = int(getattr(model, "head_dim",
                               model.hidden_size // heads))
        if max_len > int(getattr(model, "max_len", max_len)):
            raise ValueError(
                f"cache max_len={max_len} exceeds the model's positional "
                f"table ({model.max_len})")
        return (layers, slots, heads, max_len, head_dim)

    @classmethod
    def for_model(cls, model, slots: int, max_len: int,
                  dtype=None) -> "KVCache":
        """Size a cache from a decoder model's declared geometry,
        e.g. a :class:`~bigdl_tpu.models.transformer.TransformerLM`."""
        return cls(*cls._model_geometry(model, slots, max_len), dtype)

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

        layers, slots, heads, max_len, head_dim = cls._model_geometry(
            model, slots, max_len)
        dt = dtype if dtype is not None else Engine.default_dtype()
        layer = jax.ShapeDtypeStruct((slots, heads, head_dim, max_len),
                                     dt)
        return (layer,) * layers, (layer,) * layers

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
