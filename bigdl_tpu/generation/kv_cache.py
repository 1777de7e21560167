"""Preallocated, shape-bucketed KV cache + host-side slot accounting.

The decode engine's whole memory story is ONE allocation per model
version: per layer one entry of a declared kind - one K and one V array
``[slots, heads, head_dim, columns]`` (``columns`` is ``max_len``,
already padded to the top rung of the service's length ladder, or a
sliding-window layer's ring), or a recurrent state's named arrays
``[slots, ...]`` with no time axis, or nothing - an explicit per-slot
``lengths`` vector, and a host-side alloc/free bitmap. Requests *occupy slots* — admission is a
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


class RecurrentStateError(ValueError):
    """Raised by a feature that cuts a slot's cache at a position (the
    prefix cache, speculative verify) when it is handed a model with a
    recurrent entry: a state has no columns to cut, and a snapshot of
    it at a position is not built."""


def has_recurrent(layout) -> bool:
    """Whether a ``cache_layout`` names a recurrent entry."""
    return any(e[0] == "state" for e in layout)


def _entry_arrays(entry, dtype) -> dict:
    """``{name: (shape of one slot's row, dtype)}`` of one layout
    entry."""
    if entry[0] == "kv":
        return {"k": (entry[1:], dtype), "v": (entry[1:], dtype)}
    if entry[0] == "state":
        return {name: (shape, dtype if dt is None else np.dtype(dt))
                for name, shape, dt in entry[1]}
    return {}


class KVCache:
    """One model version's preallocated decode cache.

    ``entries`` is a tuple of one dict of device arrays a layer,
    threaded (donated) as ONE pytree through every prefill/decode
    program call; ``lengths`` is the explicit host-side int32 vector of
    per-slot sequence lengths (= the next write position), and
    ``allocator`` the slot bitmap.

    **Entries of a declared kind per layer.** ``layout`` is one tuple a
    layer whose first element names the kind (the model declares it:
    ``cache_layout(max_len)``, the cache's one entry point in the
    engine's contract, ``generation/engine.py``):

    - ``("kv", kv heads, head dim, columns)``: keys and values with a
      time axis, ``{"k", "v"}`` each ``[slots, heads, head_dim,
      columns]``. A layer that attends its whole prefix keeps ``columns
      = max_len`` (position ``p`` at column ``p``); a sliding-window
      layer keeps a RING of ``window`` columns (position ``p`` at column
      ``p mod window``; ``nn.attention.cached_attention`` owns that
      rule). A freed slot's columns are NOT zeroed: every position a
      future occupant can attend is re-written (prompt region by its
      prefill, each generated position by the decode step that produces
      it) before the length-masked causal mask ever exposes it.
    - ``("state", ((name, shape, dtype), ...))``: a recurrent state,
      named arrays ``[slots, *shape]`` without a time axis, each of its
      own dtype (None: the cache's). It is read and rewritten whole
      every step and cannot be cut at a position; a slot handed to a new
      request must start from zero, which the MODEL does (a row at
      offset 0 drops what the slot held), so nothing is zeroed here
      either.
    - ``("none",)``: the layer keeps nothing, ``{}``."""

    def __init__(self, slots: int, max_len: int, layout, dtype=None):
        import jax.numpy as jnp

        from bigdl_tpu.utils.engine import Engine

        self.slots = slots
        self.max_len = max_len
        self.dtype = dtype if dtype is not None else Engine.default_dtype()
        self.layout = self._layout(layout, max_len)
        self.layers = len(self.layout)
        self.entries = tuple(
            {name: jnp.zeros((slots,) + shape, dt) for name, (shape, dt)
             in _entry_arrays(e, self.dtype).items()}
            for e in self.layout)
        # the layers that keep a recurrent state, and the bytes ONE
        # slot's states take over all of them
        states = [e for e, kind in zip(self.entries, self.layout)
                  if kind[0] == "state"]
        self.state_layers = len(states)
        self.state_slot_bytes = sum(int(a.nbytes) // slots
                                    for e in states for a in e.values())
        self.lengths = np.zeros((slots,), np.int32)
        self.allocator = SlotAllocator(slots)

    @staticmethod
    def _layout(layout, max_len: int) -> tuple:
        """The layout as hashable tuples, checked."""
        out = []
        for e in layout:
            kind = e[0] if len(e) else None
            if kind == "kv" and len(e) == 4 \
                    and 1 <= int(e[3]) <= max_len:
                out.append(("kv",) + tuple(int(n) for n in e[1:]))
            elif kind == "state" and len(e) == 2 and len(e[1]) \
                    and not {"k", "v"} & {n for n, _, _ in e[1]}:
                out.append(("state", tuple(
                    (str(name), tuple(int(n) for n in shape),
                     None if dt is None else np.dtype(dt).name)
                    for name, shape, dt in e[1])))
            elif kind == "none" and len(e) == 1:
                out.append(("none",))
            else:
                raise ValueError(
                    f"cache layout entry {e!r} is none of ('kv', heads, "
                    f"head_dim, columns <= {max_len}), ('state', ((name, "
                    "shape, dtype), ...)) with no array named 'k' or 'v' "
                    "(the engine cuts those at a column), ('none',)")
        if not out:
            raise ValueError("an empty cache layout")
        return tuple(out)

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
        """The ``entries`` pytree :meth:`for_model` would allocate
        (same derivation, same validation) as
        ``jax.ShapeDtypeStruct`` leaves — nothing touches a device. The
        static program verifier lowers the engine's prefill/decode
        jits over these instead of a live cache."""
        import jax

        from bigdl_tpu.utils.engine import Engine

        layout, declared = cls._model_geometry(model, max_len)
        dt = dtype if dtype is not None else (
            declared if declared is not None else Engine.default_dtype())
        return tuple(
            {name: jax.ShapeDtypeStruct((slots,) + shape, d)
             for name, (shape, d) in _entry_arrays(e, dt).items()}
            for e in layout)

    # ---- views by kind
    @property
    def k(self) -> tuple:
        """The K arrays of the layers that keep keys and values."""
        return tuple(e["k"] for e in self.entries if "k" in e)

    @property
    def v(self) -> tuple:
        """The V arrays of the same layers."""
        return tuple(e["v"] for e in self.entries if "v" in e)

    @property
    def kv_layout(self) -> tuple:
        """``(kv heads, head dim, columns)`` of each K/V layer."""
        return tuple(e[1:] for e in self.layout if e[0] == "kv")

    @property
    def uniform(self) -> bool:
        """Every layer keeps keys and values of one shape."""
        return len(set(self.layout)) == 1 and self.layout[0][0] == "kv"

    @property
    def recurrent(self) -> bool:
        """Some layer keeps a recurrent state."""
        return has_recurrent(self.layout)

    def kind_bytes(self) -> dict:
        """Device bytes by kind of entry: ``window`` (rings shorter
        than ``max_len``), ``global`` (every position kept) and
        ``state`` (recurrent arrays)."""
        out = {"window": 0, "global": 0, "state": 0}
        for kind, e in zip(self.layout, self.entries):
            if kind[0] == "none":
                continue
            name = "state" if kind[0] == "state" else \
                "window" if kind[3] < self.max_len else "global"
            out[name] += sum(int(a.nbytes) for a in e.values())
        return out

    def occupancy(self) -> float:
        """Live-slot fraction (the ``cache_occupancy`` gauge)."""
        return 1.0 - self.allocator.free_count / self.slots

    def live_lengths(self) -> np.ndarray:
        """Lengths of the live slots only (host view)."""
        live = sorted(self.allocator.live)
        return self.lengths[live] if live else np.zeros((0,), np.int32)

    def nbytes(self) -> int:
        """Device bytes held by every entry's arrays."""
        return sum(int(a.nbytes) for e in self.entries
                   for a in e.values())

    def __repr__(self) -> str:
        kinds = {k: sum(1 for e in self.layout if e[0] == k)
                 for k in ("kv", "state", "none")}
        return (f"KVCache(L={self.layers} {kinds} slots={self.slots} "
                f"T={self.max_len} {np.dtype(self.dtype).name}, "
                f"live={len(self.allocator.live)})")
