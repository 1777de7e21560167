"""Prefix/KV reuse: skip prefill for prompts the fleet has seen before.

Production prompt traffic is massively redundant — the same system
prompt, the same few-shot template, thousands of times a second. The
prefill that re-computes that shared prefix's K/V rows is pure waste:
its result is a deterministic function of (model version, prefix
tokens). This module caches that result as **committed KV blocks**:

- :meth:`PrefixCache.insert` stores, per ``(servable version, prompt)``
  key, the prompt's K/V rows (a device copy sliced out of the slot the
  prefill just wrote, padded to the prompt's ladder rung so seeding
  shapes stay bucketed) plus the prefill's first-token logits row;
- :meth:`PrefixCache.lookup` answers an admission with the entry — the
  decode loop then **seeds** the slot's cache rows by device copy
  (:meth:`seed`) and goes straight to decode: a full-prefix hit's TTFT
  approaches one decode step, because that is all that remains;
- :meth:`PrefixCache.lookup_prefix` is the long-context PARTIAL probe:
  under chunked prefill the loop sizes cached blocks at prefill-chunk
  boundaries, so a long shared system prompt hits here even when the
  full prompt differs — the entry seeds the covered chunks and the
  engine prefills only the remainder (``start=``);
- the cache is **reference-counted and capacity-bounded**: a lookup
  pins its entry until the reading slot is released, eviction is LRU
  over refcount-zero entries only, and an insert that cannot fit after
  evicting every unpinned entry is refused rather than growing past
  ``max_bytes`` (this class is the sanctioned fixture for the
  ``unbounded-cache-growth`` lint rule — a serving-surface cache must
  carry its eviction with it).

Correctness: the stored rows are exactly the bytes the slot's own
prefill committed, and rows beyond the prompt length are never
attended (the engine's length-masked causal attention), so a seeded
slot's greedy stream is bit-identical to the cold-path stream
(asserted in tests/test_fleet.py).
"""
from __future__ import annotations

import functools
import hashlib
import itertools
import threading
from collections import OrderedDict
from typing import Dict, Optional

import numpy as np

import bigdl_tpu.telemetry as telemetry


def _refuse(kv) -> None:
    """A cache this store cannot cut blocks out of, by type."""
    from bigdl_tpu.generation.kv_cache import RecurrentStateError

    if kv.recurrent:
        raise RecurrentStateError(
            "the prefix cache stores a prompt's K/V columns up to a "
            "position; a recurrent state has no columns, and a snapshot "
            "of it at that position is not built")
    raise ValueError(
        f"the prefix cache stores one kind of K/V block; this cache "
        f"keeps {sorted(set(kv.layout))}")


@functools.lru_cache(maxsize=64)
def _seed_program(cache_shape, dtype_str, rung):
    """The donated seed-copy program for one (cache geometry, rung):
    splices an entry's K/V blocks into one slot's rows of every
    layer's array IN PLACE (donated buffers — no full-cache copy per
    hit). One compile per rung per geometry, bounded by the ladder;
    cached here rather than per-instance so every PrefixCache sharing
    a geometry shares the executable."""
    import jax

    def splice(layers, entry, slot):
        return tuple(
            jax.lax.dynamic_update_slice(a, entry[i][None],
                                         (slot, 0, 0, 0))
            for i, a in enumerate(layers))

    def fn(k, v, ek, ev, slot):
        return splice(k, ek, slot), splice(v, ev, slot)

    return jax.jit(fn, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=64)
def _extract_program(rung):
    """The extract-copy program for one rung (jit keys the cache
    geometry itself): one slot's first ``rung`` columns of every
    layer's array, stacked ``[layers, heads, head_dim, rung]`` — one
    dispatch per admission, not one per layer."""
    import jax
    import jax.numpy as jnp

    def rows(layers, slot):
        return jnp.stack([
            jax.lax.dynamic_index_in_dim(a, slot, 0, keepdims=False)
            [..., :rung] for a in layers])

    return jax.jit(lambda k, v, slot: (rows(k, slot), rows(v, slot)))


def register_prefix_instruments(r) -> Dict[str, object]:
    """Get-or-create the ``fleet/prefix/*`` instrument surface in
    registry ``r`` (audited by ``tools.check --telemetry-audit``)."""
    return {
        "hits": r.counter(
            "fleet/prefix/hits", "admissions seeded from a cached prefix "
            "(prefill skipped entirely)"),
        "misses": r.counter(
            "fleet/prefix/misses", "admissions that ran a cold prefill"),
        "partial_hits": r.counter(
            "fleet/prefix/partial_hits",
            "admissions seeded from a chunk-boundary prefix (only the "
            "remaining chunks prefilled)"),
        "inserts": r.counter(
            "fleet/prefix/inserts", "prefix entries committed to the cache"),
        "evictions": r.counter(
            "fleet/prefix/evictions",
            "refcount-zero prefix entries evicted (LRU) to fit an insert"),
        "bytes": r.gauge(
            "fleet/prefix/bytes", "device bytes held by cached KV blocks"),
        "entries": r.gauge(
            "fleet/prefix/entries", "prefix entries resident in the cache"),
    }


class PrefixEntry:
    """One cached prefix: committed K/V blocks + first-token logits.

    ``k``/``v`` are device arrays ``[layers, heads, head_dim, rung]``
    (time last, as the cache keeps it; ``rung`` = the prompt's ladder
    bucket — padded so every seeding copy runs at a bucketed shape),
    ``length`` the real prefix length,
    ``logits`` the host ``[V]`` first-token logits row the prefill
    computed — or ``None`` for a chunk-BOUNDARY entry, whose tokens
    end mid-prompt so no first-token row exists; such entries serve
    only :meth:`PrefixCache.lookup_prefix` (the exact-match
    :meth:`~PrefixCache.lookup` skips them). ``refs`` counts live
    readers; the cache never evicts an entry with ``refs > 0``."""

    __slots__ = ("key", "version_key", "length", "rung", "k", "v",
                 "logits", "nbytes", "refs", "tick", "doomed")

    def __init__(self, key, version_key, length, rung, k, v, logits):
        self.key = key
        self.version_key = version_key
        self.length = int(length)
        self.rung = int(rung)
        self.k = k
        self.v = v
        self.logits = None if logits is None else np.asarray(logits)
        self.nbytes = (int(k.nbytes) + int(v.nbytes)
                       + (0 if self.logits is None
                          else self.logits.nbytes))
        self.refs = 0
        self.tick = 0       # LRU clock (deterministic, not wall time)
        self.doomed = False  # version unloaded while pinned: drop at 0


class PrefixCache:
    """Reference-counted, capacity-bounded LRU cache of committed KV
    blocks (module docstring has the contract). Thread-safe: decode
    loops of several models (or replicas sharing a service) call
    ``lookup``/``insert``/``release`` concurrently."""

    def __init__(self, max_bytes: int, metrics=None):
        if max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, PrefixEntry]" = OrderedDict()
        self._bytes = 0
        self._clock = itertools.count(1)
        r = metrics if metrics is not None else telemetry.registry()
        inst = register_prefix_instruments(r)
        self._c_hits = inst["hits"]
        self._c_misses = inst["misses"]
        self._c_partial_hits = inst["partial_hits"]
        self._c_inserts = inst["inserts"]
        self._c_evictions = inst["evictions"]
        self._g_bytes = inst["bytes"]
        self._g_entries = inst["entries"]

    # ------------------------------------------------------------ keys
    @staticmethod
    def key_for(version_key, tokens) -> str:
        """The cache key: a digest over the servable version AND the
        prefix tokens — programs (and therefore K/V bytes) are never
        shared across versions, so neither are cached blocks."""
        h = hashlib.sha256(repr(tuple(version_key)).encode())
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.hexdigest()

    # ---------------------------------------------------------- lookup
    def lookup(self, version_key, tokens, **labels) -> Optional[PrefixEntry]:
        """The admission-time probe: a full-prefix hit returns the
        entry PINNED (``refs`` incremented — the caller must
        :meth:`release` when the reading slot frees); a miss returns
        None. Counts ``fleet/prefix/hits``/``misses``."""
        entry = self._probe(self.key_for(version_key, tokens),
                            full=True)
        if entry is None:
            self._c_misses.inc(**labels)
            return None
        self._c_hits.inc(**labels)
        return entry

    def _probe(self, key: str, full: bool) -> Optional[PrefixEntry]:
        """One pinned probe. ``full`` probes skip logits-less
        chunk-boundary entries (they cannot provide the first
        token)."""
        with self._lock:
            entry = self._entries.get(key)
            # capture the verdict INSIDE the lock: a concurrent
            # drop_version may doom the entry right after we pinned
            # it, and re-reading entry.doomed outside would leak the
            # pin (an unevictable entry forever)
            hit = (entry is not None and not entry.doomed
                   and not (full and entry.logits is None))
            if hit:
                entry.refs += 1
                entry.tick = next(self._clock)
                self._entries.move_to_end(key)
        return entry if hit else None

    def lookup_prefix(self, version_key, tokens, chunk: int, **labels):
        """The long-context partial probe: the LONGEST cached prefix
        of ``tokens`` ending on a ``chunk`` boundary strictly inside
        the prompt, as a pinned ``(entry, boundary)`` pair — the loop
        seeds the covered rows and prefills only from ``boundary`` on
        (``DecodeEngine.prefill(start=...)``). None when no boundary
        prefix is cached. Counts ``fleet/prefix/partial_hits`` (the
        full-prompt miss was already counted by :meth:`lookup`)."""
        n = len(tokens)
        for m in range((n - 1) // chunk, 0, -1):
            entry = self._probe(
                self.key_for(version_key, tokens[:m * chunk]),
                full=False)
            if entry is not None:
                self._c_partial_hits.inc(**labels)
                return entry, m * chunk
        return None

    def release(self, entry: PrefixEntry) -> None:
        """Unpin one reader (the slot that seeded from this entry was
        released). A doomed entry (its version unloaded while pinned)
        is dropped once its last reader lets go."""
        with self._lock:
            entry.refs -= 1
            assert entry.refs >= 0, \
                f"prefix entry {entry.key[:8]} over-released"
            if entry.doomed and entry.refs == 0 \
                    and entry.key in self._entries:
                self._drop_locked(entry.key)

    # ---------------------------------------------------------- insert
    def insert(self, version_key, tokens, k_rows, v_rows, logits,
               **labels) -> Optional[PrefixEntry]:
        """Commit one prefix's KV blocks (device copies the caller
        sliced out of the freshly prefilled slot) + first-token logits
        (``None`` for a chunk-boundary entry — partial-probe only).
        Evicts LRU refcount-zero entries until the new entry fits;
        refused (returns None) when even a full sweep of unpinned
        entries cannot make room — the cache NEVER exceeds
        ``max_bytes`` and never frees blocks a live slot still
        reads."""
        key = self.key_for(version_key, tokens)
        rung = int(k_rows.shape[3])
        entry = PrefixEntry(key, tuple(version_key), len(tokens), rung,
                            k_rows, v_rows, logits)
        evicted, committed = 0, None
        with self._lock:
            if key in self._entries:
                # a concurrent admission already committed this prefix
                self._entries[key].tick = next(self._clock)
                self._entries.move_to_end(key)
                return self._entries[key]
            if entry.nbytes <= self.max_bytes:
                while self._bytes + entry.nbytes > self.max_bytes:
                    victim = next((k for k, e in self._entries.items()
                                   if e.refs == 0), None)
                    if victim is None:
                        break  # every resident entry is pinned: refuse
                    self._drop_locked(victim)
                    evicted += 1
                if self._bytes + entry.nbytes <= self.max_bytes:
                    entry.tick = next(self._clock)
                    self._entries[key] = entry
                    self._bytes += entry.nbytes
                    self._g_bytes.set(self._bytes)
                    self._g_entries.set(len(self._entries))
                    committed = entry
        if evicted:
            self._c_evictions.inc(evicted, **labels)
        if committed is not None:
            self._c_inserts.inc(**labels)
        return committed

    def _drop_locked(self, key: str) -> None:
        entry = self._entries.pop(key)
        self._bytes -= entry.nbytes
        self._g_bytes.set(self._bytes)
        self._g_entries.set(len(self._entries))

    # --------------------------------------------------------- version
    def drop_version(self, version_key) -> int:
        """Drop every entry of an unloaded servable version. Pinned
        entries are doomed instead (their blocks stay valid for the
        slots still reading them) and fall out at the last
        :meth:`release`. Returns how many entries dropped now."""
        vk = tuple(version_key)
        dropped = 0
        with self._lock:
            for key in [k for k, e in self._entries.items()
                        if e.version_key == vk]:
                entry = self._entries[key]
                if entry.refs > 0:
                    entry.doomed = True
                else:
                    self._drop_locked(key)
                    dropped += 1
        return dropped

    # ---------------------------------------------------- seed/extract
    @staticmethod
    def extract(kv, slot: int, rung: int):
        """Device-copy the committed K/V blocks out of a freshly
        prefilled slot: ``[layers, heads, head_dim, rung]`` for K and
        V. Columns past the real prompt length ride along (the rung
        pads them) but are never attended."""
        if not kv.uniform:
            _refuse(kv)
        return _extract_program(int(rung))(kv.k, kv.v, np.int32(slot))

    @staticmethod
    def seed(kv, slot: int, entry: PrefixEntry) -> None:
        """Seed one slot from a cached entry by device copy — the hit
        path's whole data plane: the slot's first ``rung`` cache rows
        become the committed blocks and ``lengths[slot]`` the prefix
        length, exactly the state a cold prefill would have left. The
        copy runs as a donated compiled splice (no full-cache copy),
        so a full-prefix hit's TTFT is one dynamic_update_slice plus
        the first decode step."""
        if not kv.uniform:
            _refuse(kv)
        fn = _seed_program((kv.layers,) + kv.k[0].shape,
                           str(np.dtype(kv.dtype)), entry.rung)
        k, v = fn(kv.k, kv.v, entry.k, entry.v, np.int32(slot))
        kv.entries = tuple({"k": a, "v": b} for a, b in zip(k, v))
        kv.lengths[slot] = entry.length

    # ------------------------------------------------------- introspect
    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def nbytes(self) -> int:
        """Device bytes currently held by cached blocks."""
        with self._lock:
            return self._bytes

    def pinned(self) -> int:
        """Entries with live readers (never evictable right now)."""
        with self._lock:
            return sum(1 for e in self._entries.values() if e.refs > 0)

    def stats(self) -> Dict[str, float]:
        """Point-in-time cache stats (host view)."""
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "pinned": sum(1 for e in self._entries.values()
                                  if e.refs > 0),
                    "max_bytes": self.max_bytes}
