"""Compiled prefill/decode program pairs, bucketed by sequence length.

The XLA serving lesson (TensorFlow paper §4.4) applied to
autoregression: a naive decode loop re-traces every time the sequence
grows — one compile *per token*. This engine pins every shape instead:

- **prefill** runs at ``[prefill_rows, S_b]`` for a prompt-length
  bucket ``S_b`` from the service's :class:`~bigdl_tpu.serving.
  compile_cache.BucketLadder` — the padded-prompt batch computes the
  prompt's K/V rows *and* the first-token logits in one program, and
  scatters the rows straight into the big cache (out-of-bounds slot
  ids are dropped, which is how padding rows write nothing);
- **decode** runs at ``[slots]`` — one token per slot per step, the
  cache donated through — with attention restricted to the first
  ``T_b`` cache positions for a length bucket ``T_b``, so short
  sequences never scan the whole preallocated ``max_len``.

The cache every program takes and returns is
:class:`~bigdl_tpu.generation.kv_cache.KVCache`'s own form, ONE pytree:
a tuple of one entry a layer, each a dict of arrays of the kind the
model declared — keys and values ``{"k", "v"}`` ``[slots, heads,
head_dim, columns]`` (time last: ``head_dim`` 64 on the lanes would pad
2x), a recurrent state's named arrays ``[slots, ...]`` with no time
axis, or ``{}`` — donated and written in place: a decode step holds no
copy of it. A prefill scatters its rows' entries back by slot id (the
column slice only where an entry has columns). What it hands the model
depends on the call: a chunked call (a ``prefill_chunk`` piece, a rung
the engine cut into pieces itself, a start past a seeded prefix) gathers
the rows from their slots, because chunk ``c`` attends what chunks
``0..c-1`` wrote; a one-shot (``fresh``) prefill reads NOTHING of the
cache and makes its rows in the program, zeros of the gathered rows'
shapes (nothing attends what the slot held, and a gather of four rows
cost a copy of every layer's whole K and V).

**A prefill's shape follows from the model.** A rung whose one-shot
``[prefill_rows, heads, rung, rung]`` float32 scores would pass
``_PREFILL_SCORE_BYTES`` is filled by the engine itself in ``[1,
chunk]`` pieces through the same per-rung program (``prefill_shape``):
no option asks for it, and a rung that fits keeps the program it had.
A model whose layers keep different cache entries
(``KVCache.layout``: rings for window layers, states for state-space
layers) has each layer's entry gathered, attended and written back at
its own width (``min(rung, columns)``; a state whole). Every program also returns the model's
``MOE_STATS_KEY`` state leaves (``[expert layers, 3]``; nothing for a
model without experts), recorded on the host with the logits.

K ladder rungs ⇒ at most K prefill + K decode = **2K compiled
programs** per model version, warmed eagerly as pairs by
:meth:`DecodeEngine.warmup` and counted — not trusted — through the
shared :class:`~bigdl_tpu.serving.compile_cache.CompileCache` compile
counter the serving tests already assert against.

**What a decoder must provide to be served** (the whole contract;
``TransformerLM`` and ``PatternDecoderLM`` meet it, and the programs
below ask a model nothing else - no signature is inspected, no
attribute has a default):

- ``apply(params, state, tokens, *, training, cache, positions,
  attend_len, logits_at=None, live=None, fresh=False) -> (logits,
  state, cache)``: one cached step over ``tokens [B, S]``. ``cache`` is
  a tuple of one entry a layer, ``B`` rows of ``cache_layout``'s
  arrays (K/V entries cut to the rung's columns), returned in the same
  form; ``positions`` (int32 ``[B]``) each row's write offset;
  ``attend_len`` (static) the rung. ``logits_at`` (int32 ``[B]``):
  return that one new position's logits a row, ``[B, 1, V]``, the
  tokens past it being padding; without it ``[B, S, V]``. ``live``
  (bool ``[B]``): False for rows that are padding or free decode slots
  (they may compute garbage; a live row never reads another's).
  ``fresh`` (static): a one-shot prefill, every offset 0 and nothing of
  the rows cached yet. A recurrent entry obeys the same arguments: a
  row at offset 0 (every row of a ``fresh`` call) starts from a ZERO
  state whatever the slot held, a row at a later offset continues from
  its entry, and the state stops at the row's last real token
  (``logits_at``).
- ``cache_layout(max_len)``: one entry a layer naming its kind —
  ``("kv", kv heads, head dim, columns)``, ``("state", ((name, shape,
  dtype), ...))`` or ``("none",)`` (``KVCache`` has their meaning);
  ``cache_dtype()`` (None: the default type); ``scoreless_prefill(rung)
  -> bool``: whether the layers that attend let ``rung`` fresh tokens
  attend each other without ``[rung, rung]`` scores.
- the attributes ``num_layers``, ``num_heads``, ``max_len``,
  ``vocab_size``.

Speculative decoding (``bigdl_tpu.fleet.speculative``) adds one
**verify** program per rung — ``[slots, w]`` draft tokens through the
same cached incremental forward, adjudicated host-side — growing the
documented bound to **at most 3 programs per (version, bucket)**
(prefill, decode, verify), asserted structurally at registration and
via the compile counter in tests/test_fleet.py. A verify step rewinds a
slot to the last accepted position, which a recurrent entry cannot do:
``verify_program`` refuses such a model with
:class:`~bigdl_tpu.generation.kv_cache.RecurrentStateError`.
"""
from __future__ import annotations

import functools
import threading
from collections import Counter
from typing import Dict, Sequence, Set, Tuple

import numpy as np

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache
from bigdl_tpu.generation.kv_cache import (KVCache, RecurrentStateError,
                                           has_recurrent)
from bigdl_tpu.kernels.ragged_decode import block_columns, kv_tile

#: float32 attention scores one prefill call may hold, ``rows x heads x
#: tokens x rung x 4`` bytes: past it the engine chunks the rung
_PREFILL_SCORE_BYTES = 512 << 20

_H_TOUCHED = telemetry.histogram(
    "serving/moe/experts_touched",
    "experts touched by a decode step, of those held, mean over the "
    "expert layers")
_H_PAIRS = telemetry.histogram(
    "serving/moe/local_pairs",
    "token-expert pairs of a decode step that fell on held experts, "
    "summed over the expert layers")
_H_PAIRS_MAX = telemetry.histogram(
    "serving/moe/pairs_per_expert_max",
    "the most pairs one expert took in a decode step, over the expert "
    "layers")


def _moe_stats(state):
    """The model's ``MOE_STATS_KEY`` leaves stacked ``[expert layers,
    3]`` in block order, or None: what a program returns beside its
    logits."""
    import jax.numpy as jnp

    from bigdl_tpu.nn.moe import MOE_STATS_KEY

    found = []

    def walk(node):
        if isinstance(node, dict):
            for key in sorted(node, key=lambda k: (len(k), k)):
                if key == MOE_STATS_KEY:
                    found.append(node[key])
                else:
                    walk(node[key])
    walk(state)
    return jnp.stack(found) if found else None


def _record_moe(stats, kind: str) -> None:
    """One program call's expert statistics into the always-on
    histograms (decode steps) and, with the span tracer on, into a ring
    record that carries the call's totals (``serving/moe/step``: what a
    traced window sums, decode and prefill alike)."""
    if stats is None:
        return
    stats = np.asarray(stats)
    touched, pairs, most = (float(stats[:, 0].sum()),
                            float(stats[:, 1].sum()),
                            float(stats[:, 2].max()))
    if kind == "decode":
        _H_TOUCHED.observe(touched / len(stats))
        _H_PAIRS.observe(pairs)
        _H_PAIRS_MAX.observe(most)
    if telemetry.enabled():
        telemetry.tracer().record(
            "serving/moe/step", 0.0,
            args={"kind": kind, "layers": len(stats),
                  "experts_touched": touched, "local_pairs": pairs,
                  "pairs_per_expert_max": most})


def _record_kv(model, kv: KVCache, positions, active,
               attend_len: int, kernel_layers: int = 0) -> None:
    """With the span tracer on, one decode step's cache columns into a
    ring record (``serving/decode/kv``): ``valid_columns``, the columns
    the live slots attend summed over the layers (``c``, or ``min(c,
    window)`` in a ring), and ``fetched_columns``, the same rounded up
    to the whole tiles the ragged decode kernel fetches, by its own
    tile function; ``written_columns``, the new columns the step writes
    (one a live slot and layer), and ``kernel_written_columns``, those
    of the ``kernel_layers`` layers whose decode kernel wrote them
    itself (counted when the step's program was traced). Host
    arithmetic on the lengths vector only."""
    if not telemetry.enabled() or not kv.kv_layout:
        return
    c = positions[active].astype(np.int64) + 1
    itemsize = kv.k[0].dtype.itemsize
    valid = fetched = 0
    for (heads, d, columns), layers in Counter(kv.kv_layout).items():
        n = np.minimum(c, columns)
        tile = kv_tile(block_columns(columns, min(attend_len, columns)),
                       d, int(model.num_heads) // heads, itemsize)
        valid += layers * int(n.sum())
        fetched += layers * int((-(-n // tile) * tile).sum())
    telemetry.tracer().record(
        "serving/decode/kv", 0.0,
        args={"valid_columns": valid, "fetched_columns": fetched,
              "written_columns": len(c) * len(kv.kv_layout),
              "kernel_written_columns": len(c) * kernel_layers})


def _record_prefill_kv(kv: KVCache, rows: int, attend_len: int,
                       fresh: bool) -> None:
    """With the span tracer on, one prefill call's rows into a ring
    record (``serving/prefill/kv``): ``rows_bytes``, the bytes of the
    call's ``rows`` rows over every entry as the model is handed them
    (K/V cut to the rung's columns, a state whole: :func:`_gather`'s
    shapes), and ``unread_bytes``, those of them the program made
    itself instead of reading them from the cache - all of them for a
    ``fresh`` program, none for a chunk. Host arithmetic on shapes
    only."""
    if not telemetry.enabled():
        return
    rows_bytes = rows * sum(
        a.nbytes // kv.slots // a.shape[3] * min(attend_len, a.shape[3])
        if n in ("k", "v") else a.nbytes // kv.slots
        for e in kv.entries for n, a in e.items())
    telemetry.tracer().record(
        "serving/prefill/kv", 0.0,
        args={"rows_bytes": rows_bytes,
              "unread_bytes": rows_bytes if fresh else 0})


def _record_ssm(kv: KVCache, rows: int, kind: str) -> None:
    """With the span tracer on, one program call's recurrent-state
    traffic into a ring record (``serving/ssm/step``): ``slot_layers``,
    the live rows times the layers that keep a state, and
    ``state_bytes``, what the call must read AND write of them (every
    array of those rows' states, twice). Host arithmetic only; nothing
    for a model without such layers."""
    if not telemetry.enabled() or not kv.state_layers:
        return
    telemetry.tracer().record(
        "serving/ssm/step", 0.0,
        args={"kind": kind, "slot_layers": rows * kv.state_layers,
              "state_bytes": 2 * rows * kv.state_slot_bytes})


def _gather(cache, ids, attend_len: int):
    """Rows ``ids`` of every entry: K/V cut to the rung's columns, a
    state whole."""
    return tuple(
        {n: a[ids, :, :, :min(attend_len, a.shape[3])] if n in ("k", "v")
         else a[ids] for n, a in e.items()} for e in cache)


def _blank_rows(cache, ids, attend_len: int):
    """Zeros of :func:`_gather`'s shapes and dtypes: the rows of a
    ``fresh`` prefill, which reads nothing of the cache."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda c, i: _gather(c, i, attend_len), cache, ids))


def _scatter(cache, ids, rows, attend_len: int):
    """:func:`_gather`'s inverse; out-of-range ids are dropped."""
    return tuple(
        {n: a.at[ids, :, :, :min(attend_len, a.shape[3])].set(
            r[n], mode="drop") if n in ("k", "v")
         else a.at[ids].set(r[n], mode="drop") for n, a in e.items()}
        for e, r in zip(cache, rows))


class DecodeEngine:
    """Per-servable prefill/decode programs over one length ladder.

    Stateless apart from the program handles it registers in the
    shared :class:`CompileCache` (keys ``servable.key + ("prefill",
    S_b)`` / ``+ ("decode", T_b)``); the caller owns the
    :class:`KVCache` buffers and threads them through."""

    def __init__(self, cache: CompileCache, ladder: BucketLadder,
                 slots: int, prefill_rows: int,
                 prefill_chunk: int = None):
        self.cache = cache
        self.ladder = ladder
        self.slots = slots
        self.prefill_rows = prefill_rows
        # chunked prefill (long-context serving): prompts whose ladder
        # rung exceeds ``prefill_chunk`` prefill in fixed [rows, chunk]
        # pieces against that rung's attend window instead of one
        # [rows, rung] shot — same ONE prefill program per rung (the
        # chunk width is the program's token shape), so the ≤ 2/3-per-
        # bucket compile bound is untouched and a 128K prompt never
        # mints a 128K-wide program. Admission rule: the chunk must
        # divide every larger rung, else chunk starts would drift off
        # the attend window (docs/performance.md "Long context").
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk={prefill_chunk} "
                                 f"must be >= 1")
            for rung in ladder:
                if rung > prefill_chunk and rung % prefill_chunk:
                    raise ValueError(
                        f"prefill_chunk={prefill_chunk} must divide "
                        f"every larger ladder rung (rung {rung})")
        self.prefill_chunk = prefill_chunk
        # program keys registered per servable key, so unload can drop
        # exactly the programs this engine created; guarded — the
        # decode-loop thread registers while metrics readers iterate
        self._lock = threading.Lock()
        self._keys: Dict[Tuple, Set[Tuple]] = {}
        # per decode program key: the layers whose decode kernel also
        # wrote the step's cache column, counted when it was traced
        self._kernel_layers: Dict[Tuple, int] = {}

    # ------------------------------------------------------- programs
    # items one program call processes (program-profile MFU basis):
    # prefill computes rows x bucket prompt tokens, decode one token
    # per slot — both read the tokens operand (positional arg 3)
    _PROFILE_ITEMS = {
        "prefill": lambda args, kwargs: (args[3].shape[0]
                                         * args[3].shape[1]),
        "decode": lambda args, kwargs: args[3].shape[0],
        "verify": lambda args, kwargs: (args[3].shape[0]
                                        * args[3].shape[1]),
    }

    #: the full program-kind vocabulary per ladder rung — the
    #: documented ≤ 3-programs-per-(version, bucket) bound
    _KINDS = frozenset({"prefill", "decode", "verify"})

    def _program(self, servable, kind: str, bucket: int, build):
        key = servable.key + (kind, bucket)
        prog = self.cache.program_for(
            key, build, profile_items=self._PROFILE_ITEMS.get(kind))
        with self._lock:
            keys = self._keys.setdefault(servable.key, set())
            keys.add(key)
            kinds = {k[-2] for k in keys if k[-1] == bucket}
            assert kinds <= self._KINDS and len(kinds) <= 3, \
                (f"program kinds {sorted(kinds)} for bucket {bucket} "
                 f"break the ≤3-per-(version, bucket) bound")
        return prog

    @staticmethod
    def _prefill_jit(model, attend_len: int, on_trace,
                     fresh: bool = False):
        """The raw prefill jit (donated cache) — shared by the cached
        :meth:`prefill_program` and the :meth:`abstract_programs`
        verification hook, so both see the identical program.

        Offset-aware: ``tokens [Bp, Sq]`` is one CHUNK of each row's
        prompt, placed at per-row cache position ``offsets`` with
        attention over the first ``attend_len`` cache lanes. In a
        chunked call (not ``fresh``) each row's earlier chunks are
        gathered from its slot's cache rows, so chunk ``c`` attends
        everything chunks ``0..c-1`` wrote. Single-shot prefill is the
        ``offsets == 0, Sq == attend_len`` special case, and ``fresh``
        (static) says so, to the model - the new tokens attend only
        each other, through a flash kernel where one fits - and to
        this program, which then reads NOTHING of the cache: every
        column the model is handed it overwrites or leaves past the
        row's length, and a recurrent entry starts from zero at offset
        0, so the rows are zeros made here (:func:`_blank_rows`) and
        only the scatter touches the donated entries. (A gather of
        ``prefill_rows`` 4 rows compiled to copies of every layer's
        whole K and V, 10 of a 26 ms GPT-2 prefill call: PERF.md, PR
        37.)"""
        import jax
        import jax.numpy as jnp

        def serving_prefill(params, state, cache, tokens, last_in_chunk,
                            slot_ids, offsets):
            on_trace()
            ids = slot_ids.astype(jnp.int32)
            last_at = last_in_chunk.astype(jnp.int32) - 1
            # each row's slot window, layer by layer, as wide as the
            # layer's entry reaches into the rung: gathered for a chunk
            # (OOB padding rows clamp to the last slot; their garbage
            # output is never read and their write-back below is
            # dropped), blank for a one-shot prompt
            with jax.named_scope("attn/kv_write"):
                rows = (_blank_rows if fresh else _gather)(
                    cache, ids, attend_len)
            logits, new_state, rows = model.apply(
                params, state, tokens, training=False, cache=rows,
                positions=offsets.astype(jnp.int32),
                attend_len=attend_len, logits_at=last_at,
                live=ids < jax.tree.leaves(cache)[0].shape[0],
                fresh=fresh)
            with jax.named_scope("attn/kv_write"):
                cache = _scatter(cache, ids, rows, attend_len)
            return logits[:, 0, :], cache, _moe_stats(new_state)

        return jax.jit(serving_prefill, donate_argnums=(2,))

    @staticmethod
    def _decode_jit(model, attend_len: int, on_trace,
                    kernel_wrote=lambda layers: None):
        """The raw decode-step jit for length bucket ``attend_len``
        (donated cache) — shared like :meth:`_prefill_jit`. Each trace
        tells ``kernel_wrote`` in how many layers the decode kernel
        took the cache write with it (the dispatch's ``decode_write``
        count)."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.kernels.dispatch import taken_in_thread

        def serving_decode(params, state, cache, tokens, positions, active):
            on_trace()
            pos = jnp.where(active, positions.astype(jnp.int32), 0)
            before = taken_in_thread("decode_write")
            logits, new_state, cache = model.apply(
                params, state, tokens[:, None], training=False,
                cache=cache, positions=pos,
                attend_len=attend_len, live=active)
            kernel_wrote(taken_in_thread("decode_write") - before)
            logits = logits[:, 0, :]
            # the greedy token of every slot rides along: a step whose
            # requests are all greedy copies [slots] ids to the host,
            # not the [slots, V] logits (ties to the lowest id, as
            # np.argmax on the host breaks them)
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, cache, _moe_stats(new_state), ids

        return jax.jit(serving_decode, donate_argnums=(2,))

    @staticmethod
    def _verify_jit(model, attend_len: int, on_trace):
        """The raw speculative-verify jit for length bucket
        ``attend_len`` (donated cache) — ``w`` draft tokens per slot
        through ONE cached incremental forward, shared by
        :meth:`verify_program` and :meth:`abstract_programs`."""
        import jax
        import jax.numpy as jnp

        def serving_verify(params, state, cache, tokens, positions, active):
            on_trace()
            pos = jnp.where(active, positions.astype(jnp.int32), 0)
            logits, new_state, cache = model.apply(
                params, state, tokens, training=False, cache=cache,
                positions=pos, attend_len=attend_len, live=active)
            return logits, cache, _moe_stats(new_state)

        return jax.jit(serving_verify, donate_argnums=(2,))

    def prefill_program(self, servable, bucket: int):
        """The compiled prefill for prompt bucket ``bucket``:
        ``(params, state, cache, tokens[Bp,Sq], last_in_chunk[Bp],
        slot_ids[Bp], offsets[Bp]) -> (logits[Bp,V], cache', expert
        counts)`` with the cache (one entry a layer) donated. ``Sq`` is the
        bucket itself, or the engine's ``prefill_chunk`` for larger
        rungs — ONE token shape per rung either way, so chunking never
        adds a program. Padding rows
        carry ``slot_ids == slots`` (out of bounds): their scatter
        is dropped and their logits row is garbage the driver never
        reads."""
        model = servable.model
        fresh = self.prefill_shape(model, bucket)[1] == bucket
        return self._program(
            servable, "prefill", bucket,
            lambda on_trace: self._prefill_jit(model, bucket, on_trace,
                                               fresh))

    def chunk_for(self, bucket: int) -> int:
        """The prefill token width for ``bucket`` under the configured
        ``prefill_chunk``: the bucket itself, or the fixed chunk for
        rungs past it."""
        if self.prefill_chunk is None or bucket <= self.prefill_chunk:
            return bucket
        return self.prefill_chunk

    def prefill_shape(self, model, bucket: int) -> Tuple[int, int]:
        """``(rows, tokens)`` of one prefill call at rung ``bucket``.

        A configured ``prefill_chunk`` decides as before. Without one
        the engine looks at what it can see: ``prefill_rows`` whole
        prompts in one shot while their float32 scores (``rows x heads
        x rung x rung``) stay within ``_PREFILL_SCORE_BYTES``; past
        that, ONE row — in one shot where the model says its attention
        holds no scores at this rung (``scoreless_prefill``), else in
        the widest pieces that divide the rung and fit. A call reads
        every weight once, so a padding row would cost whole calls, and
        one row wastes none."""
        if self.prefill_chunk is not None:
            return self.prefill_rows, self.chunk_for(bucket)
        per_token = int(model.num_heads) * bucket * 4
        if self.prefill_rows * bucket * per_token <= _PREFILL_SCORE_BYTES:
            return self.prefill_rows, bucket
        if model.scoreless_prefill(bucket):
            return 1, bucket
        fit = max(1, _PREFILL_SCORE_BYTES // per_token)
        chunk = max(c for c in range(1, min(fit, bucket) + 1)
                    if bucket % c == 0)
        return 1, chunk

    def decode_program(self, servable, attend_len: int):
        """The compiled decode step for length bucket ``attend_len``:
        ``(params, state, cache, tokens[slots], positions[slots],
        active[slots]) -> (logits[slots,V], cache', expert counts,
        argmax ids[slots])``, cache donated.
        Each live slot writes its token's K/V at ``positions[s]`` and
        attends the first ``attend_len`` cache positions under the
        length-masked causal mask; inactive slots write into their own
        (free) row at position 0, which the slot's next prefill
        re-writes before anything can attend it."""
        model = servable.model
        key = servable.key + ("decode", attend_len)
        return self._program(
            servable, "decode", attend_len,
            lambda on_trace: self._decode_jit(
                model, attend_len, on_trace,
                functools.partial(self._kernel_layers.__setitem__, key)))

    def verify_program(self, servable, attend_len: int):
        """The compiled speculative-verify step for length bucket
        ``attend_len``: ``(params, state, cache, tokens[slots, w],
        positions[slots], active[slots]) -> (logits[slots, w, V],
        cache', expert counts)``, cache donated. Row ``s`` writes K/V for its ``w`` input
        tokens at ``positions[s] .. positions[s]+w-1`` and
        ``logits[s, i]`` is the target distribution for the token
        AFTER input ``i`` — the adjudication rows speculative decoding
        accepts draft proposals against. One verify program per rung
        (``w`` is fixed per decoder config), the third and last kind
        of the ≤ 3-per-(version, bucket) bound. A model with a
        recurrent entry is refused: rejected drafts would have to be
        rolled back out of its state."""
        model = servable.model

        def build(on_trace):
            if has_recurrent(model.cache_layout(attend_len)):
                raise RecurrentStateError(
                    f"{servable.name!r} keeps a recurrent state: a "
                    "verify step needs a snapshot of the state at the "
                    "last accepted position to rewind to, which is not "
                    "built")
            return self._verify_jit(model, attend_len, on_trace)

        return self._program(servable, "verify", attend_len, build)

    def verify(self, servable, kv: KVCache, tokens: np.ndarray,
               positions: np.ndarray, active: np.ndarray):
        """Run one speculative-verify step (``tokens`` is
        ``[slots, w]``); returns the ``[slots, w, V]`` logits as a host
        ndarray plus the attend bucket. The attend length must cover
        the deepest write (``positions + w``), so the bucket is taken
        from the longest live row plus the verify width."""
        return self._step(self.verify_program, servable, kv, tokens,
                          positions, active, int(tokens.shape[1]))

    def abstract_programs(self, model, params, state,
                          kv_dtype=None):
        """Program-enumeration hook for the static verifier
        (``bigdl_tpu.analysis.programs``): the prefill/decode jit pair
        for the TOP ladder rung as ``(name, jitted, abstract_args)``
        triples, built OUTSIDE the compile cache — no counters, no
        cache mutation, nothing executed. ``params``/``state`` may be
        ``jax.ShapeDtypeStruct`` trees; ``jitted.lower(*abstract_args)
        .compile()`` yields exactly the programs :meth:`prefill` /
        :meth:`decode` would run, donated cache included."""
        import jax

        import numpy as np

        from bigdl_tpu.generation.kv_cache import KVCache

        bucket = max(self.ladder)
        spec = KVCache.spec_for_model(model, self.slots, bucket, kv_dtype)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))

        noop = lambda: None  # noqa: E731  on_trace hook, nothing to count
        rows, sq = self.prefill_shape(model, bucket)
        programs = [
            (f"prefill/{bucket}", self._prefill_jit(model, bucket, noop,
                                                    sq == bucket),
             (params, state, spec,
              sds((rows, sq), np.int32), sds((rows,), np.int32),
              sds((rows,), np.int32), sds((rows,), np.int32))),
            (f"decode/{bucket}", self._decode_jit(model, bucket, noop),
             (params, state, spec,
              sds((self.slots,), np.int32), sds((self.slots,), np.int32),
              sds((self.slots,), bool)))]
        if not has_recurrent(model.cache_layout(bucket)):
            # the speculative-verify rung (fleet.speculative): a
            # representative draft width of 4 — the verify program's
            # donation/HBM contract is width-independent. Not for a
            # model with a recurrent entry, which verify refuses
            programs.append(
                (f"verify/{bucket}", self._verify_jit(model, bucket, noop),
                 (params, state, spec,
                  sds((self.slots, 4), np.int32),
                  sds((self.slots,), np.int32),
                  sds((self.slots,), bool))))
        return programs

    # ------------------------------------------------------ execution
    def prefill(self, servable, kv: KVCache, prompts: Sequence[np.ndarray],
                slot_ids: Sequence[int], start: Sequence[int] = None):
        """Run one padded-prompt prefill batch: writes each prompt's
        K/V into its slot's cache rows and returns the ``[n, V]``
        last-prompt-token logits (host ndarray) for the ``n`` real
        rows.

        Prompts pad to the ladder rung of the longest prompt in the
        batch; rows pad to ``prefill_rows`` with dropped slot ids.
        Past ``prefill_chunk`` the rung is filled chunk by chunk
        through the SAME per-rung program (chunk ``c`` gathers chunks
        ``0..c-1`` from the cache rows); each row's logits are taken
        from the chunk holding its last prompt token. ``start[i]``
        (chunk-aligned; prefix-cache seeding) skips chunks a seeded
        prefix already wrote."""
        n = len(prompts)
        if n == 0 or n > self.prefill_rows:
            raise ValueError(f"prefill batch of {n} rows "
                             f"(prefill_rows={self.prefill_rows})")
        lens = [len(p) for p in prompts]
        bucket = self.ladder.bucket_for(max(lens))
        rows, sq = self.prefill_shape(servable.model, bucket)
        starts = [0] * n if start is None else [int(s) for s in start]
        for i, s0 in enumerate(starts):
            if s0 % sq or not 0 <= s0 < lens[i]:
                raise ValueError(
                    f"start[{i}]={s0} must be a chunk multiple "
                    f"(chunk {sq}) below the prompt length {lens[i]}")
        prog = self.prefill_program(servable, bucket)
        out = [None] * n
        # the batch goes through in groups of `rows` prompts (all of it
        # at once unless the engine chunked the rung by itself)
        for first in range(0, n, rows):
            group = range(first, min(first + rows, n))
            for c in range(bucket // sq):
                off = c * sq
                tokens = np.zeros((rows, sq), np.int32)
                last_in = np.ones((rows,), np.int32)
                ids = np.full((rows,), self.slots, np.int32)  # OOB
                offsets = np.zeros((rows,), np.int32)
                live = False
                for r, i in enumerate(group):
                    # a row rides chunk c while it still has tokens
                    # there and its seeded prefix doesn't already
                    # cover it
                    if lens[i] <= off or starts[i] > off:
                        continue
                    live = True
                    ids[r] = slot_ids[i]
                    offsets[r] = off
                    piece = np.asarray(prompts[i][off:off + sq], np.int32)
                    tokens[r, :len(piece)] = piece
                    last_in[r] = min(lens[i] - off, sq)
                if not live:
                    continue
                logits, kv.entries, stats = prog(
                    servable.params, servable.state, kv.entries, tokens,
                    last_in, ids, offsets)
                with telemetry.span("serving/prefill/device_wait"):
                    for r, i in enumerate(group):
                        if (ids[r] != self.slots
                                and (lens[i] - 1) // sq == c):
                            out[i] = np.asarray(logits[r])
                _record_moe(stats, "prefill")
                _record_prefill_kv(kv, rows, bucket, sq == bucket)
                _record_ssm(kv, int((ids != self.slots).sum()), "prefill")
        for i, slot in enumerate(slot_ids):
            kv.lengths[slot] = lens[i]
        return np.stack(out), bucket

    def prefill_dispatches(self, model, bucket: int,
                           lens: Sequence[int],
                           starts: Sequence[int]) -> int:
        """How many prefill program calls :meth:`prefill` makes for
        prompts of ``lens`` (seeded up to ``starts``) at rung
        ``bucket`` — the same walk, for the ``prefill_chunks``
        counter."""
        rows, sq = self.prefill_shape(model, bucket)
        return sum(
            1 for first in range(0, len(lens), rows)
            for c in range(bucket // sq)
            if any(l > c * sq and s0 <= c * sq for l, s0 in
                   zip(lens[first:first + rows],
                       starts[first:first + rows])))

    def decode(self, servable, kv: KVCache, tokens: np.ndarray,
               positions: np.ndarray, active: np.ndarray,
               ids_only: bool = False):
        """Run one decode step over every slot (one token per live
        slot); returns the ``[slots, V]`` logits as a host ndarray —
        or, with ``ids_only`` (every live request greedy), the
        ``[slots]`` argmax ids the program computed, and the logits
        never leave the device.
        ``attend_len`` is re-bucketed from the longest live row each
        step, so a batch of short sequences runs the small-rung
        program.

        ``positions`` is the host per-slot lengths vector
        (``kv.lengths`` for live slots) — the bucket only fixes the
        program's *shape*: with the ragged kernel enabled
        (``bigdl_tpu.kernels``), attention inside the program reads
        only ``positions[s] + 1`` valid cache rows per slot instead of
        scanning the whole bucket, and because the vector is already
        an operand the kernel adds no program keys — the ≤ 2-per-
        bucket compile bound holds with kernels on (asserted in
        tests/test_kernels.py)."""
        return self._step(self.decode_program, servable, kv, tokens,
                          positions, active, 1, ids_only)

    def _step(self, program_for, servable, kv: KVCache, tokens, positions,
              active, width: int, ids_only: bool = False):
        """One decode or verify step in three host spans under the
        caller's ``serving/decode``: the launch (bucket choice, casts,
        the program call returning), the wait on the device, and the
        logits block copied to the host. The wait is explicit whether
        or not anything is traced — ``np.asarray`` would block for it
        anyway, so the program is the same program either way."""
        import jax

        with telemetry.span("serving/decode/dispatch"):
            longest = (int(positions[active].max()) + width
                       if active.any() else width)
            attend_len = self.ladder.bucket_for(longest)
            prog = program_for(servable, attend_len)
            logits, kv.entries, stats, *ids = prog(
                servable.params, servable.state, kv.entries,
                tokens.astype(np.int32), positions.astype(np.int32),
                active.astype(bool))
            wanted = ids[0] if ids_only else logits
        with telemetry.span("serving/decode/device_wait"):
            if width == 1:      # host work behind the device's
                _record_kv(servable.model, kv, positions, active,
                           attend_len, self._kernel_layers.get(
                               servable.key + ("decode", attend_len), 0))
                _record_ssm(kv, int(active.sum()), "decode")
            jax.block_until_ready(wanted)
        with telemetry.span("serving/decode/logits_d2h"):
            host = np.asarray(wanted)
            _record_moe(stats, "decode")
        return host, attend_len

    # -------------------------------------------------------- warmup
    def warmup(self, servable, kv: KVCache = None, kv_dtype=None) -> int:
        """Eagerly compile the prefill+decode program *pair* for every
        ladder rung (the generation analogue of
        :meth:`CompileCache.warmup`, which warms one eval program per
        rung) so no live request ever eats an XLA compile. All writes
        are dropped/inactive, so the cache stays servable: pass the
        ``kv`` the decode loop will adopt (the service does — the
        warmup buffers must not be a second full-size allocation on
        top of the serving one) or omit it for a throwaway. Returns
        how many programs this call compiled (≤ 2 × ladder rungs;
        rungs already compiled cost nothing)."""
        import jax

        if kv is None:
            kv = KVCache.for_model(servable.model, self.slots,
                                   self.ladder.max_batch_size, kv_dtype)
        before = self.compile_count(servable)
        dec_tokens = np.zeros((self.slots,), np.int32)
        dec_pos = np.zeros((self.slots,), np.int32)
        inactive = np.zeros((self.slots,), bool)
        for rung in self.ladder:
            pre = self.prefill_program(servable, rung)
            # the rows and token width serving will actually feed this
            # rung — the chunk for rungs past prefill_chunk, or the
            # engine's own pieces of a rung too wide for one shot — so
            # a live admission never re-traces
            rows, sq = self.prefill_shape(servable.model, rung)
            prompts = np.zeros((rows, sq), np.int32)
            # warmup exists to GATE on both programs of every rung
            # before the version takes traffic
            _, kv.entries, _ = pre(
                servable.params, servable.state, kv.entries, prompts,
                np.ones((rows,), np.int32),
                np.full((rows,), self.slots, np.int32),
                np.zeros((rows,), np.int32))
            dec = self.decode_program(servable, rung)
            out, kv.entries, *_ = dec(
                servable.params, servable.state, kv.entries, dec_tokens,
                dec_pos, inactive)
            jax.block_until_ready(out)  # bigdl: disable=sync-in-loop
        return self.compile_count(servable) - before

    # ----------------------------------------------------- accounting
    def compile_count(self, servable) -> int:
        """Programs compiled for this servable through this engine."""
        with self._lock:
            keys = list(self._keys.get(servable.key, ()))
        return sum(self.cache.compile_count(k) for k in keys)

    def drop(self, key: Tuple) -> None:
        """Release every program registered for a servable key (called
        at unload, mirroring :meth:`CompileCache.drop` for eval
        steps)."""
        with self._lock:
            keys = self._keys.pop(key, ())
        for k in keys:
            self.cache.drop(k)
            self._kernel_layers.pop(k, None)
