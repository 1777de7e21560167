"""DecodeLoop — the continuous-batching decode driver.

One thread per model name runs the generation loop the way the
MicroBatcher runs batched forwards — same admission vocabulary
(bounded queue ⇒ :class:`~bigdl_tpu.serving.batcher.QueueFull`,
deadlines ⇒ :class:`~bigdl_tpu.serving.batcher.DeadlineExceeded`,
supervised worker ⇒ :class:`~bigdl_tpu.serving.batcher.WorkerDied`,
graceful drain) — but where the batcher's unit of work is one batch,
the loop's is one *decode step*, and the batch **never drains to
admit**: every step first admits queued requests into whatever cache
slots are free (a padded-prompt prefill on the side, its K/V rows
spliced into the big cache inside the compiled program), then decodes
one token for every live slot, then evicts finished / EOS /
max-token / deadline-expired slots. Short requests leave mid-flight
and their slots refill next step, so a long generation never holds the
whole batch hostage.

Hot-swap rides the registry exactly like batched serving: live slots
are grouped by the servable snapshot they prefilled on; a swap routes
*new* admissions to the new version while the old version's group
keeps decoding until its slots drain, then its cache is dropped (two
caches exist only during the overlap).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional

import numpy as np

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu import faults
from bigdl_tpu.generation.kv_cache import KVCache
from bigdl_tpu.generation.sampling import Sampler, SamplingParams
from bigdl_tpu.generation.stream import TokenStream
from bigdl_tpu.serving.batcher import (DeadlineExceeded, QueueFull,
                                       WorkerDied)
from bigdl_tpu.telemetry.tracer import TENURE

#: a group's identity in the trace, unique in the process
_GROUP_IDS = itertools.count(1)


def register_generation_instruments(r) -> Dict[str, object]:
    """Get-or-create every ``serving/generation/*`` instrument in
    registry ``r`` — the DecodeLoop's whole metric surface, factored
    out so ``tools.check --telemetry-audit`` audits the real
    registration calls."""
    return {
        "requests": r.counter(
            "serving/generation/requests", "generation requests admitted"),
        "rejected": r.counter(
            "serving/generation/rejected",
            "generation requests rejected at admission (QueueFull)"),
        "timed_out": r.counter(
            "serving/generation/timed_out",
            "generations failed past their deadline"),
        "tokens": r.counter(
            "serving/generation/tokens", "tokens generated"),
        "finished": r.counter(
            "serving/generation/finished", "generations finished cleanly"),
        "worker_restarts": r.counter(
            "serving/generation/worker_restarts",
            "decode-loop deaths survived by supervision"),
        "worker_failed": r.counter(
            "serving/generation/worker_failed",
            "generations failed with WorkerDied by a loop death"),
        "queue_depth": r.gauge(
            "serving/generation/queue_depth",
            "generation requests waiting for a cache slot"),
        "cache_occupancy": r.gauge(
            "serving/generation/cache_occupancy",
            "live KV-cache slot fraction"),
        "padding_efficiency": r.gauge(
            "serving/generation/padding_efficiency",
            "real cached tokens / (live slots x attended length) of the "
            "last decode step"),
        "ttft_ms": r.histogram(
            "serving/generation/ttft_ms",
            "submit -> first token latency (ms)"),
        "token_ms": r.histogram(
            "serving/generation/token_ms",
            "decode-step wall-clock per generated token (ms)"),
        "prefill_fill": r.histogram(
            "serving/generation/prefill_fill",
            "real rows / padded rows per prefill batch"),
        "prefill_chunks": r.counter(
            "serving/generation/prefill_chunks",
            "prefill chunk programs dispatched (chunked long-prompt "
            "admission; one per batch when chunking is off)"),
        "preempted": r.counter(
            "serving/generation/preempted",
            "generations failed typed by priority preemption (their "
            "slots freed for the preemptor; partial tokens kept)"),
    }


class _Gen:
    """One in-flight generation (driver-private)."""

    __slots__ = ("prompt", "stream", "sampler", "max_new", "deadline",
                 "last", "produced", "slot", "prefix_entry", "tenure")

    def __init__(self, prompt: np.ndarray, stream: TokenStream,
                 sampler: Sampler, max_new: int,
                 deadline: Optional[float]):
        self.prompt = prompt
        self.stream = stream
        self.sampler = sampler
        self.max_new = max_new
        self.deadline = deadline
        self.last: int = -1       # the newest sampled, not-yet-cached token
        self.produced: int = 0
        self.slot: int = -1
        #: pinned fleet.PrefixCache entry this gen seeded from (hit
        #: path); released when the slot frees
        self.prefix_entry = None
        #: args of its tenure record while the tracer is on: the decode
        #: steps of its group it takes part in (``last_step`` set at
        #: release)
        self.tenure = None


class _Group:
    """Live decode state pinned to ONE servable snapshot (hot-swap
    isolation: a decode batch never mixes versions)."""

    __slots__ = ("servable", "kv", "gens", "id", "steps")

    def __init__(self, servable, kv: KVCache):
        self.servable = servable
        self.kv = kv
        self.gens: Dict[int, _Gen] = {}
        #: the group's identity in the trace and its decode steps so
        #: far: a ``serving/decode`` span carries both
        self.id = next(_GROUP_IDS)
        self.steps = 0


class _Phase:
    """The engine thread's one open top-level span: :meth:`to` closes
    it and opens the next, so that one phase begins where the last
    ended and together they tile the thread's time. With the tracer off
    each is the shared no-op span."""

    __slots__ = ("_model", "_name", "_span")

    def __init__(self, model: str):
        self._model = model
        self._name = None
        self._span = telemetry.NOOP_SPAN

    def to(self, name: str, **args):
        """Close the open phase and open ``name`` (a phase without args
        that is already open stays open); returns its span."""
        if name != self._name or args:
            self._span.__exit__(None, None, None)
            self._name = name
            self._span = telemetry.span(name, model=self._model, **args)
            self._span.__enter__()
        return self._span

    def end(self) -> None:
        self._span.__exit__(None, None, None)
        self._name, self._span = None, telemetry.NOOP_SPAN


class DecodeLoop:
    """Continuous-batching generation driver for one model name (see
    module docstring for the step anatomy). Created and owned by
    :class:`~bigdl_tpu.generation.service.GenerationService`."""

    def __init__(self, name: str, registry, engine, *, max_len: int,
                 eos_token: Optional[int] = None, max_queue: int = 256,
                 default_max_new: int = 64,
                 timeout_ms: Optional[float] = None, metrics=None,
                 kv_dtype=None, cache_provider=None, prefix_cache=None):
        self._name = name
        self._registry = registry
        self._engine = engine
        self._max_len = max_len
        #: optional fleet.PrefixCache: admissions whose full prompt is
        #: cached seed their slot by device copy and skip prefill
        self._prefix = prefix_cache
        #: servable -> KVCache for a new group; the service's provider
        #: hands over the cache its load-time warmup already allocated
        self._cache_provider = cache_provider or (
            lambda servable: KVCache.for_model(
                servable.model, engine.slots, max_len, kv_dtype))
        self._eos = eos_token
        self._max_queue = max_queue
        self._default_max_new = default_max_new
        self._timeout_ms = timeout_ms

        r = metrics if metrics is not None else telemetry.MetricsRegistry()
        self.registry_metrics = r
        self._labels = {"model": name}
        inst = register_generation_instruments(r)
        self._c_requests = inst["requests"]
        self._c_rejected = inst["rejected"]
        self._c_timed_out = inst["timed_out"]
        self._c_tokens = inst["tokens"]
        self._c_finished = inst["finished"]
        self._c_worker_restarts = inst["worker_restarts"]
        self._c_worker_failed = inst["worker_failed"]
        self._g_depth = inst["queue_depth"]
        self._g_occupancy = inst["cache_occupancy"]
        self._g_padding = inst["padding_efficiency"]
        self._h_ttft = inst["ttft_ms"]
        self._h_token = inst["token_ms"]
        self._h_prefill_fill = inst["prefill_fill"]
        self._c_prefill_chunks = inst["prefill_chunks"]
        self._c_preempted = inst["preempted"]

        self._cond = threading.Condition()
        #: stream-identity -> typed error for live generations marked
        #: for preemption; the driver thread applies the mark at its
        #: next per-slot sweep (queued generations fail immediately)
        self._preempt_marks: Dict[int, BaseException] = {}
        self._seq = itertools.count(1)  # trace_id suffixes
        self._queue: Deque[_Gen] = deque()
        self._groups: "OrderedDict[tuple, _Group]" = OrderedDict()
        self._stopping = False
        self._drain = True
        self._thread = threading.Thread(
            target=self._supervised, name=f"serving-decode-{name}",
            daemon=True)
        self._thread.start()

    # -------------------------------------------------------- submit
    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               timeout_ms: Optional[float] = None) -> TokenStream:
        """Enqueue one generation; returns its :class:`TokenStream`.

        Raises :class:`QueueFull` at the admission bound (a full KV
        cache only *queues* — rejection happens at queue depth, never
        by dropping), and ValueError for prompts that cannot fit the
        cache (``len(prompt) >= max_len`` leaves no room for even one
        generated token). ``max_new_tokens`` is capped to the cache
        room left after the prompt."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] < 1:
            raise ValueError("prompt needs >= 1 tokens")
        if prompt.shape[0] >= self._max_len:
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens leaves no room to "
                f"generate in a max_len={self._max_len} cache")
        max_new = max_new_tokens if max_new_tokens is not None \
            else self._default_max_new
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        max_new = min(max_new, self._max_len - prompt.shape[0])
        sampling = (sampling or SamplingParams()).validate()
        timeout_ms = timeout_ms if timeout_ms is not None \
            else self._timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1000.0
                    if timeout_ms is not None else None)
        stream = TokenStream(prompt.shape[0], max_new,
                             trace_id=f"{self._name}/gen-"
                                      f"{next(self._seq)}")
        gen = _Gen(prompt, stream, Sampler(sampling), max_new, deadline)
        with self._cond:
            if self._stopping:
                raise RuntimeError(
                    f"generation loop {self._name!r} is shut down")
            if len(self._queue) >= self._max_queue:
                self._c_rejected.inc(**self._labels)
                raise QueueFull(
                    f"{self._name}: generation queue at max depth "
                    f"{self._max_queue}")
            self._queue.append(gen)
            self._c_requests.inc(**self._labels)
            self._g_depth.set(len(self._queue), **self._labels)
            self._cond.notify_all()
        return stream

    def preempt(self, stream: TokenStream, err: BaseException
                ) -> Optional[str]:
        """Fail one in-flight generation *typed* so its capacity goes
        to a higher-priority request (the fleet admission layer's
        decode-slot preemption). A queued generation fails immediately
        and frees its queue slot now; a live one is marked and failed
        by the driver thread at its next per-slot sweep (within one
        decode step), so the KV slot is released only from the thread
        that owns the cache. The partial tokens the stream already
        produced stay on it, and ``err`` gets a ``tokens`` attribute
        carrying them. Returns ``"queued"``/``"live"`` for a found
        stream, None when it is not held here (already resolved)."""
        with self._cond:
            for g in self._queue:
                if g.stream is stream:
                    self._queue.remove(g)
                    self._g_depth.set(len(self._queue), **self._labels)
                    self._c_preempted.inc(**self._labels)
                    err.tokens = stream.tokens()
                    g.stream._fail(err)
                    return "queued"
            for group in self._groups.values():
                for g in group.gens.values():
                    if g.stream is stream:
                        self._preempt_marks[id(stream)] = err
                        self._cond.notify_all()
                        return "live"
        return None

    def queue_depth(self) -> int:
        """Requests waiting for a cache slot."""
        with self._cond:
            return len(self._queue)

    def live_slots(self) -> int:
        """Generations currently occupying cache slots (all
        versions)."""
        with self._cond:
            return sum(len(g.gens) for g in self._groups.values())

    # ---------------------------------------------------- the driver
    def _has_live_locked(self) -> bool:
        return any(g.gens for g in self._groups.values())

    def _supervised(self) -> None:
        """Run ``_loop`` under PR-5 supervision semantics: a crash in
        the decode machinery (or an injected ``serving/decode`` fault)
        fails every in-flight generation AND everything queued with a
        typed :class:`WorkerDied` — never a silent hang — then
        restarts the loop with fresh caches so the name keeps
        serving."""
        while True:
            try:
                self._loop()
                return  # clean shutdown
            except BaseException as e:  # noqa: BLE001 — supervision
                with self._cond:
                    died: List[_Gen] = list(self._queue)
                    self._queue.clear()
                    for group in self._groups.values():
                        died.extend(group.gens.values())
                    # the step may have died mid-donation: the caches
                    # are unrecoverable state — rebuild on demand
                    self._groups.clear()
                    self._preempt_marks.clear()
                    restart = not self._stopping
                    if restart:
                        # only an actual restart is a "death survived
                        # by supervision" — a crash racing shutdown
                        # must not count a recovery that never happened
                        self._c_worker_restarts.inc(**self._labels)
                    self._c_worker_failed.inc(len(died), **self._labels)
                    self._g_depth.set(0, **self._labels)
                    self._g_occupancy.set(0.0, **self._labels)
                    self._cond.notify_all()
                # post-mortem bundle BEFORE failing streams: the last
                # decode spans + generation gauges are the evidence
                from bigdl_tpu.telemetry import flight
                flight.on_fatal("serving/decode", e,
                                metrics=self.registry_metrics)
                err = WorkerDied(
                    f"decode loop {self._name!r} died: "
                    f"{type(e).__name__}: {e}")
                err.__cause__ = e
                for g in died:
                    self._unpin(g)
                    try:
                        g.stream._fail(err)
                    except Exception:
                        pass  # racing a caller-side resolution
                if not restart:
                    return

    def _loop(self) -> None:
        """One iteration: housekeep (idle while there is no work),
        admit, prepare, then decode and sample a live group - each
        phase a top-level span that opens where the one before closes
        (:class:`_Phase`), so that the six tile the engine thread's
        time."""
        phase = _Phase(self._name)
        try:
            while True:
                # serving/housekeep: from the last step's gauges through
                # the lock, the expiry and the admission decision
                phase.to("serving/housekeep")
                with self._cond:
                    while (not self._queue and not self._has_live_locked()
                           and not self._stopping):
                        # going idle: drop drained groups NOW — a stale
                        # post-swap cache must not stay pinned in device
                        # memory just because traffic paused ("two
                        # caches exist only during the overlap")
                        self._groups.clear()
                        phase.to("serving/idle")
                        self._cond.wait()
                        phase.to("serving/housekeep")
                    if self._stopping:
                        if not self._drain:
                            self._abort_locked()
                            return
                        if not self._queue and not self._has_live_locked():
                            return
                    self._expire_queued_locked(time.monotonic())
                    seated = self._seat_locked(phase)
                if seated is not None:
                    self._admit_popped(*seated)
                self._decode_step(phase)
        finally:
            phase.end()

    def _abort_locked(self) -> None:
        """drain=False shutdown: fail queued AND live promptly (typed),
        free every slot."""
        err = RuntimeError(f"generation loop {self._name!r} shut down")
        doomed = list(self._queue)
        self._queue.clear()
        for group in self._groups.values():
            doomed.extend(group.gens.values())
        self._groups.clear()
        self._preempt_marks.clear()
        self._g_depth.set(0, **self._labels)
        self._g_occupancy.set(0.0, **self._labels)
        for g in doomed:
            self._unpin(g)
            g.stream._fail(err)

    def _expire_queued_locked(self, now: float) -> None:
        if not self._queue:
            return
        keep: Deque[_Gen] = deque()
        for g in self._queue:
            if g.deadline is not None and now > g.deadline:
                self._c_timed_out.inc(**self._labels)
                g.stream._fail(DeadlineExceeded(
                    f"{self._name}: generation waited past its deadline "
                    "in the admission queue"))
            else:
                keep.append(g)
        if len(keep) != len(self._queue):
            self._queue = keep
            self._g_depth.set(len(self._queue), **self._labels)

    # ------------------------------------------------------ admission
    def _seat_locked(self, phase: "_Phase"):
        """Seat queued requests in free slots of the CURRENT version's
        cache — runs every step, so admission never waits for the batch
        to drain. Returns ``(servable, group, gens)`` for
        :meth:`_admit_popped`, or None when nothing fits; where
        something does, ``phase`` moves to ``serving/admit`` here,
        under the lock, and on after the last first-token emit."""
        if not self._queue:
            return None
        servable = self._registry.current(self._name)
        group = self._groups.get(servable.key)
        if group is None:
            group = _Group(servable, self._cache_provider(servable))
            self._groups[servable.key] = group
        n = min(group.kv.allocator.free_count,
                self._engine.prefill_rows, len(self._queue))
        if n == 0:
            return None  # full cache queues; eviction frees slots
        phase.to("serving/admit", rows=n)
        gens = [self._queue.popleft() for _ in range(n)]
        self._g_depth.set(len(self._queue), **self._labels)
        # enter the group BEFORE the prefill dispatch: a prefill that
        # raises must find these gens in group.gens so the supervisor
        # fails their streams typed instead of stranding
        # popped-but-unprefilled requests forever
        for g in gens:
            g.slot = group.kv.allocator.alloc()
            group.gens[g.slot] = g
        return servable, group, gens

    def _admit_popped(self, servable, group: _Group,
                      gens: List[_Gen]) -> None:
        """The unlocked part of admission: prefix lookups, the prefill
        batch and the first tokens of ``gens``, already seated in
        ``group`` by :meth:`_seat_locked`."""
        # prefix/KV reuse (bigdl_tpu.fleet.prefix): a full-prompt hit
        # seeds its slot's cache rows by device copy and goes straight
        # to decode — only the misses pay a prefill program. Under
        # chunked prefill a full-prompt miss still probes CHUNK
        # BOUNDARIES (lookup_prefix): a partial hit seeds the covered
        # chunks and the engine prefills only the remainder
        # (``start=``), which is how a long shared system prompt skips
        # most of its prefill even when the tails differ
        hits: List[_Gen] = []
        misses: List[_Gen] = list(gens)
        starts: List[int] = [0] * len(gens)
        chunk = self._engine.prefill_chunk
        if self._prefix is not None:
            hits, misses, starts = [], [], []
            for g in gens:
                g.prefix_entry = self._prefix.lookup(
                    servable.key, g.prompt, **self._labels)
                if g.prefix_entry is not None:
                    hits.append(g)
                    continue
                s0 = 0
                if chunk is not None and g.prompt.shape[0] > chunk:
                    part = self._prefix.lookup_prefix(
                        servable.key, g.prompt, chunk, **self._labels)
                    if part is not None:
                        g.prefix_entry, s0 = part
                misses.append(g)
                starts.append(s0)
        t0 = time.monotonic()
        for g in hits:
            self._prefix.seed(group.kv, g.slot, g.prefix_entry)
        if misses:
            for g, s0 in zip(misses, starts):
                if s0:  # partial hit: seed the covered chunks first
                    self._prefix.seed(group.kv, g.slot, g.prefix_entry)
            with telemetry.span("serving/prefill", model=self._name,
                                rows=len(misses)):
                logits, bucket = self._engine.prefill(
                    servable, group.kv, [g.prompt for g in misses],
                    [g.slot for g in misses],
                    start=starts if any(starts) else None)
            self._h_prefill_fill.observe(
                len(misses) / self._engine.prefill_rows, **self._labels)
            self._c_prefill_chunks.inc(
                self._chunks_dispatched(servable.model, bucket, misses,
                                        starts),
                **self._labels)
            if self._prefix is not None:
                ladder = self._engine.ladder
                for i, g in enumerate(misses):
                    plen = int(g.prompt.shape[0])
                    kr, vr = self._prefix.extract(
                        group.kv, g.slot, ladder.bucket_for(plen))
                    self._prefix.insert(servable.key, g.prompt, kr, vr,
                                        logits[i], **self._labels)
                    if (chunk is not None and plen > chunk
                            and g.prefix_entry is None):
                        # boundary block: the first chunk alone, sized
                        # so the NEXT prompt sharing this head
                        # partial-hits (logits=None — no first token
                        # exists mid-prompt)
                        kr, vr = self._prefix.extract(group.kv, g.slot,
                                                      chunk)
                        self._prefix.insert(servable.key,
                                            g.prompt[:chunk], kr, vr,
                                            None, **self._labels)
        t1 = time.monotonic()
        for g in hits:
            self._emit(group, g, g.sampler.sample(g.prefix_entry.logits))
        for i, g in enumerate(misses):
            self._emit(group, g, g.sampler.sample(logits[i]))
        if telemetry.enabled():
            self._request_tracks_prefill(group, gens, t0, t1,
                                         time.monotonic())
        self._g_occupancy.set(group.kv.occupancy(), **self._labels)

    def _chunks_dispatched(self, model, bucket: int, misses: List[_Gen],
                           starts: List[int]) -> int:
        """How many prefill program dispatches the engine just ran for
        this batch (a chunk runs iff some row still has tokens there
        that its seeded prefix doesn't already cover), feeding the
        ``prefill_chunks`` counter."""
        return self._engine.prefill_dispatches(
            model, bucket, [int(g.prompt.shape[0]) for g in misses],
            starts)

    def _request_tracks_prefill(self, group: _Group, gens: List[_Gen],
                                t0: float, t1: float, t2: float) -> None:
        """Per-request trace spans for one admission: queue wait
        (submit -> prefill dispatch), the prefill itself (flow-linked
        back to this decode thread's ``serving/prefill`` span), the
        first token — which the prefill program computed — and the
        request's tenure record, which the export expands into one
        ``serving/request/decode`` event a decode step it takes part in
        (``telemetry/tracer.py``), so a request's token count equals
        its ``serving/request/decode`` span count in the export."""
        tr = telemetry.tracer()
        tok_dur = (t2 - t1) / max(len(gens), 1)
        for i, g in enumerate(gens):
            tid = tr.track(f"req {g.stream.trace_id}")
            args = {"trace_id": g.stream.trace_id, "model": self._name}
            tr.record_span("serving/request/queue_wait",
                           g.stream._t_submit, t0 - g.stream._t_submit,
                           tid=tid, args=args)
            tr.record_span("serving/request/prefill", t0, t1 - t0,
                           tid=tid,
                           args=dict(args, slot=g.slot,
                                     prompt_len=int(g.prompt.shape[0])),
                           flow=g.stream.trace_id)
            tr.record_span("serving/request/decode",
                           t1 + i * tok_dur, tok_dur, tid=tid,
                           args=dict(args, token=0, phase="prefill",
                                     ttft_ms=g.stream.ttft_ms))
            # from the group's next step on; a request its first token
            # finished was released already and takes no step
            g.tenure = dict(
                args, group=group.id, first_step=group.steps + 1,
                last_step=(None if group.gens.get(g.slot) is g
                           else group.steps))
            tr.record_span(TENURE, t2, 0.0, tid=tid, args=g.tenure)

    # ---------------------------------------------------- decode step
    def _decode_step(self, phase: "_Phase") -> None:
        phase.to("serving/prepare")
        for group, live, tokens, positions, active, greedy, real in \
                self._prepare():
            span = phase.to("serving/decode", slots=len(live),
                            group=group.id, step=group.steps)
            t0 = time.monotonic()
            logits, attend_len = self._engine.decode(
                group.servable, group.kv, tokens, positions, active,
                ids_only=greedy)
            now = time.monotonic()
            span.set_args(attend_len=attend_len)
            phase.to("serving/sample", slots=len(live))
            self._sample_and_emit(group, live, logits, now, greedy)
            phase.to("serving/housekeep")
            self._h_token.observe((now - t0) * 1000.0 / len(live),
                                  **self._labels)
            self._g_padding.set(real / (len(live) * attend_len),
                                **self._labels)
            self._g_occupancy.set(group.kv.occupancy(), **self._labels)

    def _prepare(self) -> list:
        """Every live group's next decode step, built on the host:
        ``(group, live slots, tokens, positions, active, greedy, real
        cached tokens)``; numbers the step (``group.steps``) and drops
        the groups that drained."""
        with self._cond:
            # snapshot under the lock: submit/admission mutate the
            # group map concurrently with this driver-thread sweep
            groups = list(self._groups.items())
        steps = []
        for key, group in groups:
            if not group.gens:
                # an old version's slots drained after a hot-swap (or
                # traffic paused): release its cache
                with self._cond:
                    if not group.gens:
                        self._groups.pop(key, None)
                continue
            kv = group.kv
            live = sorted(group.gens)
            tokens = np.zeros((kv.slots,), np.int32)
            positions = np.zeros((kv.slots,), np.int32)
            active = np.zeros((kv.slots,), bool)
            for slot in live:
                g = group.gens[slot]
                tokens[slot] = g.last
                # kv.lengths IS the ragged bound: the decode program's
                # attention (bigdl_tpu.kernels ragged kernel, when
                # enabled) reads exactly lengths[slot]+1 cache rows —
                # the host lengths vector flows through unmodified
                positions[slot] = kv.lengths[slot]
                active[slot] = True
            # the decode-machinery death site the chaos harness
            # injects into (PR-5 supervision contract)
            faults.point("serving/decode", model=self._name,
                         slots=len(live))
            # a step whose requests are all greedy takes the program's
            # own argmax: [slots] ids cross to the host, not the
            # [slots, V] logits (sampling parameters stay operands of
            # nothing: a sampled request simply asks for the logits)
            greedy = all(group.gens[s].sampler.params.temperature <= 0.0
                         for s in live)
            group.steps += 1
            steps.append((group, live, tokens, positions, active, greedy,
                          int(kv.lengths[live].sum()) + len(live)))
        return steps

    def _sample_and_emit(self, group: _Group, live: List[int], logits,
                         now: float, ids: bool = False) -> None:
        """The per-slot sweep after a decode step: preempt and deadline
        checks, host sampling, delivery (callers' callbacks run in
        ``_emit``). ``ids``: ``logits`` holds the step's ``[slots]``
        greedy token ids, not logits rows."""
        kv = group.kv
        for slot in live:
            g = group.gens[slot]
            kv.lengths[slot] += 1  # g.last's K/V landed this step
            with self._cond:
                perr = self._preempt_marks.pop(id(g.stream), None)
            if perr is not None:
                # the preemptor's typed error carries the partial
                # tokens; the stream keeps them too (.tokens())
                perr.tokens = g.stream.tokens()
                self._c_preempted.inc(**self._labels)
                g.stream._fail(perr)
                self._release(group, g)
                continue
            if g.deadline is not None and now > g.deadline:
                self._c_timed_out.inc(**self._labels)
                g.stream._fail(DeadlineExceeded(
                    f"{self._name}: generation passed its deadline "
                    f"after {g.produced} tokens"))
                self._release(group, g)
                continue
            self._emit(group, g, int(logits[slot]) if ids
                       else g.sampler.sample(logits[slot]))

    def _emit(self, group: _Group, g: _Gen, token: int) -> None:
        """Deliver one sampled token and apply the eviction rules
        (EOS / max_new_tokens / cache end)."""
        first = g.produced == 0
        g.last = token
        g.produced += 1
        g.stream._push(token)
        self._c_tokens.inc(**self._labels)
        if first and g.stream.ttft_ms is not None:
            self._h_ttft.observe(g.stream.ttft_ms, **self._labels)
        if self._eos is not None and token == self._eos:
            self._finish(group, g, "eos")
        elif g.produced >= g.max_new:
            self._finish(group, g, "max_tokens")
        elif g.prompt.shape[0] + g.produced >= self._max_len:
            # defensive: the submit-time cap makes this unreachable
            self._finish(group, g, "max_len")

    def _finish(self, group: _Group, g: _Gen, reason: str) -> None:
        self._c_finished.inc(**self._labels)
        g.stream._finish(reason)
        self._release(group, g)

    def _release(self, group: _Group, g: _Gen) -> None:
        if g.tenure is not None:
            g.tenure["last_step"] = group.steps
        group.gens.pop(g.slot, None)
        group.kv.lengths[g.slot] = 0
        group.kv.allocator.free(g.slot)
        with self._cond:  # RLock-backed: safe under _abort_locked too
            self._preempt_marks.pop(id(g.stream), None)
        self._unpin(g)

    def _unpin(self, g: _Gen) -> None:
        """Release the gen's pinned prefix entry (every slot-release
        path, including supervisor death and abort, must unpin — a
        leaked pin would make its entry unevictable forever)."""
        if g.prefix_entry is not None:
            self._prefix.release(g.prefix_entry)
            g.prefix_entry = None

    # ------------------------------------------------------ shutdown
    def shutdown(self, drain: bool = True) -> None:
        """Stop admission; with ``drain`` run queued + live
        generations to completion, else fail them promptly (typed);
        then join the driver thread."""
        with self._cond:
            self._stopping = True
            self._drain = drain and self._drain
            self._cond.notify_all()
        self._thread.join()
