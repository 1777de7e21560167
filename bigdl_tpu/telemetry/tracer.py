"""Host-side span tracer: nested, thread-aware wall-clock spans.

The missing observability layer between the driver's ``Metrics`` averages
and ``jax.profiler``'s device traces (utils/profiling.trace): *host*
attribution — where a step's wall-clock went across data staging,
compile, collective entry, serving queues — recorded with monotonic
clocks into a bounded ring buffer and exported as Chrome trace-event
JSON that loads in Perfetto / ``chrome://tracing``.

Design constraints (ISSUE 3 acceptance criteria):

- **near-zero overhead when disabled** — ``span()`` checks ONE module
  flag and returns a shared no-op context manager; no allocation, no
  clock read, no lock. A micro-benchmark test asserts the bound.
- **bounded memory** — finished spans land in a ``deque(maxlen=...)``
  ring; a forgotten-enabled tracer can never grow without limit.
- **thread-aware nesting** — each thread keeps its own open-span stack
  (``threading.local``), so serving batcher threads, prefetch stagers
  and the driver loop interleave without corrupting each other's
  nesting; Chrome trace ``tid`` separates them per track.

Spans are "complete" events (``ph: "X"``): one record per finished span
with ``ts``/``dur`` in microseconds on one monotonic clock, which is
what keeps the export loadable by the trace-event schema without
begin/end pairing fix-ups.

**The profiler's clock.** An enabled live span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so while a profiler
session is on (``jax.profiler.start_trace`` / ``utils.profiling.trace``)
the span lies on ``/host:CPU``, on the calling thread's line, on the
clock the device's ``XLA Ops`` are on — which is what lets a long idle
gap of the device be put down to what the host was doing in it. With no
session on, the annotation is one atomic load. The ring keeps
``time.monotonic()``; pre-measured ``record()`` / ``record_span()``
intervals (virtual request tracks) stay ring-only.

**A request's tenure.** The decode loop writes no record a token: one
``serving/request/tenure`` record a request (``generation/loop.py``)
names the decode steps it took part in, and the export expands it into
one ``serving/request/decode`` event per ``serving/decode`` span of
those steps, on the request's track (:func:`_tenure_events`).
"""
from __future__ import annotations

import bisect
import json
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

__all__ = ["SpanRecord", "SpanTracer", "NOOP_SPAN"]

_ANNOTATION = None


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on the first enabled
    span (a disabled process never pays for the import)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class SpanRecord:
    """One finished span: name, monotonic start, duration, thread,
    nesting depth, user args (the kwargs passed to ``span()``), and an
    optional ``flow`` link ``(flow_id, src_tid)`` — the Chrome-trace
    flow arrow tying this span's track back to the thread that
    recorded it (request tracks use it to point at the dispatch
    thread)."""

    __slots__ = ("name", "ts", "dur", "tid", "depth", "args", "flow")

    def __init__(self, name: str, ts: float, dur: float, tid: int,
                 depth: int, args: Optional[Dict[str, Any]],
                 flow: Optional[tuple] = None):
        self.name = name
        self.ts = ts          # seconds, monotonic clock
        self.dur = dur        # seconds
        self.tid = tid
        self.depth = depth
        self.args = args
        self.flow = flow

    def __repr__(self) -> str:
        return (f"SpanRecord({self.name!r} ts={self.ts:.6f} "
                f"dur={self.dur * 1e3:.3f}ms tid={self.tid} "
                f"depth={self.depth})")


class _NoopSpan:
    """The shared disabled-path context manager: no state, no clock."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_args(self, **args):
        pass


NOOP_SPAN = _NoopSpan()


class _Span:
    """A live (enabled-path) span context manager."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.args = args

    # the clock is read first on the way in and last on the way out: the
    # tracer's own bookkeeping is counted in the span that pays for it,
    # so that spans which follow each other leave no gap of the
    # tracer's making between them
    def __enter__(self):
        self._t0 = time.monotonic()
        stack = self._tracer._stack()
        self._depth = len(stack)
        stack.append(self)
        self._ann = _annotation()(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        tid = threading.get_ident()
        self._tracer._record(SpanRecord(
            self.name, self._t0, time.monotonic() - self._t0, tid,
            self._depth, self.args))
        return False

    def set_args(self, **args):
        """Add args known only inside the span (recorded as it ends)."""
        self.args = dict(self.args or (), **args)


class SpanTracer:
    """Bounded ring buffer of finished spans + per-thread open stacks.

    ``span(name, **args)`` is the instrumentation surface (usually via
    ``bigdl_tpu.telemetry.span`` which adds the disabled fast path);
    ``record(name, duration_s)`` logs a pre-measured interval ending
    now — the optimizer uses it so the trace carries the EXACT
    ``t_data``/``t_compute`` numbers ``Metrics.summary()`` reports,
    keeping the two views arithmetically consistent.
    """

    #: virtual-track tids start here — far above any OS thread ident,
    #: so request tracks can never collide with a real thread's track
    _TRACK_BASE = 1 << 48
    #: bound on live virtual tracks: one track per in-flight request is
    #: plenty, and an unbounded name->tid dict would leak at traffic
    #: rate (the cardinality failure the ring buffer exists to prevent)
    _MAX_TRACKS = 4096

    def __init__(self, capacity: int = 65536):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=capacity)
        self._local = threading.local()
        self._thread_names: Dict[int, str] = {}
        self._tracks: "OrderedDict[str, int]" = OrderedDict()
        self._next_track = self._TRACK_BASE

    # ------------------------------------------------------ recording
    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
            tid = threading.get_ident()
            with self._lock:
                self._thread_names[tid] = threading.current_thread().name
        return stack

    def _record(self, rec: SpanRecord) -> None:
        with self._lock:
            self._spans.append(rec)

    def span(self, name: str,
             args: Optional[Dict[str, Any]] = None) -> _Span:
        """Context manager measuring the enclosed block as one span."""
        return _Span(self, name, args)

    def record(self, name: str, duration_s: float,
               args: Optional[Dict[str, Any]] = None,
               end: Optional[float] = None) -> None:
        """Log a pre-measured interval of ``duration_s`` seconds ending
        at ``end`` (monotonic; default: now). Depth nests under
        whatever span is currently open on this thread."""
        self._stack()  # register the thread name
        t1 = time.monotonic() if end is None else end
        self._record(SpanRecord(name, t1 - duration_s, float(duration_s),
                                threading.get_ident(),
                                len(self._stack()), args))

    def track(self, name: str) -> int:
        """Get-or-create a **virtual track**: a synthetic tid labelled
        ``name`` in the export, for spans that belong to a logical
        entity (one request's timeline) rather than a thread.

        The table is bounded (``_MAX_TRACKS``, oldest evicted): request
        trace_ids arrive at traffic rate, and an unbounded name->tid
        map would leak exactly the way the span ring is bounded not
        to. An evicted track's already-recorded spans stay in the ring;
        only their name-metadata row ages out of the export."""
        with self._lock:
            tid = self._tracks.get(name)
            if tid is None:
                if len(self._tracks) >= self._MAX_TRACKS:
                    _, old_tid = self._tracks.popitem(last=False)
                    self._thread_names.pop(old_tid, None)
                tid = self._next_track
                self._next_track += 1
                self._tracks[name] = tid
                self._thread_names[tid] = name
            return tid

    def record_span(self, name: str, start: float, dur: float,
                    tid: Optional[int] = None,
                    args: Optional[Dict[str, Any]] = None,
                    flow: Optional[str] = None) -> None:
        """Record a span with explicit monotonic ``start``/``dur`` and
        an explicit (usually virtual) ``tid``. With ``flow``, the
        export links this span back to the *recording* thread's track
        via a Chrome-trace flow arrow — how a request track points at
        the dispatch-thread span that served it."""
        link = (flow, threading.get_ident()) if flow is not None else None
        self._record(SpanRecord(
            name, start, float(dur),
            threading.get_ident() if tid is None else tid, 0, args,
            link))

    # ------------------------------------------------------ reading
    def spans(self) -> List[SpanRecord]:
        """Snapshot of the ring buffer (oldest first)."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        """Drop every recorded span (open spans are unaffected)."""
        with self._lock:
            self._spans.clear()

    def set_capacity(self, capacity: int) -> None:
        """Re-bound the ring, keeping the newest recorded spans."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        with self._lock:
            self._spans = deque(self._spans, maxlen=capacity)
            self.capacity = capacity

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    # ------------------------------------------------------ export
    def chrome_trace_events(self) -> List[Dict[str, Any]]:
        """The trace-event list: one ``ph: "X"`` complete event per
        span (``ts``/``dur`` in µs on the shared monotonic clock) plus
        ``ph: "M"`` thread_name metadata so Perfetto labels tracks."""
        pid = os.getpid()
        with self._lock:
            spans = list(self._spans)
            names = dict(self._thread_names)
        events: List[Dict[str, Any]] = []
        for tid, tname in sorted(names.items()):
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": tname}})
        for s in _tenure_events(spans):
            ev: Dict[str, Any] = {
                "ph": "X", "pid": pid, "tid": s.tid, "name": s.name,
                "cat": s.name.split("/")[0],
                "ts": round(s.ts * 1e6, 3),
                "dur": round(s.dur * 1e6, 3),
            }
            if s.args:
                ev["args"] = {k: _jsonable(v) for k, v in s.args.items()}
            events.append(ev)
            if s.flow is not None:
                # flow arrow: start ("s") on the recording thread's
                # track, finish ("f", bind-enclosing) on the span's own
                # (virtual) track — Perfetto draws the link between
                # the dispatch thread and the request timeline
                flow_id, src_tid = s.flow
                ts = round(s.ts * 1e6, 3)
                events.append({"ph": "s", "id": str(flow_id),
                               "pid": pid, "tid": src_tid, "ts": ts,
                               "name": "request", "cat": "request"})
                events.append({"ph": "f", "bp": "e", "id": str(flow_id),
                               "pid": pid, "tid": s.tid, "ts": ts,
                               "name": "request", "cat": "request"})
        return events

    def export_chrome_trace(self, path: str) -> int:
        """Write ``{"traceEvents": [...]}`` JSON loadable in Perfetto /
        ``chrome://tracing``; returns the number of span events."""
        events = self.chrome_trace_events()
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return sum(1 for e in events if e["ph"] == "X")


TENURE = "serving/request/tenure"


def _tenure_events(spans: List[SpanRecord]):
    """``spans`` with each ``serving/request/tenure`` record replaced by
    the per-token events it stands for: one ``serving/request/decode``
    on the request's track per ``serving/decode`` span of its group
    (``args["group"]``) whose ``step`` lies in ``first_step ..
    last_step`` (None: still decoding), with that span's ``ts`` and
    ``dur``; the token is the step's place in the tenure, from 1 (the
    prefill's token is 0)."""
    steps: Dict[Any, List[SpanRecord]] = {}
    for s in spans:
        if s.name == "serving/decode" and s.args and "step" in s.args:
            steps.setdefault(s.args["group"], []).append(s)
    numbers = {}
    for group, held in steps.items():
        held.sort(key=lambda s: s.args["step"])
        numbers[group] = [s.args["step"] for s in held]
    for t in spans:
        if t.name != TENURE:
            yield t
            continue
        a = t.args
        first, last = a["first_step"], a["last_step"]
        nums = numbers.get(a["group"], [])
        stop = len(nums) if last is None else bisect.bisect_right(nums,
                                                                  last)
        for s in steps.get(a["group"], [])[
                bisect.bisect_left(nums, first):stop]:
            yield SpanRecord(
                "serving/request/decode", s.ts, s.dur, t.tid, 0,
                {"trace_id": a["trace_id"], "model": a["model"],
                 "token": s.args["step"] - first + 1,
                 "attend_len": s.args.get("attend_len")})


def _jsonable(v):
    """Span args must serialize: keep JSON natives, stringify the rest
    (a jax array in span args must not break the export)."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)
