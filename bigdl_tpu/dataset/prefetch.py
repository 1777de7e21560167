"""Host→device transfer overlap.

The reference overlapped batch building with compute via
MTLabeledBGRImgToBatch worker threads; on TPU the equivalent win is
keeping the chip fed: stage the next MiniBatch onto the device (or across
a mesh, sharded along the batch axis) while the current step runs.
``device_prefetch`` is that double-buffer — jax transfers are async, so
``device_put`` of batch k+1 overlaps the dispatched step k.

``stack_windows`` is the standalone pipeline form of window stacking:
it groups ``k`` consecutive equal-shaped MiniBatches into ONE
``[k, ...]`` stacked MiniBatch — the buffer shape a ``lax.scan`` over
``k`` train steps consumes in one dispatch. The windowed Optimizer
(``set_steps_per_sync``) performs the same grouping inline (it must
also flush windows at trigger boundaries) and shares the stacking unit,
``stack_minibatches``/``batch_signature``, with this stage.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Iterator, List, Optional

import jax
import numpy as np

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu import faults
from bigdl_tpu.dataset.sample import MiniBatch

# data-path instruments: how deep the staged queue runs (is the chip
# fed?), how long the stager takes to build+put each batch, and how
# long the consumer stalls waiting on it (the feed bottleneck number)
_QUEUE_DEPTH = telemetry.gauge("data/prefetch/queue_depth",
                               "staged device batches waiting")
_STAGE_S = telemetry.histogram("data/prefetch/stage_s",
                               "seconds to pull + stage one batch")
_FETCH_WAIT_S = telemetry.histogram(
    "data/prefetch/fetch_wait_s",
    "seconds the consumer blocked waiting for a staged batch")
_STAGED = telemetry.counter("data/prefetch/staged_batches",
                            "batches staged to device")


def _put(batch: MiniBatch, sharding) -> MiniBatch:
    def tx(x):
        if x is None:
            return None
        if isinstance(x, (list, tuple)):
            return type(x)(tx(e) for e in x)
        return jax.device_put(x, sharding) if sharding is not None \
            else jax.device_put(x)
    return MiniBatch(tx(batch.input), tx(batch.target))


def _stack_leaves(parts):
    """Stack matching MiniBatch leaves along a NEW leading axis,
    preserving list/tuple input structure; None targets stay None."""
    def stk(*leaves):
        if any(v is None for v in leaves):
            if not all(v is None for v in leaves):
                raise ValueError(
                    "cannot window-stack batches that mix None and "
                    "non-None targets")
            return None
        if isinstance(leaves[0], (list, tuple)):
            return type(leaves[0])(
                stk(*grp) for grp in zip(*leaves))
        return np.stack([np.asarray(v) for v in leaves])
    return stk(*parts)


def stack_windows(it: Iterator[MiniBatch], k: int) -> Iterator[MiniBatch]:
    """Group ``k`` consecutive MiniBatches into one stacked MiniBatch
    whose every leaf gains a leading window axis of length ``k`` — the
    ``[K, B, ...]`` buffer layout a fused K-step scan dispatches over.
    This is the standalone stage for external pipelines; the windowed
    Optimizer groups inline with the same ``stack_minibatches`` unit so
    it can additionally flush windows at trigger boundaries.

    Batches are stacked with ``np.stack``, so all ``k`` members of a
    window must agree in shape; a shape change (e.g. a short final
    batch) closes the current window early, and the tail is emitted as
    a shorter window. Each distinct window length compiles its own
    scanned program downstream — steady-state traffic is all length
    ``k``, so in practice that is one program plus at most one tail
    variant per epoch.
    """
    if k < 1:
        raise ValueError(f"window size must be >= 1, got {k}")
    pend: List[MiniBatch] = []
    sig = None

    def flush():
        nonlocal sig
        if not pend:
            return None
        out = stack_minibatches(pend)
        pend.clear()
        sig = None
        return out

    for b in it:
        s = batch_signature(b)
        # the post-append flush keeps pend below k here; only a shape
        # change closes a window early
        if pend and s != sig:
            yield flush()
        if not pend:
            sig = s
        pend.append(b)
        if len(pend) >= k:
            yield flush()
    tail = flush()
    if tail is not None:
        yield tail


def stack_minibatches(batches) -> MiniBatch:
    """Stack equal-shaped MiniBatches into ONE windowed MiniBatch with a
    leading axis of length ``len(batches)`` (the ``stack_windows``
    unit of work, also called directly by the windowed Optimizer)."""
    return MiniBatch(_stack_leaves([b.input for b in batches]),
                     _stack_leaves([b.target for b in batches]))


def batch_signature(batch: MiniBatch):
    """Nested (shape, dtype) signature — two batches stack iff equal."""
    def leaf(x):
        if x is None:
            return None
        if isinstance(x, (list, tuple)):
            return tuple(leaf(e) for e in x)
        a = np.asarray(x)
        return (a.shape, str(a.dtype))
    return (leaf(batch.input), leaf(batch.target))


class _PrefetchHandle:
    """Close protocol shared between the consumer generator and tests:
    signals the stager to stop, drains whatever it already queued (so a
    blocked ``q.put`` wakes up), and joins the daemon thread.

    The join is BOUNDED: a stager parked on ``q.put`` observes the stop
    event within its 0.1 s put timeout and exits, but one blocked deep
    inside ``next(it)`` on a slow upstream iterator cannot be
    interrupted from outside — close() must not stall the abandoning
    consumer behind it, so after ``timeout`` the (daemon) thread is
    left to finish its current pull and exit on its own."""

    def __init__(self, q: queue.Queue, stop: threading.Event,
                 thread: threading.Thread):
        self._q = q
        self._stop = stop
        self._thread = thread

    def close(self, timeout: float = 1.0):
        self._stop.set()
        # drain so a stager blocked mid-put gets a free slot and can
        # observe the stop event instead of waiting forever
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout)


def device_prefetch(it: Iterator[MiniBatch], *, size: int = 2,
                    sharding=None) -> Iterator[MiniBatch]:
    """Wrap a MiniBatch iterator so batches are staged to device ``size``
    steps ahead. ``sharding`` (e.g. ``NamedSharding(mesh, P('data'))``)
    lays each array out across the mesh batch-dim for multi-chip feeding.

    The staging thread only calls ``device_put`` (async in jax) and
    queue ops, so it cannot race the consumer's computation.

    Abandoning the generator early (``close()`` / ``GeneratorExit`` —
    e.g. an end trigger fires mid-epoch) stops the staging thread
    cleanly: every blocking QUEUE operation it performs is bounded and
    re-checks a stop event, and the consumer's ``finally`` drains the
    queue and joins the thread — no daemon thread left parked on a full
    queue holding device buffers alive. (A stager blocked inside
    ``next(it)`` on a slow upstream iterator is the one thing close()
    cannot interrupt; the bounded join leaves it to exit on its own
    after the current pull rather than stalling the consumer.)

    Caveat: on a host whose link serializes transfers with compute, a
    host->device transfer issued while a step is executing can stall
    both. There, stage numpy batches on the host thread instead and
    ``device_put`` between compute calls on the consumer side (see
    bench.py's fed mode).
    """
    q: queue.Queue = queue.Queue(maxsize=size)
    _END = object()
    stop = threading.Event()
    error: list = []
    it = iter(it)

    def put_bounded(item) -> bool:
        """q.put that gives up when the consumer signalled stop;
        returns False on abandonment."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def stage():
        try:
            while not stop.is_set():
                t0 = time.perf_counter()
                batch = next(it, _END)
                if batch is _END:
                    # the exhausting pull is not a staged batch: no
                    # span, no stage_s sample
                    break
                with telemetry.span("data/prefetch_stage"):
                    # staging-thread death site: an injected failure
                    # here rides the existing error channel to the
                    # consumer (never a silent end-of-dataset)
                    faults.point("prefetch/stage")
                    staged = _put(batch, sharding)
                _STAGE_S.observe(time.perf_counter() - t0)
                _STAGED.inc()
                if not put_bounded(staged):
                    return
                _QUEUE_DEPTH.set(q.qsize())
        except BaseException as e:  # re-raised in the consumer
            error.append(e)
        finally:
            put_bounded(_END)

    t = threading.Thread(target=stage, daemon=True)
    t.start()
    handle = _PrefetchHandle(q, stop, t)
    try:
        while True:
            t0 = time.perf_counter()
            item = q.get()
            if item is not _END:
                # waiting for the end sentinel is not feed latency
                _FETCH_WAIT_S.observe(time.perf_counter() - t0)
            _QUEUE_DEPTH.set(q.qsize())
            if item is _END:
                if error:
                    # a device_put/iterator failure must not masquerade
                    # as normal end-of-dataset
                    raise error[0]
                return
            yield item
    finally:
        handle.close()
