"""Classified retry with exponential backoff + jitter.

The reference's driver retried EVERY failure on a fixed interval
(DistriOptimizer.scala:789-855); a structurally broken model fails
identically on attempt 5 as on attempt 1, and a fleet of workers
retrying on the same fixed clock stampedes whatever just recovered.
This module is the shared policy both the optimizer's
retry-from-checkpoint loop and the IO paths (dataset download, remote
writes) apply instead:

- :func:`classify` splits exceptions into **fatal** (structural /
  compile-shaped: wrong types, missing attributes, shape mismatches —
  retrying cannot fix them, fail fast with the original diagnostic)
  and **transient** (IO, runtime, injected faults — retry);
- :func:`backoff_delay` doubles a base interval per attempt up to a
  cap, with equal-jitter randomization so synchronized retriers spread
  out;
- :func:`retry_call` wraps one callable with both, counting every
  retried attempt into the ``io/retry/retries`` telemetry counter (the
  number the chaos CLI reconciles against injected IO faults).
"""
from __future__ import annotations

import logging
import random
import time
from typing import Callable, Optional, Tuple

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.faults.core import InjectedFault

logger = logging.getLogger("bigdl_tpu")

_RETRIES = telemetry.counter(
    "io/retry/retries",
    "transient-failure retries performed by retry_call")

# jitter source when the caller passes no seeded rng: a private
# instance (never the global stdlib stream — callers wanting
# reproducible schedules pass their own random.Random(seed))
_JITTER_RNG = random.Random()

#: structural / compile-shaped errors: retrying replays the identical
#: failure, so fail fast with the first (clearest) diagnostic. Checked
#: BEFORE the transient set — NotImplementedError subclasses
#: RuntimeError, and jax's concretization/type errors subclass
#: TypeError/ValueError, so order is what keeps them fatal.
FATAL_TYPES: Tuple[type, ...] = (
    TypeError, ValueError, KeyError, IndexError, AttributeError,
    NotImplementedError, ImportError, SyntaxError, MemoryError,
)

#: plausibly-environmental errors worth retrying: IO and connectivity,
#: generic runtime failures (XlaRuntimeError subclasses RuntimeError),
#: and injected faults (so recovery paths exercise their real logic).
TRANSIENT_TYPES: Tuple[type, ...] = (
    OSError, ConnectionError, TimeoutError, RuntimeError, InjectedFault,
)


#: status codes of a ``jax.errors.JaxRuntimeError`` that say the
#: PROGRAM is wrong or does not fit — a compile the TPU compiler
#: refused, a device out of memory: the retry would replay it
_FATAL_XLA_STATUS = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT",
                     "UNIMPLEMENTED", "FAILED_PRECONDITION")


def _is_deterministic_xla_error(exc: BaseException) -> bool:
    """A compile error or a device out-of-memory, as XLA reports them:
    ``JaxRuntimeError`` is a ``RuntimeError`` (transient by type), but
    its message leads with the status code."""
    import jax

    if not isinstance(exc, jax.errors.JaxRuntimeError):
        return False
    text = str(exc)
    return text.startswith(_FATAL_XLA_STATUS) \
        or "Mosaic failed to compile" in text


def classify(exc: BaseException) -> str:
    """``"fatal"`` or ``"transient"`` for one exception.

    Fatal types win over transient ones (a ``NotImplementedError`` IS
    a ``RuntimeError``); an exception carrying ``bigdl_fatal = True``
    (e.g. ``CheckpointCorrupt`` escaping a quarantine-impossible
    resume) is fatal regardless of its base class, and so is an XLA
    runtime error whose status says the program itself is at fault (a
    refused compile, a device out of memory); unknown exception
    types default to transient — the reference retried everything, and
    a retry that re-raises is strictly more informative than a
    fast-fail on a recoverable blip."""
    if getattr(exc, "bigdl_fatal", False):
        return "fatal"
    if isinstance(exc, FATAL_TYPES) or _is_deterministic_xla_error(exc):
        return "fatal"
    if isinstance(exc, TRANSIENT_TYPES):
        return "transient"
    return "transient"


def is_transient(exc: BaseException) -> bool:
    """True when :func:`classify` says the exception is retryable."""
    return classify(exc) == "transient"


def backoff_delay(attempt: int, base_s: float, max_s: float = 30.0,
                  rng: Optional[random.Random] = None) -> float:
    """Seconds to sleep before retry number ``attempt`` (0-based):
    ``base * 2**attempt`` capped at ``max_s``, equal-jittered into
    ``[d/2, d)`` so synchronized retriers don't stampede. Pass a seeded
    ``rng`` for reproducible schedules."""
    d = min(float(base_s) * (2.0 ** attempt), float(max_s))
    r = (rng if rng is not None else _JITTER_RNG).random()
    return d / 2.0 + d / 2.0 * r


def retry_call(fn: Callable, *args, attempts: int = 3,
               base_delay_s: float = 0.5, max_delay_s: float = 30.0,
               rng: Optional[random.Random] = None,
               describe: str = "", sleep: Callable[[float], None]
               = time.sleep, **kwargs):
    """Call ``fn(*args, **kwargs)``, retrying classified-transient
    failures up to ``attempts`` total tries with
    :func:`backoff_delay` sleeps between them. Fatal errors and the
    final transient failure re-raise unchanged. Each performed retry
    increments ``io/retry/retries`` and logs a warning naming
    ``describe`` (defaults to the callable's name)."""
    if attempts < 1:
        raise ValueError(f"attempts must be >= 1, got {attempts}")
    what = describe or getattr(fn, "__name__", "call")
    for attempt in range(attempts):
        try:
            return fn(*args, **kwargs)
        except Exception as e:
            if classify(e) == "fatal" or attempt == attempts - 1:
                raise
            delay = backoff_delay(attempt, base_delay_s, max_delay_s,
                                  rng)
            _RETRIES.inc()
            logger.warning(
                "%s failed (%s: %s); retry %d/%d in %.2fs", what,
                type(e).__name__, e, attempt + 1, attempts - 1, delay)
            sleep(delay)
