"""The tests' one compile counter (not itself a pytest file).

Built on the public ``jax.monitoring`` duration events: jit reports
``/jax/core/compile/backend_compile_duration`` once for every program it
has XLA compile (jax 0.9.0 — patching the private
``jax._src.compiler.backend_compile`` counted nothing, because jit no
longer calls it). One listener is registered for the process, on first
use; ``count_compiles`` scopes what it records.
"""
import contextlib
from typing import Iterator, List

import jax.monitoring

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active: List[List[float]] = []
_registered = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == COMPILE_EVENT:
        for sink in _active:
            sink.append(duration)


@contextlib.contextmanager
def count_compiles() -> Iterator[List[float]]:
    """Yield a list that gains one entry (the seconds it took) for every
    XLA compile this process makes inside the ``with`` block."""
    global _registered
    if not _registered:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _registered = True
    sink: List[float] = []
    _active.append(sink)
    try:
        yield sink
    finally:
        _active.remove(sink)
