"""Environment capability gates for tier-1 (not itself a pytest file).

CPU runtimes that cannot EXECUTE cross-process collectives used to
surface as identical crash-shaped failures. That is environmental, not
a bug, so it routes through the ONE probe helper
(``bigdl_tpu.elastic.capability``): each excluded test skips with the
precise, auditable reason, and a runtime that DOES support the surface
runs the real tests unchanged. The probe result is cached per process,
so its two-process gang runs at most once per pytest session.
"""
import pytest

from bigdl_tpu.elastic.capability import multiprocess_cpu


def require_multiprocess_cpu() -> None:
    """Skip the calling test unless this runtime can execute
    cross-process collectives on the CPU backend (probed once per
    session by a real two-process reduction)."""
    ok, reason = multiprocess_cpu()
    if not ok:
        pytest.skip(reason)
