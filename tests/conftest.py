"""Test config: force an 8-device virtual CPU platform BEFORE jax imports,
so sharding/mesh tests run anywhere (SURVEY.md §4 — the reference simulates
multi-node with multiple partitions in one JVM; we simulate a pod with
virtual CPU devices)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

# The tests run on the CPU (the driver's command also sets
# JAX_PLATFORMS=cpu). Anything that imported jax before this file has
# read the environment already, so say it through the live config too.
import jax

jax.config.update("jax_platforms", "cpu")

import threading
import time

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    from bigdl_tpu.utils.random import RandomGenerator
    RandomGenerator.set_seed(42)
    np.random.seed(42)
    yield


#: test modules exercising the package's thread-owning surfaces; each
#: must return the live non-daemon thread count to its baseline (the
#: PR 4 batcher-drain regression, generalized package-wide)
_THREAD_SURFACE_MODULES = ("tests.test_serving", "tests.test_generation",
                          "tests.test_fleet", "tests.test_elastic",
                          "test_serving", "test_generation",
                          "test_fleet", "test_elastic")


def _live_non_daemon():
    return {t for t in threading.enumerate()
            if t.is_alive() and not t.daemon
            and t is not threading.main_thread()}


@pytest.fixture(scope="module", autouse=True)
def _no_thread_leak(request):
    """A concurrency-surface test module must not leak non-daemon
    threads: every batcher/loop/replica/writer it starts must be shut
    down by module end (daemon workers are excluded — supervised
    worker threads are daemonized by design and die with the process).
    A short grace poll absorbs joins that are in flight at teardown."""
    name = request.module.__name__
    if not name.startswith(_THREAD_SURFACE_MODULES):
        yield
        return
    baseline = _live_non_daemon()
    yield
    deadline = time.monotonic() + 5.0
    while _live_non_daemon() - baseline \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    leaked = _live_non_daemon() - baseline
    assert not leaked, (
        f"{name} leaked non-daemon threads: "
        f"{sorted(t.name for t in leaked)}")
