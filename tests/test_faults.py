"""Fault-injection framework: schedule semantics (nth / seeded prob /
match / times / delay / first-rule-wins determinism), the disarmed
fast-path overhead bound, classified retry + backoff, and the
integration faultpoints (fetch retry, prefetch error channel, batcher
supervision sites are covered in their own suites)."""
import time

import pytest

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu import faults
from bigdl_tpu.faults import (FaultRule, FaultSchedule, InjectedFault,
                              backoff_delay, classify, parse_schedule,
                              retry_call)


@pytest.fixture(autouse=True)
def _disarmed():
    faults.disarm()
    yield
    faults.disarm()


# ------------------------------------------------------------ schedules

def test_nth_fires_exactly_on_the_nth_call():
    with faults.armed("p/x=nth:3,raise:RuntimeError") as s:
        faults.point("p/x")
        faults.point("p/x")
        with pytest.raises(RuntimeError):
            faults.point("p/x")
        faults.point("p/x")  # past nth: silent again
    assert s.fired() == {"p/x": 1}


def test_nth_range_fires_on_each_call_in_range():
    with faults.armed("p/x=nth:2-3,raise:OSError") as s:
        faults.point("p/x")
        with pytest.raises(OSError):
            faults.point("p/x")
        with pytest.raises(OSError):
            faults.point("p/x")
        faults.point("p/x")
    assert s.total_fired() == 2


def test_seeded_probability_is_deterministic_and_times_capped():
    def run():
        hits = []
        with faults.armed("p/x=prob:0.5,seed:7,times:3"):
            for i in range(30):
                try:
                    faults.point("p/x")
                    hits.append(0)
                except InjectedFault:
                    hits.append(1)
        return hits

    a, b = run(), run()
    assert a == b  # same seed, same schedule -> same injections
    assert sum(a) == 3  # times cap


def test_match_keys_gate_on_call_context():
    with faults.armed("p/x=match:neval=4,raise") as s:
        faults.point("p/x", neval=3)
        with pytest.raises(InjectedFault):
            faults.point("p/x", neval=4)
        faults.point("p/x", neval=5)
    assert s.total_fired() == 1


def test_sibling_rules_on_one_point_count_calls_independently():
    # two nth rules on the same point: each observes EVERY call, so
    # their nth positions are absolute call numbers, not order-dependent
    s = FaultSchedule([
        FaultRule("p/x", nth=2, exc=RuntimeError),
        FaultRule("p/x", nth=4, exc=OSError),
    ])
    with faults.armed(s):
        faults.point("p/x")
        with pytest.raises(RuntimeError):
            faults.point("p/x")
        faults.point("p/x")
        with pytest.raises(OSError):
            faults.point("p/x")
    assert [r.fired for r in s.rules] == [1, 1]


def test_delay_rule_injects_latency_without_raising():
    with faults.armed("p/x=delay:30,times:1") as s:
        t0 = time.perf_counter()
        faults.point("p/x")
        assert time.perf_counter() - t0 >= 0.025
        t0 = time.perf_counter()
        faults.point("p/x")  # times exhausted: no delay
        assert time.perf_counter() - t0 < 0.02
    assert s.total_fired() == 1


def test_injected_counter_labels_by_point():
    c = telemetry.counter("faults/point/injected")
    before = c.value(point="p/ctr")
    with faults.armed("p/ctr=nth:1-2,raise"):
        for _ in range(2):
            with pytest.raises(InjectedFault):
                faults.point("p/ctr")
    assert c.value(point="p/ctr") - before == 2


def test_parse_rejects_malformed_schedules():
    for bad in ("", "p/x", "p/x=wat:1", "p/x=raise:NoSuchError"):
        with pytest.raises(ValueError):
            parse_schedule(bad)


def test_points_are_noops_when_disarmed():
    assert not faults.is_armed()
    faults.point("p/x", neval=1)  # nothing raises, nothing counts


def test_disarmed_point_overhead_bounded():
    """The production contract: a disarmed faultpoint is one module
    flag check (same budget as a disabled telemetry span; real cost
    ~0.2us, bound generous for CI noise)."""
    assert not faults.is_armed()
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        faults.point("train/step", neval=i)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"{per_call * 1e6:.2f}us per disarmed point"


# ---------------------------------------------------- classified retry

def test_classify_fatal_beats_transient_supertypes():
    assert classify(TypeError("x")) == "fatal"
    assert classify(ValueError("shape")) == "fatal"
    # NotImplementedError IS a RuntimeError; it must still be fatal
    assert classify(NotImplementedError()) == "fatal"
    assert classify(OSError("io")) == "transient"
    assert classify(RuntimeError("xla")) == "transient"
    assert classify(InjectedFault("chaos")) == "transient"
    assert classify(Exception("unknown")) == "transient"


def test_classify_honors_the_bigdl_fatal_marker():
    # CheckpointCorrupt only ESCAPES resume when quarantine is
    # impossible — retrying re-hashes the same corrupt dir, so it must
    # fail fast despite subclassing RuntimeError
    from bigdl_tpu.utils.serialization import CheckpointCorrupt
    assert classify(CheckpointCorrupt("bad digest")) == "fatal"


@pytest.mark.parametrize("message, verdict", [
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm", "fatal"),
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
     "allocating on stack", "fatal"),
    ("INTERNAL: Mosaic failed to compile TPU kernel: cannot statically "
     "prove that index in dimension 2 is a multiple of 2", "fatal"),
    ("INVALID_ARGUMENT: Executable expected shape f32[8]", "fatal"),
    ("UNAVAILABLE: connection reset by peer", "transient"),
    ("INTERNAL: stream did not block host until done", "transient"),
])
def test_classify_xla_compile_errors_and_oom_fail_at_once(message,
                                                          verdict):
    """A refused compile or a device out-of-memory replays identically:
    the optimizer's retry loop must raise the first diagnostic, not
    back off and try again. Environmental XLA errors stay retryable."""
    import jax

    assert classify(jax.errors.JaxRuntimeError(message)) == verdict


def test_backoff_doubles_to_cap_with_equal_jitter():
    import random
    rng = random.Random(0)
    ds = [backoff_delay(a, 1.0, 8.0, rng) for a in range(6)]
    for a, d in enumerate(ds):
        full = min(1.0 * 2 ** a, 8.0)
        assert full / 2 <= d <= full
    # deterministic under a seeded rng
    rng2 = random.Random(0)
    assert ds == [backoff_delay(a, 1.0, 8.0, rng2) for a in range(6)]


def test_retry_call_retries_transient_and_counts():
    c = telemetry.counter("io/retry/retries")
    before = c.value()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("blip")
        return "ok"

    slept = []
    assert retry_call(flaky, attempts=4, base_delay_s=0.01,
                      sleep=slept.append) == "ok"
    assert len(calls) == 3 and len(slept) == 2
    assert c.value() - before == 2


def test_retry_call_fails_fast_on_fatal():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError):
        retry_call(broken, attempts=5, base_delay_s=0.01,
                   sleep=lambda s: None)
    assert len(calls) == 1  # no second attempt


def test_retry_call_exhausts_attempts_then_reraises():
    calls = []

    def always():
        calls.append(1)
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(always, attempts=3, base_delay_s=0.01,
                   sleep=lambda s: None)
    assert len(calls) == 3


# ------------------------------------------------- integration points

def test_fetch_download_retries_through_faultpoint(tmp_path):
    """maybe_download survives two injected transient failures and
    removes a stale .part from a prior crashed run (the satellite
    contract)."""
    from bigdl_tpu.dataset.fetch import maybe_download
    src = tmp_path / "payload.bin"
    src.write_bytes(b"corpus-bytes")
    work = tmp_path / "cache"
    work.mkdir()
    stale = work / "got.bin.part"
    stale.write_bytes(b"half-written garbage from a dead process")
    with faults.armed("fetch/download=nth:1-2,raise:OSError") as s:
        out = maybe_download("got.bin", str(work), src.as_uri())
    assert s.total_fired() == 2
    assert open(out, "rb").read() == b"corpus-bytes"
    assert not stale.exists()


def test_fetch_download_exhausted_attempts_raise(tmp_path):
    from bigdl_tpu.dataset.fetch import maybe_download
    src = tmp_path / "payload.bin"
    src.write_bytes(b"x")
    with faults.armed("fetch/download=nth:1-9,raise:OSError"):
        with pytest.raises(OSError):
            maybe_download("got.bin", str(tmp_path / "c"), src.as_uri(),
                           attempts=3)
    assert not (tmp_path / "c" / "got.bin").exists()


def test_prefetch_stage_fault_propagates_to_consumer():
    """An injected staging-thread failure must surface as the
    consumer's exception, never a silent end-of-dataset."""
    import numpy as np

    from bigdl_tpu.dataset.prefetch import device_prefetch
    from bigdl_tpu.dataset.sample import MiniBatch

    batches = [MiniBatch(np.ones((2, 3), np.float32), None)
               for _ in range(4)]
    with faults.armed("prefetch/stage=nth:2,raise:RuntimeError"):
        it = device_prefetch(iter(batches), size=1)
        got = [next(it)]
        with pytest.raises(RuntimeError, match="injected"):
            for b in it:
                got.append(b)
    assert len(got) >= 1


def test_known_points_table_matches_call_sites_exactly():
    """faults.KNOWN_POINTS is the registry docs/robustness.md mirrors:
    every `faults.point("name", ...)` call site in the package must be
    a table entry (no undeclared points), and every table entry must
    have a live call site (no stale rows)."""
    import os
    import re

    import bigdl_tpu

    pkg = os.path.dirname(bigdl_tpu.__file__)
    pat = re.compile(r'faults\.point\(\s*"([a-z0-9_/]+)"')
    found = set()
    for root, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                found.update(pat.findall(f.read()))
    declared = set(faults.KNOWN_POINTS)
    assert found - declared == set(), \
        f"faults.point call sites missing from KNOWN_POINTS: " \
        f"{sorted(found - declared)}"
    assert declared - found == set(), \
        f"stale KNOWN_POINTS entries with no call site: " \
        f"{sorted(declared - found)}"
    for name, site in faults.KNOWN_POINTS.items():
        assert "/" in name and site.strip(), name
