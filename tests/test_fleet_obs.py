"""Fleet observability plane tests (ISSUE 18): cross-process snapshot
merge algebra (counter sums exact to the digit, associative and
order-independent; gauges keep per-source series; histogram count/sum
exact with percentiles from the merged reservoir), merged Chrome traces
(3 synthetic hosts, every span/flow pair preserved, ids namespaced),
snapshot-JSONL identity header back-compat, the SloSpec grammar /
evaluate / burn-rate engine, straggler detection, the snapshot shipper
(disabled = one flag check, micro-benchmark-asserted), and the
``diagnose --fleet`` / multi-bundle ``--postmortem`` CLI modes.
"""
import itertools
import json
import os
import time

import pytest

from bigdl_tpu import telemetry
from bigdl_tpu.telemetry import agg, slo
from bigdl_tpu.telemetry.metrics import MetricsRegistry
from bigdl_tpu.utils.profiling import percentile_summary


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    telemetry.tracer().clear()
    yield
    agg.stop_shipping(final=False)
    telemetry.disable()
    telemetry.tracer().clear()


def _host_snapshot(host, counters=(), hist=(), gauges=()):
    """A (identity, rows) source built through the REAL registry."""
    r = MetricsRegistry()
    for name, vals in counters:
        c = r.counter(name, "test counter")
        for v in vals:
            c.inc(v)
    for name, vals in hist:
        h = r.histogram(name, "test histogram")
        for v in vals:
            h.observe(v)
    for name, v in gauges:
        r.gauge(name, "test gauge").set(v)
    return ({"host": host, "pid": 1000 + host},
            r.snapshot(include_samples=True))


# ---------------------------------------------------------- merge algebra

class TestMergeAlgebra:
    # values chosen so naive left-to-right float addition disagrees
    # between orders — fsum-over-sorted must not
    VALS = [0.1, 1e16, 0.2, 3.0, 7e-17, 0.3]

    def _sources(self):
        return [
            _host_snapshot(0, counters=[("train/x/events", self.VALS)],
                           hist=[("train/x/lat", [1.0, 2.0, 3.0])],
                           gauges=[("train/x/depth", 4.0)]),
            _host_snapshot(1, counters=[("train/x/events",
                                         self.VALS[::-1])],
                           hist=[("train/x/lat", [10.0, 20.0])],
                           gauges=[("train/x/depth", 9.0)]),
            _host_snapshot(2, counters=[("train/x/events", [5.0])],
                           hist=[("train/x/lat", [0.5])],
                           gauges=[("train/x/depth", 1.0)]),
        ]

    @staticmethod
    def _counter_total(merged, name):
        row = next(r for r in merged if r["name"] == name)
        return agg._fsum_sorted(s["value"] for s in row["series"])

    def test_counter_sums_to_the_digit(self):
        import math
        merged = agg.aggregate_snapshots(self._sources())
        want = math.fsum(sorted(
            self.VALS + self.VALS[::-1] + [5.0]))
        assert self._counter_total(merged, "train/x/events") == want

    def test_order_independent_across_all_permutations(self):
        sources = self._sources()
        reports = []
        for perm in itertools.permutations(sources):
            merged = agg.aggregate_snapshots(list(perm))
            reports.append((
                self._counter_total(merged, "train/x/events"),
                next(tuple(sorted(
                    (s["count"], s["sum"], s["p50"], s["p99"])
                    for s in r["series"]))
                    for r in merged if r["name"] == "train/x/lat")))
        assert len(set(reports)) == 1, reports

    def test_associative_via_remerge(self):
        """merge(merge(A,B), C) == merge(A, B, C): merged series carry
        their reservoirs, so a merged snapshot is itself a source."""
        a, b, c = self._sources()
        ab = agg.aggregate_snapshots([a, b])
        two_step = agg.aggregate_snapshots([({"host": 9}, ab), c])
        flat = agg.aggregate_snapshots([a, b, c])
        for name in ("train/x/events", "train/x/lat"):
            t = next(r for r in two_step if r["name"] == name)
            f = next(r for r in flat if r["name"] == name)
            assert t["kind"] == f["kind"]
            if t["kind"] == "counter":
                assert self._counter_total(two_step, name) == \
                    self._counter_total(flat, name)
            else:
                ts, fs = t["series"][0], f["series"][0]
                assert ts["count"] == fs["count"]
                assert ts["sum"] == fs["sum"]
                assert ts["p50"] == fs["p50"]
                assert ts["p99"] == fs["p99"]

    def test_gauges_keep_per_source_series(self):
        merged = agg.aggregate_snapshots(self._sources())
        row = next(r for r in merged if r["name"] == "train/x/depth")
        got = {tuple(sorted(s["labels"].items())): s["value"]
               for s in row["series"]}
        assert got == {(("host", "0"),): 4.0,
                       (("host", "1"),): 9.0,
                       (("host", "2"),): 1.0}

    def test_histogram_count_sum_exact_percentiles_from_union(self):
        merged = agg.aggregate_snapshots(self._sources())
        row = next(r for r in merged if r["name"] == "train/x/lat")
        s = row["series"][0]
        union = sorted([1.0, 2.0, 3.0, 10.0, 20.0, 0.5])
        assert s["count"] == 6
        assert s["sum"] == sum(union)
        want = percentile_summary(union, (50, 90, 99))
        assert s["p50"] == want["p50"]
        assert s["p99"] == want["p99"]
        assert sorted(s["samples"]) == union

    def test_merge_invariant_clean_and_detects_tamper(self):
        sources = self._sources()
        merged = agg.aggregate_snapshots(sources)
        assert agg.check_merge_invariant(sources, merged) == []
        row = next(r for r in merged if r["name"] == "train/x/events")
        # big enough to survive float spacing at the ~1e16 total
        row["series"][0]["value"] += 16.0
        bad = agg.check_merge_invariant(sources, merged)
        assert bad and "train/x/events" in bad[0]

    def test_kind_conflict_raises(self):
        a = _host_snapshot(0, counters=[("train/x/v", [1.0])])
        b = _host_snapshot(1, gauges=[("train/x/v", 2.0)])
        with pytest.raises(ValueError):
            agg.aggregate_snapshots([a, b])


# ------------------------------------------------------------ trace merge

class TestTraceMerge:
    def _host_events(self, host):
        base = 1000.0 * host
        return [
            {"ph": "X", "name": f"step{host}", "pid": 7, "tid": 1,
             "ts": base, "dur": 5.0},
            {"ph": "X", "name": "decode", "pid": 7,
             "tid": (1 << 48) + 3, "ts": base + 6, "dur": 2.0},
            {"ph": "s", "name": "req", "pid": 7, "tid": 1,
             "ts": base, "id": 42, "cat": "request"},
            {"ph": "f", "name": "req", "pid": 7, "tid": 1,
             "ts": base + 8, "id": 42, "cat": "request",
             "bp": "e"},
        ]

    def test_three_hosts_preserved_namespaced_no_collisions(self):
        sources = [({"host": h}, self._host_events(h))
                   for h in range(3)]
        merged = agg.merge_chrome_traces(sources)
        meta = [e for e in merged if e["ph"] == "M"]
        spans = [e for e in merged if e["ph"] == "X"]
        flows = [e for e in merged if e["ph"] in ("s", "f")]
        assert len(meta) == 3
        assert {m["args"]["name"] for m in meta} == \
            {"host0", "host1", "host2"}
        # every span preserved, one process track per host
        assert len(spans) == 6
        assert {e["pid"] for e in spans} == {1, 2, 3}
        # tids (incl. virtual tracks) verbatim
        assert {e["tid"] for e in spans} == {1, (1 << 48) + 3}
        # every flow PAIR preserved, ids namespaced per source — three
        # distinct pairs, no cross-host pairing
        ids = sorted(e["id"] for e in flows if e["ph"] == "s")
        assert ids == ["host0:42", "host1:42", "host2:42"]
        for s_ev in (e for e in flows if e["ph"] == "s"):
            f_ev = [e for e in flows if e["ph"] == "f"
                    and e["id"] == s_ev["id"]]
            assert len(f_ev) == 1 and f_ev[0]["pid"] == s_ev["pid"]

    def test_duplicate_tags_get_suffixes(self):
        sources = [("worker", [{"ph": "X", "name": "a", "pid": 1,
                                "tid": 1, "ts": 0, "dur": 1}])] * 2
        merged = agg.merge_chrome_traces(sources)
        names = {m["args"]["name"] for m in merged if m["ph"] == "M"}
        assert names == {"worker", "worker#1"}

    def test_write_and_file_merge_roundtrip(self, tmp_path):
        paths = []
        for h in range(2):
            p = tmp_path / f"host{h}-trace.json"
            with open(p, "w") as f:
                json.dump({"traceEvents": self._host_events(h)}, f)
            paths.append(str(p))
        merged = agg.merge_chrome_trace_files(paths)
        assert len([e for e in merged if e["ph"] == "X"]) == 4
        out = tmp_path / "merged.json"
        n = agg.write_merged_trace(
            str(out), [("a", self._host_events(0))])
        doc = json.loads(out.read_text())
        assert len(doc["traceEvents"]) == n


# ------------------------------------------- snapshot header back-compat

class TestSnapshotHeader:
    def test_new_files_carry_identity_header(self, tmp_path):
        r = MetricsRegistry()
        r.counter("train/x/events", "d").inc(3)
        path = str(tmp_path / "snap.jsonl")
        telemetry.JsonlExporter(
            r, path, identity={"host": 2, "pid": 77}).export()
        with open(path) as f:
            first = json.loads(f.readline())
        assert first["header"] == telemetry.SNAPSHOT_HEADER_FORMAT
        assert first["identity"] == {"host": 2, "pid": 77}
        ident, records = telemetry.read_jsonl_with_identity(path)
        assert ident == {"host": 2, "pid": 77}
        assert len(records) == 1
        # read_jsonl (the pre-header reader) still parses, skipping it
        assert len(telemetry.read_jsonl(path)) == 1

    def test_old_headerless_files_still_parse(self, tmp_path):
        path = str(tmp_path / "old.jsonl")
        rec = {"time": 1.0, "metrics": [
            {"name": "train/x/events", "kind": "counter",
             "description": "", "series": [{"labels": {},
                                            "value": 2.0}]}]}
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
        assert telemetry.read_jsonl(path) == [rec]
        ident, records = telemetry.read_jsonl_with_identity(path)
        assert ident is None and records == [rec]

    def test_tolerant_mode_skips_torn_tail(self, tmp_path):
        """A SIGKILL mid-write leaves a torn last line; the postmortem
        reader must keep every complete record."""
        r = MetricsRegistry()
        r.counter("train/x/events", "d").inc(1)
        path = str(tmp_path / "torn.jsonl")
        telemetry.JsonlExporter(r, path, identity={"pid": 1}).export()
        with open(path, "a") as f:
            f.write('{"time": 2.0, "metri')  # torn
        with pytest.raises(ValueError):
            telemetry.read_jsonl_with_identity(path)
        ident, records = telemetry.read_jsonl_with_identity(
            path, tolerant=True)
        assert ident == {"pid": 1} and len(records) == 1


# ------------------------------------------------------------- SLO engine

class TestSlo:
    def _snapshot(self):
        r = MetricsRegistry()
        r.counter("fleet/replica/evictions", "d").inc(2, replica="r0")
        r.counter("fleet/replica/evictions", "d").inc(1, replica="r1")
        h = r.histogram("serving/generation/ttft_ms", "d")
        for v in (10.0, 20.0, 300.0):
            h.observe(v, model="m")
        return r.snapshot(include_samples=True)

    def test_parse_grammar_and_roundtrip(self):
        spec = slo.SloSpec.parse(
            "p99: serving/generation/ttft_ms.p99 <= 250\n"
            "evictions: fleet/replica/evictions <= 0 default 0;"
            "goodput: goodput_tokens_per_sec >= 40 default 0")
        assert [o.name for o in spec.objectives] == \
            ["p99", "evictions", "goodput"]
        assert spec.objectives[1].default == 0.0
        with pytest.raises(ValueError):
            slo.SloSpec.parse("nonsense without colon")
        with pytest.raises(ValueError):
            slo.SloSpec.parse("a: x == 1")  # only <= / >=

    def test_evaluate_label_reduction_and_breach(self):
        spec = slo.SloSpec.parse(
            "evictions: fleet/replica/evictions <= 0 default 0;"
            "p99: serving/generation/ttft_ms.p99 <= 250")
        report = slo.evaluate(spec, self._snapshot())
        by = {v.objective.name: v for v in report.verdicts}
        # counter series SUM (2 + 1); histogram takes the worst series
        assert by["evictions"].value == 3.0
        assert by["p99"].value > 250.0
        assert report.breached == ["evictions", "p99"]
        with pytest.raises(slo.SloBreach) as ei:
            report.check()
        assert ei.value.report is report

    def test_missing_metric_default_vs_breach(self):
        ok = slo.evaluate(slo.SloSpec.parse(
            "evictions: fleet/replica/evictions <= 0 default 0"), [])
        assert ok.passed
        assert ok.verdicts[0].source == "default"
        bad = slo.evaluate(slo.SloSpec.parse(
            "evictions: fleet/replica/evictions <= 0"), [])
        assert not bad.passed
        assert bad.verdicts[0].source == "missing"
        assert bad.verdicts[0].value is None

    def test_observations_win_over_snapshot(self):
        spec = slo.SloSpec.parse(
            "evictions: fleet/replica/evictions <= 0")
        report = slo.evaluate(spec, self._snapshot(),
                              {"fleet/replica/evictions": 0.0})
        assert report.passed
        assert report.verdicts[0].source == "observation"

    def test_engine_multi_window_burn_rate(self):
        spec = slo.SloSpec.parse("evictions: x <= 0 default 0")
        eng = slo.SloEngine(spec, error_budget=0.5,
                            windows=(5.0, 100.0))
        t0 = 1000.0
        # clean for 5 evaluations, then breaching for 5 (1s apart)
        for i in range(10):
            obs = {"x": 1.0 if i >= 5 else 0.0}
            eng.evaluate(observations=obs, now=t0 + i)
        rates = eng.burn_rates(now=t0 + 9)
        # short window (ts > 1004): all 5 breach -> 1.0/0.5 = 2.0
        assert rates[5.0] == pytest.approx(2.0)
        # long window: 5/10 breach -> 0.5/0.5 = 1.0
        assert rates[100.0] == pytest.approx(1.0)
        assert not eng.burning(now=t0 + 9)  # long window not OVER 1.0
        eng.evaluate(observations={"x": 1.0}, now=t0 + 10)
        # 6/11 long-window breaches now burn past 1.0 -> page
        assert eng.burning(now=t0 + 10)


# ------------------------------------------------------------- stragglers

def test_detect_stragglers_flags_slow_host():
    sources = [
        _host_snapshot(0, hist=[("train/optimizer/computing_time",
                                 [0.10, 0.11, 0.10])]),
        _host_snapshot(1, hist=[("train/optimizer/computing_time",
                                 [0.10, 0.10, 0.12])]),
        _host_snapshot(2, hist=[("train/optimizer/computing_time",
                                 [0.50, 0.55, 0.52])]),
    ]
    out = agg.detect_stragglers(sources, threshold=1.5)
    assert set(out["per_source"]) == {"host0", "host1", "host2"}
    assert [s["source"] for s in out["stragglers"]] == ["host2"]
    assert out["stragglers"][0]["ratio"] > 1.5
    # all-even fleet: nobody flagged
    even = agg.detect_stragglers(sources[:2], threshold=1.5)
    assert even["stragglers"] == []


# ---------------------------------------------------------------- shipper

class TestShipper:
    def test_ship_and_read_roundtrip(self, tmp_path):
        r = MetricsRegistry()
        r.counter("train/x/events", "d").inc(7)
        d = str(tmp_path / "snaps")
        agg.start_shipping(d, interval_s=0.0, registry=r,
                           identity={"replica": "r0", "pid": 1})
        assert agg.maybe_ship() is not None
        r.counter("train/x/events", "d").inc(1)
        assert agg.maybe_ship(force=True) is not None
        agg.stop_shipping()
        sources = agg.read_snapshot_dir(d)
        assert len(sources) == 1
        ident, rows = sources[0]
        assert ident["replica"] == "r0"
        # read_snapshot_dir keeps the LAST (cumulative) record
        row = next(x for x in rows if x["name"] == "train/x/events")
        assert row["series"][0]["value"] == 8.0

    @pytest.mark.parametrize("uptime_s", [None, 5.0])
    def test_interval_gate(self, tmp_path, monkeypatch, uptime_s):
        """The first ship is free whatever ``time.monotonic()`` reads:
        that clock starts near 0 at boot, and a shipper that took 0.0
        for "never shipped" gated its first line on a machine up for
        less than the interval (``uptime_s=5.0`` pins that; ``None`` is
        the machine's own clock)."""
        if uptime_s is not None:
            from types import SimpleNamespace
            monkeypatch.setattr(
                agg, "time", SimpleNamespace(monotonic=lambda: uptime_s))
        r = MetricsRegistry()
        agg.start_shipping(str(tmp_path), interval_s=3600.0,
                           registry=r, identity={"pid": 1})
        try:
            assert agg.maybe_ship() is not None   # first ship is free
            assert agg.maybe_ship() is None       # gated
            assert agg.maybe_ship(force=True) is not None
        finally:
            agg.stop_shipping(final=False)

    def test_disabled_maybe_ship_overhead_bounded(self):
        """Disarmed maybe_ship() must be ONE module-flag check — safe
        at optimizer-step cadence (same bound as disabled span())."""
        assert not agg.shipping()
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            agg.maybe_ship()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, \
            f"{per_call * 1e6:.2f}us per disarmed maybe_ship"


# ------------------------------------------------------------- CLI modes

def _ship_fake_host(d, host, step_s):
    r = MetricsRegistry()
    c = r.counter("train/optimizer/steps", "steps")
    h = r.histogram("train/optimizer/computing_time", "step time")
    for v in step_s:
        c.inc()
        h.observe(v)
    telemetry.JsonlExporter(
        r, os.path.join(d, f"snap-host{host}.jsonl"),
        identity={"host": host, "pid": 100 + host},
        include_samples=True).export()


def test_diagnose_fleet_mode(tmp_path, capsys):
    from bigdl_tpu.tools import diagnose

    d = str(tmp_path)
    _ship_fake_host(d, 0, [0.1, 0.1])
    _ship_fake_host(d, 1, [0.1, 0.12])
    _ship_fake_host(d, 2, [0.9, 0.95])
    assert diagnose.main(["--fleet", d]) == 0
    out = capsys.readouterr().out
    assert "fleet:" in out
    assert "3 sources" in out
    assert "merged totals equal per-process sums (exact)" in out
    assert "STRAGGLER" in out
    assert "train/optimizer/steps: 6" in out

    # --json carries the typed sections
    assert diagnose.main(["--fleet", d, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["fleet"]["violations"] == []
    strag = doc["fleet"]["stragglers"]["step_time"]
    assert [s["source"] for s in strag["stragglers"]] == ["host2"]


def test_diagnose_fleet_empty_dir_errors(tmp_path, capsys):
    from bigdl_tpu.tools import diagnose
    assert diagnose.main(["--fleet", str(tmp_path)]) == 2


def test_diagnose_postmortem_bundle_directory(tmp_path, capsys):
    """--postmortem on a directory OF bundles (what a killed gang
    leaves) merges traces and aggregates the registries."""
    from bigdl_tpu.telemetry import flight
    from bigdl_tpu.tools import diagnose

    r = MetricsRegistry()  # keep the shared registry out of it
    del r
    d = str(tmp_path)
    for i in range(2):
        telemetry.enable()
        with telemetry.span("optimizer/step", step=i):
            pass
        flight.arm(d)
        flight.note("checkpoint", step=i)
        assert flight.dump(f"test-{i}") is not None
        flight.disarm()
        telemetry.tracer().clear()
    bundles = [x for x in os.listdir(d) if x.startswith("postmortem-")]
    assert len(bundles) == 2
    assert diagnose.main(["--postmortem", d]) == 0
    out = capsys.readouterr().out
    assert "postmortem:" in out
    assert "test-0" in out and "test-1" in out


# ----------------------------------------------- ProcessReplica shipping

def test_process_replica_ships_snapshots_and_flight(tmp_path):
    """Subprocess replicas arm the flight recorder and ship serving
    snapshots into the router-owned directory; the router's
    fleet_snapshot() merges them with its own registry."""
    from bigdl_tpu.fleet.replica import ProcessReplica
    from bigdl_tpu.fleet.router import FleetRouter

    import numpy as np

    d = str(tmp_path / "fleet-telemetry")
    spec = dict(seed=42, vocab_size=32, hidden_size=16, num_layers=1,
                num_heads=2, max_len=16)
    router = None
    try:
        rep = ProcessReplica("p0", spec, slots=2, max_len=16,
                             telemetry_dir=d)
        router = FleetRouter([rep], telemetry_dir=d)
        s = router.submit(np.array([1, 2, 3], dtype=np.int32),
                          session="s0", max_new_tokens=3)
        assert len(s.result(timeout=120)) > 0
        # ships are interval-gated (0.2s): a second request after the
        # interval carries the serving counts into the shipped file
        deadline = time.time() + 60
        while True:
            time.sleep(0.3)
            s = router.submit(np.array([1, 2, 3], dtype=np.int32),
                              session="s0", max_new_tokens=3)
            assert len(s.result(timeout=120)) > 0
            merged = router.fleet_snapshot()
            ttft = next((row for row in merged
                         if row["name"] ==
                         "serving/generation/ttft_ms"), None)
            if ttft and sum(x["count"] for x in ttft["series"]) >= 1:
                break
            assert time.time() < deadline, \
                sorted({r["name"] for r in merged})
        # the shipped files themselves are postmortem-grade artifacts
        snaps = [f for f in os.listdir(d) if f.endswith(".jsonl")]
        assert snaps, "replica shipped no snapshot files"
    finally:
        if router is not None:
            router.shutdown(drain=False)


@pytest.mark.slow
def test_bench_slo_row_contract():
    """BENCH_SLO: fleet-soak goodput + p99 TTFT from the MERGED
    snapshot, keys named for the tools/regress direction rules, rides
    the schema-v2 record."""
    import importlib
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    bench = importlib.import_module("bench")
    from bigdl_tpu.tools.regress import (KNOWN_SCHEMA_VERSIONS,
                                         classify_key)

    row = bench._bench_slo()
    assert row["slo_goodput_tokens_per_sec"] > 0
    assert row["slo_ttft_ms_p99"] > 0
    assert row["slo_passed"] == 1
    assert bench.BENCH_SCHEMA_VERSION in KNOWN_SCHEMA_VERSIONS
    # regress gates the new keys with the right direction
    assert classify_key("slo_goodput_tokens_per_sec") == "higher"
    assert classify_key("slo_ttft_ms_p99") == "lower"
