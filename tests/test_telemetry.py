"""Telemetry subsystem tests (ISSUE 3): span tracer golden Chrome-trace
export + cross-thread nesting, disabled-mode overhead bound, metrics
registry semantics + thread safety under concurrent batcher traffic,
exporter agreement (TensorBoard/Prometheus/JSONL), and the acceptance
flow — instrumented LeNet training + concurrent serving burst producing
ONE schema-valid trace whose phase sums match Metrics.summary()."""
import json
import os
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import telemetry
from bigdl_tpu.telemetry import (Counter, MetricsRegistry, SpanTracer,
                                 parse_prometheus_text, prometheus_text,
                                 read_jsonl, scalarize)


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts and ends with tracing disabled and an empty
    ring (the registry is cumulative by design; tests use deltas or
    private registries)."""
    telemetry.disable()
    telemetry.tracer().clear()
    yield
    telemetry.disable()
    telemetry.tracer().clear()


def validate_chrome_trace(events):
    """The trace-event schema the acceptance criterion names: every
    complete event carries ph/ts/dur/pid/tid/name with sane types.
    Flow events ("s"/"f" — the request-track links) carry an id."""
    assert events, "empty trace"
    for ev in events:
        assert ev["ph"] in ("X", "M", "s", "f"), ev
        if ev["ph"] in ("s", "f"):
            assert "id" in ev and "ts" in ev and "tid" in ev
            continue
        if ev["ph"] == "X":
            for k in ("ts", "dur", "pid", "tid", "name"):
                assert k in ev, (k, ev)
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["dur"], (int, float))
            assert ev["dur"] >= 0
            assert isinstance(ev["pid"], int)
            assert isinstance(ev["tid"], int)
            assert isinstance(ev["name"], str) and ev["name"]
            if "args" in ev:
                json.dumps(ev["args"])  # must be JSON-serializable


# ---------------------------------------------------------------- tracer

class TestSpanTracer:
    def test_golden_chrome_trace_fields_and_nesting(self, tmp_path):
        tr = SpanTracer()
        with tr.span("optimizer/step", {"step": 1}):
            with tr.span("optimizer/data_wait"):
                time.sleep(0.002)
            with tr.span("optimizer/compute"):
                time.sleep(0.002)
        path = str(tmp_path / "trace.json")
        # export via a process-tracer-independent writer
        events = tr.chrome_trace_events()
        validate_chrome_trace(events)
        xs = {e["name"]: e for e in events if e["ph"] == "X"}
        assert set(xs) == {"optimizer/step", "optimizer/data_wait",
                           "optimizer/compute"}
        parent = xs["optimizer/step"]
        assert parent["args"] == {"step": 1}
        for child in ("optimizer/data_wait", "optimizer/compute"):
            c = xs[child]
            # nesting: child interval inside parent interval, same tid
            assert c["tid"] == parent["tid"]
            assert c["ts"] >= parent["ts"] - 1e-3
            assert c["ts"] + c["dur"] <= parent["ts"] + parent["dur"] \
                + 1e-3
        # file form loads and carries the same events
        tr2 = SpanTracer()
        with tr2.span("x/y", None):
            pass
        n = tr2.export_chrome_trace(path)
        data = json.load(open(path))
        assert n == 1
        assert "traceEvents" in data
        validate_chrome_trace(data["traceEvents"])

    def test_nesting_preserved_across_threads(self):
        tr = SpanTracer()
        # both threads live at once: a thread that ended before the
        # other started would hand it its ident, and so its track
        both = threading.Barrier(2)

        def work(tag):
            with tr.span(f"worker/{tag}/outer", None):
                with tr.span(f"worker/{tag}/inner", None):
                    both.wait(timeout=30)
                    time.sleep(0.002)

        threads = [threading.Thread(target=work, args=(t,),
                                    name=f"span-{t}")
                   for t in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        events = tr.chrome_trace_events()
        validate_chrome_trace(events)
        xs = {e["name"]: e for e in events if e["ph"] == "X"}
        assert len(xs) == 4
        # each thread's inner nests in ITS OWN outer; tracks differ
        for tag in ("a", "b"):
            outer, inner = xs[f"worker/{tag}/outer"], \
                xs[f"worker/{tag}/inner"]
            assert inner["tid"] == outer["tid"]
            assert inner["ts"] >= outer["ts"] - 1e-3
            assert inner["ts"] + inner["dur"] <= \
                outer["ts"] + outer["dur"] + 1e-3
        assert xs["worker/a/outer"]["tid"] != xs["worker/b/outer"]["tid"]
        # thread_name metadata rows the two worker tracks
        meta = {e["args"]["name"] for e in events if e["ph"] == "M"}
        assert {"span-a", "span-b"} <= meta

    def test_ring_buffer_is_bounded(self):
        tr = SpanTracer(capacity=16)
        for i in range(100):
            with tr.span(f"s/{i}", None):
                pass
        spans = tr.spans()
        assert len(spans) == 16
        assert spans[-1].name == "s/99"  # newest kept, oldest rotated

    def test_record_pre_measured_interval(self):
        tr = SpanTracer()
        tr.record("optimizer/data_wait", 0.125, {"step": 3})
        (s,) = tr.spans()
        assert s.dur == 0.125
        assert s.args == {"step": 3}

    def test_span_args_always_jsonable(self):
        tr = SpanTracer()
        with tr.span("x/y", {"arr": np.float32(1.5), "o": object()}):
            pass
        (ev,) = [e for e in tr.chrome_trace_events() if e["ph"] == "X"]
        json.dumps(ev)  # numpy scalar coerced, object stringified
        assert ev["args"]["arr"] == 1.5


class TestDisabledMode:
    def test_disabled_span_overhead_bounded(self):
        """The no-op fast path: one flag check + a shared context
        manager. Budget is generous for CI noise; the real cost is
        ~0.2us."""
        assert not telemetry.enabled()
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.span("optimizer/step"):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 5e-6, f"{per_span * 1e6:.2f}us per disabled span"

    def test_disabled_record_overhead_bounded(self):
        """record() is the optimizer hot loop's other entry point (the
        exact t_data/t_compute shipper); disabled it must be one flag
        check and return — no dict, no clock, no string work."""
        assert not telemetry.enabled()
        n = 50_000
        t0 = time.perf_counter()
        for i in range(n):
            telemetry.record("optimizer/data_wait", 0.001)
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, \
            f"{per_call * 1e6:.2f}us per disabled record"

    def test_disabled_creates_no_threads_files_or_spans(self, tmp_path):
        before_threads = set(threading.enumerate())
        cwd_before = sorted(os.listdir(tmp_path))
        for i in range(1000):
            with telemetry.span("a/b", step=i):
                pass
            telemetry.record("c/d", 0.1)
        assert set(threading.enumerate()) == before_threads
        assert sorted(os.listdir(tmp_path)) == cwd_before
        assert len(telemetry.tracer()) == 0  # nothing recorded

    def test_disabled_span_is_shared_noop(self):
        s1 = telemetry.span("a/b")
        s2 = telemetry.span("c/d", k=1)
        assert s1 is s2  # the singleton — no allocation per call

    def test_enable_capacity_honored_after_tracer_precreated(self):
        # tracer() pre-creates the ring; an explicit enable(capacity=)
        # must still re-bound it rather than silently dropping the ask
        old = telemetry.tracer().capacity
        try:
            telemetry.enable(capacity=8)
            assert telemetry.tracer().capacity == 8
            for i in range(20):
                with telemetry.span(f"s/{i}"):
                    pass
            assert len(telemetry.tracer()) == 8
            telemetry.disable()
            telemetry.enable()  # no capacity: keeps the current bound
            assert telemetry.tracer().capacity == 8
        finally:
            telemetry.tracer().set_capacity(old)

    def test_enable_disable_roundtrip(self):
        telemetry.enable()
        with telemetry.span("x/y"):
            pass
        assert len(telemetry.tracer()) == 1
        telemetry.disable()
        with telemetry.span("x/z"):
            pass
        assert len(telemetry.tracer()) == 1  # disabled span not recorded


# -------------------------------------------------------------- registry

class TestMetricsRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = MetricsRegistry()
        c = r.counter("serving/batcher/requests", "reqs")
        c.inc()
        c.inc(2, model="a")
        assert c.value() == 1
        assert c.value(model="a") == 2
        assert c.total() == 3
        with pytest.raises(ValueError):
            c.inc(-1)
        g = r.gauge("data/prefetch/queue_depth")
        g.set(4)
        g.add(-1)
        assert g.value() == 3
        h = r.histogram("serving/batcher/latency_ms", reservoir_size=8)
        for v in range(20):
            h.observe(float(v))
        assert h.count() == 20
        assert h.sum() == sum(range(20))
        assert len(h.samples()) == 8  # bounded reservoir
        assert h.percentiles((50,))["p50"] == pytest.approx(15.5)

    def test_get_or_create_and_kind_conflict(self):
        r = MetricsRegistry()
        a = r.counter("a/b/c")
        assert r.counter("a/b/c") is a
        with pytest.raises(ValueError):
            r.gauge("a/b/c")

    def test_audit_names(self):
        r = MetricsRegistry()
        r.counter("serving/batcher/requests")
        r.counter("BadName")
        r.gauge("also/bad")
        assert telemetry.audit_names(r) == ["BadName", "also/bad"]

    def test_histogram_thread_safety(self):
        r = MetricsRegistry()
        h = r.histogram("x/y/z")
        c = r.counter("x/y/n")

        def work():
            for i in range(5000):
                h.observe(1.0, model="m")
                c.inc(model="m")

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count(model="m") == 40_000
        assert h.sum(model="m") == 40_000.0
        assert c.value(model="m") == 40_000


# -------------------------------------------------------------- exporters

class TestExporters:
    def _populated(self):
        r = MetricsRegistry()
        r.counter("serving/batcher/requests", "reqs").inc(7, model="m")
        r.counter("train/optimizer/steps", "steps").inc(3)
        r.gauge("data/prefetch/queue_depth", "depth").set(2)
        h = r.histogram("serving/batcher/latency_ms", "lat")
        for v in (1.0, 2.0, 3.0):
            h.observe(v, model="m")
        return r

    def test_prometheus_escaping_label_roundtrip(self):
        r = MetricsRegistry()
        c = r.counter("serving/batcher/requests", 'desc with "quotes"')
        nasty = 'a"b\\c\nd'
        c.inc(5, model=nasty)
        text = prometheus_text(r.snapshot())
        parsed = parse_prometheus_text(text)
        assert parsed[("serving_batcher_requests",
                       (("model", nasty),))] == 5.0

    def test_prometheus_histogram_summary_form(self):
        r = self._populated()
        parsed = parse_prometheus_text(prometheus_text(r.snapshot()))
        labels = (("model", "m"),)
        assert parsed[("serving_batcher_latency_ms_count", labels)] == 3
        assert parsed[("serving_batcher_latency_ms_sum", labels)] == 6.0
        assert parsed[("serving_batcher_latency_ms",
                       labels + (("quantile", "0.5"),))] == 2.0

    def test_prometheus_nonfinite_values_render(self):
        import math
        r = MetricsRegistry()
        r.gauge("a/b/inf").set(float("inf"))
        r.gauge("a/b/nan").set(float("nan"))
        parsed = parse_prometheus_text(prometheus_text(r.snapshot()))
        assert parsed[("a_b_inf", ())] == float("inf")
        assert math.isnan(parsed[("a_b_nan", ())])

    def test_write_prometheus_atomic_file(self, tmp_path):
        r = self._populated()
        path = str(tmp_path / "m.prom")
        text = telemetry.write_prometheus(r, path)
        assert open(path).read() == text
        assert not os.path.exists(path + ".part")

    def test_tensorboard_filereader_roundtrip(self, tmp_path):
        from bigdl_tpu.visualization.tensorboard import FileReader
        r = self._populated()
        log_dir = str(tmp_path / "tb")
        exp = telemetry.TensorBoardExporter(r, log_dir)
        exp.export(step=5)
        exp.close()
        rows = FileReader.read_scalar(log_dir, "train/optimizer/steps")
        assert [(s, v) for s, v, _ in rows] == [(5, 3.0)]
        rows = FileReader.read_scalar(
            log_dir, "serving/batcher/requests[model=m]")
        assert [(s, v) for s, v, _ in rows] == [(5, 7.0)]
        rows = FileReader.read_scalar(
            log_dir, "serving/batcher/latency_ms[model=m].sum")
        assert [(s, v) for s, v, _ in rows] == [(5, 6.0)]

    def test_jsonl_append_and_read(self, tmp_path):
        r = self._populated()
        path = str(tmp_path / "m.jsonl")
        exp = telemetry.JsonlExporter(r, path)
        exp.export(step=1, meta={"run": "a"})
        r.counter("train/optimizer/steps").inc()
        exp.export(step=2)
        recs = read_jsonl(path)
        assert len(recs) == 2
        assert recs[0]["step"] == 1 and recs[0]["meta"] == {"run": "a"}
        s1 = scalarize(recs[0]["metrics"])
        s2 = scalarize(recs[1]["metrics"])
        assert s1["train/optimizer/steps"] == 3.0
        assert s2["train/optimizer/steps"] == 4.0

    def test_three_exporters_agree_on_counter_totals(self, tmp_path):
        """The acceptance criterion: TensorBoard, Prometheus text and
        JSONL all report the same counter totals for the same run."""
        from bigdl_tpu.visualization.tensorboard import FileReader
        r = self._populated()
        counters = {
            "serving/batcher/requests[model=m]":
                ("serving_batcher_requests", (("model", "m"),)),
            "train/optimizer/steps": ("train_optimizer_steps", ()),
        }
        # 1. JSONL
        jsonl_path = str(tmp_path / "m.jsonl")
        telemetry.JsonlExporter(r, jsonl_path).export()
        jsonl_vals = scalarize(read_jsonl(jsonl_path)[0]["metrics"])
        # 2. Prometheus
        prom = parse_prometheus_text(
            telemetry.write_prometheus(r, str(tmp_path / "m.prom")))
        # 3. TensorBoard
        log_dir = str(tmp_path / "tb")
        exp = telemetry.TensorBoardExporter(r, log_dir)
        exp.export(step=1)
        exp.close()
        for tag, prom_key in counters.items():
            tb = FileReader.read_scalar(log_dir, tag)
            assert len(tb) == 1
            assert jsonl_vals[tag] == prom[prom_key] == tb[0][1], tag


# ------------------------------------------------- batcher/serving wiring

class TestServingIntegration:
    def test_registry_thread_safety_under_concurrent_batcher_traffic(
            self):
        """8 submitter threads against one MicroBatcher (pure-python
        runner): every admission outcome is accounted for exactly in
        the registry-backed stats."""
        from bigdl_tpu.serving.batcher import MicroBatcher, QueueFull
        from bigdl_tpu.serving.compile_cache import BucketLadder

        reg = MetricsRegistry()
        b = MicroBatcher(lambda x: x, BucketLadder(8),
                         max_wait_ms=0.5, max_queue=512, name="m",
                         metrics=reg)
        per_thread, threads_n = 100, 8
        admitted = []

        def work():
            ok = 0
            for i in range(per_thread):
                try:
                    b.submit(np.ones((1, 4), np.float32)).result(
                        timeout=30)
                    ok += 1
                except QueueFull:
                    pass
            admitted.append(ok)

        threads = [threading.Thread(target=work)
                   for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b.shutdown(drain=True)
        total_ok = sum(admitted)
        st = b.stats
        assert st.requests == total_ok
        assert st.rows == total_ok
        assert st.rejected == per_thread * threads_n - total_ok
        assert st.errors == 0
        assert st.batched_rows == total_ok
        # the same numbers through the registry the exporters read
        assert reg.counter("serving/batcher/requests").value(
            model="m") == total_ok
        assert reg.histogram("serving/batcher/queue_wait_ms").count(
            model="m") == total_ok

    def test_service_metrics_shape_byte_compatible(self):
        """The pre-telemetry InferenceService.metrics() key set."""
        import bigdl_tpu.nn as nn
        from bigdl_tpu.serving import InferenceService, ServingConfig

        svc = InferenceService(config=ServingConfig(max_batch_size=4,
                                                    buckets=(4,)))
        m = nn.Sequential().add(nn.Linear(3, 2))
        m.ensure_initialized()
        svc.load("m", m)
        svc.predict_batch("m", np.ones((2, 3), np.float32))
        out = svc.metrics("m")
        svc.shutdown()
        assert {"request_count", "rows", "rejected", "timed_out",
                "errors", "batch_count", "batch_fill",
                "padded_row_ratio", "queue_depth",
                "compile_count"} <= set(out)
        assert out["request_count"] == 1 and out["rows"] == 2
        # and the service's registry carries the same series
        assert svc.metrics_registry.counter(
            "serving/batcher/requests").value(model="m") == 1
        assert svc.metrics_registry.counter(
            "serving/compile_cache/misses").value(model="m") == 1

    def test_two_services_do_not_mix_counts(self):
        import bigdl_tpu.nn as nn
        from bigdl_tpu.serving import InferenceService, ServingConfig

        def mk():
            svc = InferenceService(config=ServingConfig(
                max_batch_size=4, buckets=(4,)))
            m = nn.Sequential().add(nn.Linear(3, 2))
            m.ensure_initialized()
            svc.load("m", m)
            return svc

        s1, s2 = mk(), mk()
        s1.predict_batch("m", np.ones((2, 3), np.float32))
        assert s1.metrics("m")["request_count"] == 1
        assert s2.metrics("m")["request_count"] == 0
        s1.shutdown()
        s2.shutdown()


# --------------------------------------------------- end-to-end / diagnose

class TestAcceptance:
    @pytest.fixture(scope="class")
    def workload(self, tmp_path_factory):
        """One instrumented LeNet run + concurrent serving burst,
        shared by the acceptance assertions (it carries a compile)."""
        from bigdl_tpu.tools.diagnose import run_workload
        trace_path = str(tmp_path_factory.mktemp("diag") / "trace.json")
        telemetry.tracer().clear()
        opt, events, snapshot = run_workload(
            steps=3, batch_size=16, serve=True, trace_path=trace_path)
        telemetry.disable()
        return opt, events, snapshot, trace_path

    def test_single_trace_loads_structurally(self, workload):
        _, _, _, trace_path = workload
        data = json.load(open(trace_path))
        validate_chrome_trace(data["traceEvents"])
        names = {e["name"] for e in data["traceEvents"]
                 if e["ph"] == "X"}
        # train AND serving phases in the ONE trace
        assert "optimizer/data_wait" in names
        assert "optimizer/compute" in names
        assert "serving/batch" in names
        # serving batches ran on their own thread track
        tids = {e["name"]: {ev["tid"] for ev in data["traceEvents"]
                            if ev["ph"] == "X" and ev["name"] == e["name"]}
                for e in data["traceEvents"] if e["ph"] == "X"}
        assert tids["serving/batch"].isdisjoint(
            tids["optimizer/compute"])

    def test_phase_sums_consistent_with_metrics_summary(self, workload):
        opt, events, _, _ = workload
        from bigdl_tpu.tools.diagnose import aggregate_spans
        agg = aggregate_spans(events)
        # the trace is fed the EXACT t_data/t_compute floats Metrics
        # records; only the us-rounding of the export separates them
        for span_name, metric in (("optimizer/data_wait", "data time"),
                                  ("optimizer/compute",
                                   "computing time")):
            assert agg[span_name]["count"] == 3
            assert agg[span_name]["total_s"] == pytest.approx(
                sum(opt.metrics.values[metric]), abs=1e-4)
        # and the registry histograms carry the same sums
        h = telemetry.registry().histogram(
            "train/optimizer/computing_time")
        assert agg["optimizer/compute"]["total_s"] == pytest.approx(
            sum(opt.metrics.values["computing time"]), abs=1e-4)
        assert h.sum() >= sum(opt.metrics.values["computing time"]) - 1e-6

    def test_diagnose_cli_ingests_the_trace(self, workload, capsys):
        from bigdl_tpu.tools.diagnose import main
        _, _, _, trace_path = workload
        assert main(["--trace", trace_path, "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)["spans"]
        by_name = {r["name"]: r for r in rows}
        assert by_name["optimizer/compute"]["group"] == "train"
        assert by_name["serving/batch"]["group"] == "serving"
        assert abs(sum(r["share"] for r in rows) - 1.0) < 1e-6

    def test_diagnose_cli_ingests_jsonl(self, tmp_path, capsys):
        from bigdl_tpu.tools.diagnose import main
        r = MetricsRegistry()
        r.counter("train/optimizer/steps").inc(4)
        path = str(tmp_path / "m.jsonl")
        telemetry.JsonlExporter(r, path).export(step=4)
        phantom = str(tmp_path / "never_written.json")
        assert main(["--jsonl", path, "--out-trace", phantom]) == 0
        out = capsys.readouterr().out
        assert "train/optimizer/steps: 4" in out
        # ingest mode runs no workload: it must not claim a trace file
        # was written (none is)
        assert "chrome trace written" not in out
        assert not os.path.exists(phantom)

    def test_diagnose_cli_usage_errors(self, tmp_path):
        from bigdl_tpu.tools.diagnose import main
        assert main(["--trace", "a", "--jsonl", "b"]) == 2
        assert main(["--trace", str(tmp_path / "missing.json")]) == 2
        assert main(["--jsonl", str(tmp_path / "missing.jsonl")]) == 2


# ------------------------------------------------------- audit CLI wiring

class TestTelemetryAudit:
    def test_shipped_instruments_pass_the_audit(self, capsys):
        from bigdl_tpu.tools.check import main
        assert main(["--telemetry-audit"]) == 0
        out = capsys.readouterr().out
        assert "instrument names match family/component/metric" in out

    def test_audit_json_payload(self, capsys):
        from bigdl_tpu.tools.check import main
        assert main(["--telemetry-audit", "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)["telemetry"]
        assert payload["violations"] == []
        assert "serving/batcher/requests" in payload["instruments"]
        assert "train/optimizer/steps" in payload["instruments"]

    def test_audit_fails_on_bad_name(self, capsys):
        # a bad name in the DEFAULT registry must flip the exit code
        from bigdl_tpu.tools.check import main
        bad = telemetry.registry().counter("NotAValidName")
        try:
            assert main(["--telemetry-audit"]) == 1
            assert "FAIL NotAValidName" in capsys.readouterr().out
        finally:
            # registries have no public delete; scrub the test name so
            # later audits (and the shipped-clean test) stay green
            telemetry.registry()._instruments.pop("NotAValidName")
            del bad


# ----------------------------------------------------- optimizer Metrics

class TestOptimizerMetricsMigration:
    def test_metrics_summary_format_unchanged(self):
        from bigdl_tpu.optim.optimizer import Metrics
        m = Metrics(registry=MetricsRegistry())
        m.add("data time", 0.5)
        m.add("data time", 1.5)
        assert m.values["data time"] == [0.5, 1.5]
        assert m.summary() == "data time: avg 1.0000s over 2"

    def test_metrics_mirror_into_registry_histograms(self):
        from bigdl_tpu.optim.optimizer import Metrics
        r = MetricsRegistry()
        m = Metrics(registry=r)
        m.add("data time", 0.25)
        m.add("computing time", 0.75)
        assert r.histogram("train/optimizer/data_time").sum() == 0.25
        assert r.histogram(
            "train/optimizer/computing_time").sum() == 0.75
