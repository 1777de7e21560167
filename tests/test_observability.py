"""Device-level observability tests (ISSUE 10): program profile
registry (XLA cost/memory analysis -> FLOPs/HBM/MFU gauges, the
scan-body caveat in ONE place, ceiling MFU golden-unchanged),
per-request trace propagation (queue-wait + prefill + per-token decode
spans on a linked track, asserted on exported JSON), the crash flight
recorder (WorkerDied and fatal-optimizer bundles that
``diagnose --postmortem`` ingests; disarmed = one flag check), the
bench regression sentinel (a steady five-point trajectory passes, a
synthetic 20% drop fails, unknown schema refused), and the exporter
edge cases the new series exercise."""
import glob
import json
import os
import time

import numpy as np
import pytest

from bigdl_tpu import telemetry
from bigdl_tpu.telemetry import flight, programs


@pytest.fixture(autouse=True)
def _observability_off():
    """Every test starts and ends with tracing, profiling and the
    flight recorder disabled (cumulative registries are read via
    deltas or private instances)."""
    telemetry.disable()
    telemetry.tracer().clear()
    programs.disable()
    flight.disarm()
    yield
    telemetry.disable()
    telemetry.tracer().clear()
    programs.disable()
    flight.disarm()


def _lenet_step():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step

    model = LeNet5(10).set_name("LeNet5").training()
    model.ensure_initialized()
    optim = SGD(learning_rate=0.05)
    params = model.get_parameters()
    step = build_train_step(model, nn.ClassNLLCriterion(), optim)
    return model, step, (params, optim.init_state(params),
                         model.get_state())


# ------------------------------------------------- program registry

class TestProgramRegistry:
    def test_resolve_per_item_flops_is_the_one_scan_caveat_home(self):
        """The scan-body-counted-once disambiguation: body-once wins
        when closer to the estimate, body x K wins when IT is closer,
        and neither-within-4x falls back to the estimate outright."""
        # 8 items/call, scan of 4: per-item candidates are 100 (body
        # once) and 25 (body counted x4)
        f = programs.resolve_per_item_flops
        assert f(800.0, 8) == 100.0                      # no estimate
        assert f(800.0, 8, 4, per_item_estimate=90.0) == 100.0
        assert f(800.0, 8, 4, per_item_estimate=26.0) == 25.0
        # estimate 4x+ away from both candidates: trust the estimate
        assert f(800.0, 8, 4, per_item_estimate=5.0) == 5.0

    def test_ceiling_mfu_fields_golden_unchanged(self):
        """ceiling.py's reported MFU must be byte-identical after the
        dedupe — replicate the pre-refactor math here and compare."""
        import math

        from bigdl_tpu.tools import ceiling as C

        def legacy(rate, per_item_flops, per_chunk, batch, scan, peak):
            if per_chunk is not None and per_chunk > 0:
                per_item = per_chunk / batch
                if per_item_flops:
                    cands = (per_item, per_chunk / (batch * scan))
                    per_item = min(cands, key=lambda c: abs(
                        math.log(c / per_item_flops)))
                    if not 0.25 < per_item / per_item_flops < 4.0:
                        per_item = per_item_flops
                tfs = per_item * rate / 1e12
            elif per_item_flops:
                tfs = per_item_flops * rate / 1e12
            else:
                return {}
            return {"achieved_tfs": round(tfs, 2),
                    "mfu_vs_peak": round(tfs / peak, 3),
                    "peak_tfs": peak}

        old_flops, old_b, old_s = C._FLOPS["per_chunk"], C.BATCH, C.SCAN
        try:
            C.BATCH, C.SCAN = 256, 8
            for per_chunk, est in ((6.2e15, None), (6.2e15, 2.4e10),
                                   (6.2e15, 3.1e12), (6.2e15, 1.0),
                                   (None, 2.4e10), (None, None),
                                   (0.0, 5e9)):
                C._FLOPS["per_chunk"] = per_chunk
                got = C.mfu_fields(2500.0, est)
                want = legacy(2500.0, est, per_chunk, 256, 8,
                              C.DEVICE_TFS)
                assert got == want, (per_chunk, est, got, want)
        finally:
            C._FLOPS["per_chunk"] = old_flops
            C.BATCH, C.SCAN = old_b, old_s

    def test_lenet_train_step_reports_nonzero_flops_hbm_mfu(self):
        """Acceptance: a compiled LeNet train step reports non-zero
        FLOPs, HBM bytes and (after a measured rate) MFU gauges."""
        import jax

        programs.enable()
        model, step, (params, opt_state, mstate) = _lenet_step()
        x = np.random.rand(8, 1, 28, 28).astype(np.float32)
        y = (np.random.randint(0, 10, 8) + 1).astype(np.float32)
        p2, o2, m2, loss = step(params, opt_state, mstate,
                                jax.random.PRNGKey(0), 0.05, x, y)
        assert np.isfinite(float(loss))

        from bigdl_tpu.optim.optimizer import train_program_name
        name = train_program_name(model)
        prof = programs.registry().get(name)
        assert prof is not None and prof.kind == "train"
        assert prof.flops > 0 and prof.hbm_bytes > 0
        assert prof.compile_s > 0 and prof.items_per_call == 8

        programs.record_rate(name, 10_000.0)
        assert prof.mfu is not None and prof.mfu > 0
        labels = {"program": name}
        r = telemetry.registry()
        assert r.gauge("train/program/flops").value(**labels) > 0
        assert r.gauge("train/program/hbm_bytes").value(**labels) > 0
        assert r.gauge("train/program/mfu").value(**labels) > 0

        # the profiled step keeps computing: a second call reuses the
        # compiled program and matches a fresh unprofiled step's shape
        p3, o3, m3, loss2 = step(p2, o2, m2, jax.random.PRNGKey(1),
                                 0.05, x, y)
        assert np.isfinite(float(loss2))
        assert len(programs.registry().profiles()) >= 1

    def test_serving_bucket_reports_nonzero_flops_hbm_mfu(self):
        """Acceptance: one serving bucket through the CompileCache
        registers a serving/program/* profile with non-zero FLOPs,
        HBM bytes and (auto-rated) MFU."""
        import bigdl_tpu.nn as nn
        from bigdl_tpu.serving.compile_cache import CompileCache

        programs.enable()
        model = nn.Sequential().add(nn.Reshape((28 * 28,))) \
            .add(nn.Linear(28 * 28, 10))
        model.ensure_initialized()
        cache = CompileCache()
        step = cache.step_for(("obs-lenet", 1), model)
        x = np.random.rand(8, 1, 28, 28).astype(np.float32)
        out = step(model.get_parameters(), model.get_state(), x)
        assert np.asarray(out).shape == (8, 10)
        assert cache.compile_count(("obs-lenet", 1)) == 1

        prof = programs.registry().get("obs-lenet/1")
        assert prof is not None and prof.kind == "serving"
        assert prof.flops > 0 and prof.hbm_bytes > 0
        # auto_rate: the synchronous serving call recorded a rate
        assert prof.mfu is not None and prof.mfu >= 0
        labels = {"program": "obs-lenet/1"}
        r = telemetry.registry()
        assert r.gauge("serving/program/flops").value(**labels) > 0
        assert r.gauge("serving/program/hbm_bytes").value(**labels) > 0
        # second call: cached program, no recompile
        step(model.get_parameters(), model.get_state(), x)
        assert cache.compile_count(("obs-lenet", 1)) == 1

    def test_disabled_profiling_is_passthrough(self):
        """Profiling off (the default): build sites return the raw jit
        wrapper (AOT consumers keep .lower) and register nothing."""
        assert not programs.enabled()
        before = {p.name for p in programs.registry().profiles()}
        import jax

        model, step, (params, opt_state, mstate) = _lenet_step()
        assert hasattr(step, "lower")
        assert not isinstance(step, programs._ProfiledProgram)
        x = np.random.rand(4, 1, 28, 28).astype(np.float32)
        y = (np.random.randint(0, 10, 4) + 1).astype(np.float32)
        step(params, opt_state, mstate, jax.random.PRNGKey(0), 0.05,
             x, y)
        after = {p.name for p in programs.registry().profiles()}
        assert after == before

    def test_profiled_step_transparent_under_outer_trace(self):
        """A profiled step scanned inside an outer jit must pass
        tracers through untouched (the OUTER program is the compiled
        artifact)."""
        import functools

        import jax
        from jax import lax

        programs.enable()
        model, step, carry = _lenet_step()
        x = np.random.rand(4, 1, 28, 28).astype(np.float32)
        y = (np.random.randint(0, 10, 4) + 1).astype(np.float32)

        def body(c, key):
            p, o, m = c
            p, o, m, loss = step(p, o, m, key, 0.05, x, y)
            return (p, o, m), loss

        @functools.partial(jax.jit, donate_argnums=(0,))
        def chunk(c, keys):
            return lax.scan(body, c, keys)

        _, losses = chunk(carry, jax.random.split(jax.random.PRNGKey(1),
                                                  3))
        assert np.isfinite(np.asarray(losses)).all()


# ---------------------------------------------------- request tracing

def _tiny_generation_service(slots=2, max_len=16):
    from bigdl_tpu.generation import GenerationConfig, GenerationService
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(3)
    model = TransformerLM(vocab_size=64, hidden_size=32, num_layers=1,
                          num_heads=2, max_len=max_len).evaluate()
    model.ensure_initialized()
    svc = GenerationService(config=GenerationConfig(
        slots=slots, max_len=max_len, prefill_rows=slots))
    svc.load("lm", model)
    return svc


class TestRequestTracing:
    def test_generation_trace_one_request_linked_track(self, tmp_path):
        """Acceptance: for one trace_id the exported Chrome trace
        carries queue-wait + prefill + >= max_tokens decode spans on
        ONE (virtual) track, flow-linked to the decode thread —
        asserted on the exported JSON, not internals."""
        telemetry.enable()
        svc = _tiny_generation_service()
        try:
            max_new = 4
            streams = [svc.generate("lm", np.array([1, 2, 3]),
                                    max_new_tokens=max_new)
                       for _ in range(3)]
            for s in streams:
                s.result()
            trace_id = streams[0].trace_id
            assert trace_id
            path = str(tmp_path / "gen_trace.json")
            telemetry.export_chrome_trace(path)
        finally:
            svc.shutdown()
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        mine = [e for e in events if e.get("ph") == "X"
                and (e.get("args") or {}).get("trace_id") == trace_id]
        names = [e["name"] for e in mine]
        assert names.count("serving/request/queue_wait") >= 1
        assert names.count("serving/request/prefill") >= 1
        # one span per token landed (the first rides the prefill
        # program): >= max_tokens decode spans
        assert names.count("serving/request/decode") >= max_new
        # ... all on ONE track, which is not any OS thread's track
        tids = {e["tid"] for e in mine}
        assert len(tids) == 1
        track = tids.pop()
        thread_tids = {e["tid"] for e in events if e.get("ph") == "X"
                       and e["name"] in ("serving/prefill",
                                         "serving/decode")}
        assert track not in thread_tids
        # the track is labelled with the trace id and flow-linked
        assert any(e.get("ph") == "M"
                   and e["args"]["name"] == f"req {trace_id}"
                   for e in events)
        flows = [e for e in events if e.get("ph") in ("s", "f")
                 and e.get("id") == trace_id]
        assert {"s", "f"} <= {e["ph"] for e in flows}

    def test_generation_trace_decode_cadence_ordered(self, tmp_path):
        """Per-token decode spans carry the token index and advance in
        time — the per-token cadence a TTFT investigation reads."""
        telemetry.enable()
        svc = _tiny_generation_service()
        try:
            stream = svc.generate("lm", np.array([5, 6]),
                                  max_new_tokens=3)
            stream.result()
            trace_id = stream.trace_id
            events = telemetry.tracer().chrome_trace_events()
        finally:
            svc.shutdown()
        decodes = [e for e in events if e.get("ph") == "X"
                   and e["name"] == "serving/request/decode"
                   and (e.get("args") or {}).get("trace_id") == trace_id]
        toks = [e["args"]["token"] for e in decodes]
        assert toks == sorted(toks) and toks[0] == 0
        ts = [e["ts"] for e in decodes]
        assert ts == sorted(ts)

    @pytest.mark.parametrize("swap", [False, True])
    def test_the_export_gives_what_per_token_records_gave(self, swap,
                                                           monkeypatch):
        """The loop writes one tenure record a request, not one record
        a token. Beside it, this run writes the records the loop wrote
        until PR 38 - one a live slot a decode step, taken as the step's
        ``serving/decode`` span closes, the token the request had
        produced by then - and the export's ``serving/request/decode``
        events equal them record for record: track, ``ts``, ``dur``,
        token, args. With ``swap`` a hot-swap puts two groups' steps
        under one model, and requests that end at their first token
        take no step."""
        import threading

        from bigdl_tpu import faults
        from bigdl_tpu.generation.loop import DecodeLoop
        from bigdl_tpu.models import TransformerLM

        sweep = DecodeLoop._sample_and_emit

        def per_token_records(loop, group, live, logits, now, ids=False):
            tr = telemetry.tracer()
            step = [s for s in tr.spans() if s.name == "serving/decode"
                    and s.tid == threading.get_ident()][-1]
            for slot in live:
                g = group.gens[slot]
                tr.record_span(
                    "old/request/decode", step.ts, step.dur,
                    tid=tr.track(f"req {g.stream.trace_id}"),
                    args={"trace_id": g.stream.trace_id,
                          "model": loop._name, "token": g.produced,
                          "attend_len": step.args["attend_len"]})
            return sweep(loop, group, live, logits, now, ids)

        monkeypatch.setattr(DecodeLoop, "_sample_and_emit",
                            per_token_records)
        telemetry.tracer().clear()
        telemetry.enable()
        svc = _tiny_generation_service(slots=2)
        try:
            with faults.armed("serving/decode=delay:5,times:1000"):
                streams = [svc.generate("lm", np.array([1 + i, 2, 3]),
                                        max_new_tokens=n)
                           for i, n in enumerate((5, 1, 3, 6, 1, 2))]
                if swap:
                    streams[0].first(timeout=60)
                    m2 = TransformerLM(vocab_size=64, hidden_size=32,
                                       num_layers=1, num_heads=2,
                                       max_len=16).evaluate()
                    m2.ensure_initialized()
                    svc.load("lm", m2)
                    streams += [svc.generate("lm", np.array([9, 8]),
                                             max_new_tokens=n)
                                for n in (4, 1, 3)]
                for st in streams:
                    st.result(timeout=60)
            events = telemetry.tracer().chrome_trace_events()
        finally:
            svc.shutdown()
            telemetry.disable()
            telemetry.tracer().clear()
        if swap:
            groups = {e["args"]["group"] for e in events
                      if e["name"] == "serving/decode"}
            assert len(groups) == 2

        def key(e):
            return (e["tid"], e["ts"], e["dur"], e["cat"],
                    tuple(sorted(e["args"].items())))

        new = sorted(key(e) for e in events
                     if e["name"] == "serving/request/decode"
                     and "phase" not in e["args"])
        old = sorted(key(e) for e in events
                     if e["name"] == "old/request/decode")
        assert new == [(t, ts, d, "serving", a)
                       for t, ts, d, _, a in old]
        assert len(new) == sum(len(st.tokens()) - 1 for st in streams)

    def test_microbatcher_trace_id_on_future_and_track(self):
        """MicroBatcher.submit assigns a trace_id carried to the
        response future; with tracing on the request's queue wait and
        batch membership land on its track."""
        from bigdl_tpu.serving.batcher import MicroBatcher
        from bigdl_tpu.serving.compile_cache import BucketLadder

        telemetry.enable()
        mb = MicroBatcher(lambda x: x, BucketLadder(4), max_wait_ms=1.0,
                          name="obs")
        try:
            fut = mb.submit(np.ones((1, 2), np.float32))
            np.testing.assert_array_equal(
                fut.result(timeout=5), np.ones((1, 2), np.float32))
            assert fut.trace_id.startswith("obs/req-")
            time.sleep(0.05)
            events = telemetry.tracer().chrome_trace_events()
        finally:
            mb.shutdown(drain=False)
        mine = [e for e in events if e.get("ph") == "X"
                and (e.get("args") or {}).get("trace_id") == fut.trace_id]
        names = {e["name"] for e in mine}
        assert "serving/request/queue_wait" in names
        assert "serving/request/batch" in names
        batch_ev = next(e for e in mine
                        if e["name"] == "serving/request/batch")
        assert batch_ev["args"]["bucket"] >= batch_ev["args"]["rows"]

    def test_virtual_track_table_is_bounded(self):
        """Request trace_ids arrive at traffic rate: the name->tid
        track table must evict (oldest first), never grow without
        bound — and metadata rows for evicted tracks age out of the
        export."""
        from bigdl_tpu.telemetry import SpanTracer

        tr = SpanTracer(capacity=16)
        cap = tr._MAX_TRACKS
        tids = [tr.track(f"req r-{i}") for i in range(cap + 100)]
        assert len(set(tids)) == cap + 100  # no tid reuse
        assert len(tr._tracks) == cap
        # the oldest 100 evicted, newest retained and stable
        assert tr.track(f"req r-{cap + 99}") == tids[-1]
        assert "req r-0" not in tr._tracks
        meta_names = {e["args"]["name"]
                      for e in tr.chrome_trace_events()
                      if e["ph"] == "M"}
        assert f"req r-{cap + 99}" in meta_names
        assert "req r-0" not in meta_names

    def test_tracing_disabled_records_no_request_spans(self):
        """Disabled tracing: trace_ids still assigned (cheap), but the
        ring stays empty — the <5us disabled-overhead contract in
        test_telemetry covers the span() fast path itself."""
        from bigdl_tpu.serving.batcher import MicroBatcher
        from bigdl_tpu.serving.compile_cache import BucketLadder

        assert not telemetry.enabled()
        mb = MicroBatcher(lambda x: x, BucketLadder(4), max_wait_ms=1.0,
                          name="quiet")
        try:
            fut = mb.submit(np.ones((1, 2), np.float32))
            fut.result(timeout=5)
            assert fut.trace_id
        finally:
            mb.shutdown(drain=False)
        assert len(telemetry.tracer()) == 0


# --------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_disarmed_note_is_one_flag_check(self):
        """The telemetry.span discipline: a disarmed note() must cost
        a flag check, nothing else (budget generous for CI noise)."""
        assert not flight.armed()
        n = 50_000
        t0 = time.perf_counter()
        for i in range(n):
            flight.note("fault", point="x")
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, f"{per_call * 1e6:.2f}us disarmed note"

    def test_worker_died_dumps_bundle_diagnose_ingests(self, tmp_path):
        """Acceptance: an injected serving dispatch death produces a
        bundle `diagnose --postmortem` ingests (exit 0)."""
        from bigdl_tpu import faults
        from bigdl_tpu.serving.batcher import MicroBatcher, WorkerDied
        from bigdl_tpu.serving.compile_cache import BucketLadder
        from bigdl_tpu.tools.diagnose import main as diagnose_main

        flight.arm(str(tmp_path))
        mb = MicroBatcher(lambda x: x, BucketLadder(4), max_wait_ms=1.0,
                          name="doomed")
        try:
            with faults.armed("serving/take_batch=nth:1,raise"):
                fut = mb.submit(np.ones((1, 2), np.float32))
                with pytest.raises(WorkerDied):
                    fut.result(timeout=5)
            deadline = time.monotonic() + 5
            while not glob.glob(str(tmp_path / "postmortem-*")) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            mb.shutdown(drain=False)
        (bundle,) = glob.glob(str(tmp_path / "postmortem-*"))
        for name in ("MANIFEST.json", "events.jsonl", "trace.json",
                     "metrics.json", "programs.json"):
            assert os.path.exists(os.path.join(bundle, name)), name
        with open(os.path.join(bundle, "MANIFEST.json")) as f:
            manifest = json.load(f)
        assert manifest["reason"] == "serving/dispatch"
        assert manifest["error"]["type"] == "InjectedFault"
        assert diagnose_main(["--postmortem", bundle]) == 0
        assert diagnose_main(["--postmortem", bundle, "--json"]) == 0

    def test_fatal_optimizer_error_dumps_bundle(self, tmp_path):
        """Acceptance: a fatal classified Optimizer error (TypeError —
        structural, never retried) dumps a bundle diagnose ingests."""
        import bigdl_tpu.nn as nn
        from bigdl_tpu import faults
        from bigdl_tpu.dataset import (DataSet, Sample,
                                       SampleToMiniBatch)
        from bigdl_tpu.models import LeNet5
        from bigdl_tpu.optim import SGD, LocalOptimizer, max_iteration
        from bigdl_tpu.tools.diagnose import main as diagnose_main

        flight.arm(str(tmp_path))
        rng = np.random.RandomState(0)
        x = rng.rand(16, 1, 28, 28).astype(np.float32)
        y = (rng.randint(0, 10, 16) + 1).astype(np.float32)
        ds = DataSet.array([Sample(x[i], y[i]) for i in range(16)]) \
            .transform(SampleToMiniBatch(8))
        opt = LocalOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion(),
                             batch_size=8)
        opt.set_optim_method(SGD(learning_rate=0.05))
        opt.set_end_when(max_iteration(4))
        with faults.armed("train/step=nth:1,raise:TypeError"):
            with pytest.raises(TypeError):
                opt.optimize()
        (bundle,) = glob.glob(str(tmp_path / "postmortem-*"))
        with open(os.path.join(bundle, "MANIFEST.json")) as f:
            manifest = json.load(f)
        assert manifest["reason"] == "train/optimizer"
        assert manifest["error"]["type"] == "TypeError"
        # the ring captured the injected fault leading up to the death
        with open(os.path.join(bundle, "events.jsonl")) as f:
            kinds = [json.loads(ln)["kind"] for ln in f if ln.strip()]
        assert "fault" in kinds and "fatal" in kinds
        assert diagnose_main(["--postmortem", bundle]) == 0

    def test_postmortem_refuses_foreign_dir(self, tmp_path):
        from bigdl_tpu.tools.diagnose import main as diagnose_main

        assert diagnose_main(["--postmortem", str(tmp_path)]) == 2
        (tmp_path / "MANIFEST.json").write_text('{"format": "other"}')
        assert diagnose_main(["--postmortem", str(tmp_path)]) == 2

    def test_dump_cap_bounds_disk(self, tmp_path):
        import bigdl_tpu.telemetry.flight as fl

        flight.arm(str(tmp_path))
        old_seq = fl._SEQ[0]
        try:
            fl._SEQ[0] = fl._MAX_DUMPS
            assert flight.dump("cap-test") is None
        finally:
            fl._SEQ[0] = old_seq


# ------------------------------------------------ regression sentinel

class TestRegressionSentinel:
    def _trajectory(self, tmp_path):
        """A five-point trajectory in the driver's wrapper format
        (``{"parsed": <bench line>}``, pre-``schema_version`` like the
        first records were): made-up values with ~1% jitter."""
        paths = []
        for i, value in enumerate((1000.0, 1012.0, 991.0, 1006.0,
                                   1002.0), 1):
            path = tmp_path / f"BENCH_r{i:02d}.json"
            path.write_text(json.dumps({"n": i, "rc": 0, "parsed": {
                "metric": "resnet50_imagenet_train_imgs_per_sec_per_chip",
                "value": value, "unit": "images/sec",
                "vs_baseline": round(value / 50.0, 3)}}))
            paths.append(str(path))
        return paths

    def test_steady_trajectory_passes(self, tmp_path):
        """Acceptance: a steady five-point trajectory exits 0."""
        from bigdl_tpu.tools.regress import main

        paths = self._trajectory(tmp_path)
        assert len(paths) >= 5
        assert main(paths) == 0

    def test_synthetic_20pct_drop_fails(self, tmp_path):
        """Acceptance: a 20% throughput drop exits 1."""
        from bigdl_tpu.tools.regress import main

        paths = self._trajectory(tmp_path)
        with open(paths[-1]) as f:
            parsed = json.load(f)["parsed"]
        bad = dict(parsed, value=parsed["value"] * 0.8,
                   vs_baseline=parsed["vs_baseline"] * 0.8)
        cand = tmp_path / "cand.json"
        cand.write_text(json.dumps(bad))
        assert main(paths + ["--candidate", str(cand)]) == 1

    def test_latency_direction_is_lower_is_better(self, tmp_path):
        """*_ms latencies regress UP: a 50% TTFT increase exits 1, a
        50% decrease passes."""
        from bigdl_tpu.tools.regress import main

        base = {"schema_version": 2, "value": 100.0,
                "generation_ttft_ms_p50": 10.0}
        pts = []
        for i in range(3):
            p = tmp_path / f"t{i}.json"
            p.write_text(json.dumps(base))
            pts.append(str(p))
        slow = tmp_path / "slow.json"
        slow.write_text(json.dumps(
            dict(base, generation_ttft_ms_p50=15.0)))
        fast = tmp_path / "fast.json"
        fast.write_text(json.dumps(
            dict(base, generation_ttft_ms_p50=5.0)))
        assert main(pts + ["--candidate", str(slow)]) == 1
        assert main(pts + ["--candidate", str(fast)]) == 0

    def test_new_metric_never_fails_the_build(self, tmp_path):
        from bigdl_tpu.tools.regress import main

        pts = []
        for i in range(3):
            p = tmp_path / f"t{i}.json"
            p.write_text(json.dumps({"value": 100.0}))
            pts.append(str(p))
        cand = tmp_path / "cand.json"
        cand.write_text(json.dumps(
            {"value": 99.0, "brand_new_tokens_per_sec": 1.0}))
        assert main(pts + ["--candidate", str(cand)]) == 0

    def test_unknown_schema_version_refused(self, tmp_path, capsys):
        """Acceptance satellite: unknown schema_version exits 2 with a
        clear message."""
        from bigdl_tpu.tools.regress import main

        cand = tmp_path / "cand.json"
        cand.write_text(json.dumps({"schema_version": 99, "value": 1}))
        with pytest.raises(SystemExit) as exc:
            main(self._trajectory(tmp_path) + ["--candidate", str(cand)])
        assert exc.value.code == 2
        assert "schema_version" in capsys.readouterr().err

    def test_key_direction_rules(self):
        from bigdl_tpu.tools.regress import classify_key

        assert classify_key("resnet50_imgs_per_sec") == "higher"
        assert classify_key("value") == "higher"
        assert classify_key("programs_resnet50_train_mfu") == "higher"
        assert classify_key("generation_ttft_ms_p99") == "lower"
        assert classify_key("programs_resnet50_train_hbm_bytes") \
            == "lower"
        assert classify_key("zero_stage2_opt_state_bytes_per_chip") \
            == "lower"
        assert classify_key("generation_compiles") == "lower"
        assert classify_key("steps_per_sync") is None
        assert classify_key("unit") is None


# --------------------------------------------- exporter edge cases

class TestExporterEdgeCases:
    def test_prometheus_program_label_slashes_quotes_roundtrip(self):
        """Program-name labels carry slashes and may carry quotes or
        backslashes (registry keys are arbitrary) — the text
        exposition escaping must round-trip them exactly."""
        from bigdl_tpu.telemetry import (parse_prometheus_text,
                                         prometheus_text)

        r = telemetry.MetricsRegistry()
        g = r.gauge("serving/program/hbm_bytes", "d")
        gnarly = ['lm/v1/prefill/64', 'model "quoted"/v2',
                  'back\\slash/step', 'multi\nline/decode/8']
        for i, name in enumerate(gnarly):
            g.set(float(i + 1), program=name)
        text = prometheus_text(r.snapshot())
        parsed = parse_prometheus_text(text)
        for i, name in enumerate(gnarly):
            key = ("serving_program_hbm_bytes", (("program", name),))
            assert parsed[key] == float(i + 1), name

    def test_jsonl_roundtrip_of_program_profile_gauges(self, tmp_path):
        """A registered profile's gauges survive the JSONL snapshot
        round-trip with label and value intact."""
        from bigdl_tpu.telemetry import JsonlExporter, read_jsonl

        r = telemetry.MetricsRegistry()
        reg = programs.ProgramRegistry(metrics=r)
        reg.register("rt/model/step", "train",
                     analysis={"flops": 1.5e9, "bytes_accessed": 3e8,
                               "hbm_bytes": 2.5e8},
                     compile_s=1.25, items_per_call=32)
        reg.record_rate("rt/model/step", 1000.0)
        path = str(tmp_path / "m.jsonl")
        JsonlExporter(r, path).export(step=1)
        (rec,) = read_jsonl(path)
        by_name = {row["name"]: row for row in rec["metrics"]}
        flops = by_name["train/program/flops"]["series"]
        assert flops[0]["labels"] == {"program": "rt/model/step"}
        assert flops[0]["value"] == 1.5e9
        assert by_name["train/program/mfu"]["series"][0]["value"] > 0
        assert by_name["train/program/compile_s"]["series"][0][
            "value"] == 1.25

    def test_flight_bundle_metrics_json_is_snapshot_shaped(
            self, tmp_path):
        """diagnose ingestion contract: the bundle's metrics.json rows
        are registry-snapshot rows (name/kind/series)."""
        flight.arm(str(tmp_path))
        flight.note("fault", point="x")
        bundle = flight.dump("contract-test")
        assert bundle is not None
        with open(os.path.join(bundle, "metrics.json")) as f:
            snaps = json.load(f)
        for rows in snaps.values():
            for row in rows:
                assert {"name", "kind", "series"} <= set(row)
