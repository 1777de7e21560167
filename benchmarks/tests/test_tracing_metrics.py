"""The per-layer metrics that read the program's own names (ISSUE 26):
the three readers on hand-made contexts, ``BENCHMARK.json``'s new
entries, and traced rehearsals of the tiny cells with those entries
appended to a copy of the tiny benchmark (CPU; no chip is asked for).

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
"""
import json
import os
import shutil
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import run as harness                     # noqa: E402
from benchmarks.readers import (module_ms, program_histogram,  # noqa: E402
                                span_share_pct)

SERVE, TRAIN = "gpt2s_serve_closed64", "gpt2m_train_b4s1024"
#: metric -> (cell, source, what its reader finds on a CPU)
NEW = {
    "decode_device_ms.serve": (SERVE, "device_trace", False),
    "prefill_device_ms.serve": (SERVE, "device_trace", False),
    "decode_dispatch_ms.serve": (SERVE, "program_span", True),
    "logits_d2h_ms.serve": (SERVE, "program_span", True),
    "sample_emit_ms.serve": (SERVE, "program_span", True),
    "engine_host_pct.serve": (SERVE, "program_span", True),
    "window_gap_ms.train": (TRAIN, "program_counter", True),
}


def _ctx(**kw):
    base = dict(reduced=None, spans={}, log={})
    base.update(kw)
    return types.SimpleNamespace(**base)


# --------------------------------------------------------- the readers

def test_module_ms_divides_a_programs_device_time_by_its_runs():
    modules = {"jit_serving_decode(14046497487864426710)": [51, 4.829032],
               "jit_serving_prefill(2061594452506103945)": [18, 0.756042],
               "jit_dynamic_slice(1264391001803219225)": [18, 4e-5]}
    ctx = _ctx(reduced={"modules": modules})
    assert module_ms.read(ctx, "serving_decode") == pytest.approx(
        4829.032 / 51)
    assert module_ms.read(ctx, "serving_prefill") == pytest.approx(
        756.042 / 18)
    # one name, two rungs of the ladder: one program each
    modules["jit_serving_decode(7)"] = [9, 0.170968]
    assert module_ms.read(ctx, "serving_decode") == pytest.approx(
        5000.0 / 60)


@pytest.mark.parametrize("reduced", [
    None, {"modules": {}}, {"modules": {"jit_fn(123)": [51, 4.8]}}])
def test_module_ms_finds_nothing_without_the_name(reduced):
    """No trace, a CPU's trace (no ``XLA Modules`` line), the parent
    commit's (both programs are ``jit_fn``): nothing, never 0."""
    assert module_ms.read(_ctx(reduced=reduced), "serving_decode") is None


def test_span_share_pct_is_a_share_of_other_spans():
    spans = {"serving/decode/device_wait": [0.09] * 50,
             "serving/prefill/device_wait": [0.04] * 10,
             "serving/idle": [0.1],
             "serving/decode": [0.1] * 50, "serving/sample": [0.002] * 50,
             "serving/admit": [0.045] * 10}
    ctx = _ctx(spans=spans, log={"window_s": 6.0})
    waits = ["serving/decode/device_wait", "serving/prefill/device_wait",
             "serving/idle"]
    loop = ["serving/idle", "serving/admit", "serving/decode",
            "serving/sample"]
    assert span_share_pct.read(ctx, waits, loop) == pytest.approx(
        100 * 5.0 / 5.65)
    assert span_share_pct.read(ctx, waits, loop, complement=True) == \
        pytest.approx(100 * (1 - 5.0 / 5.65))
    # a span that was not recorded counts nothing; the others still do
    assert span_share_pct.read(ctx, ["serving/idle", "no/such"], loop) == \
        pytest.approx(100 * 0.1 / 5.65)


@pytest.mark.parametrize("spans", [
    {},                                                  # the parent commit
    {"serving/decode": [0.1]},                           # no wait on record
    {"serving/idle": [0.1]}])                            # no whole
def test_span_share_pct_finds_nothing_without_its_spans(spans):
    ctx = _ctx(spans=spans, log={"window_s": 6.0})
    assert span_share_pct.read(ctx, ["serving/idle"], ["serving/decode"],
                               complement=True) is None


def test_program_histogram_reads_the_programs_registry():
    from bigdl_tpu import telemetry

    name = "bench/test_tracing/histogram_ms"
    h = telemetry.histogram(name, "made for this test")
    assert program_histogram.read(_ctx(), name) is None     # nothing seen
    for v in (30.0, 10.0, 20.0, 40.0):
        h.observe(v)
    assert program_histogram.read(_ctx(), name) == 20.0     # nearest rank
    assert program_histogram.read(_ctx(), name, q=100) == 40.0
    assert program_histogram.read(_ctx(), name, q=1) == 10.0
    # no such instrument (the parent commit), or one of another kind
    assert program_histogram.read(_ctx(), "train/optimizer/no_such") is None
    assert program_histogram.read(_ctx(), "train/optimizer/steps") is None


# ----------------------------------------------- the benchmark's entries

def test_the_new_entries_are_appended_and_name_their_one_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in tail:
        cell, source, _ = NEW[m["name"]]
        assert m["workloads"] == [cell] and m["source"] == source
        assert cell in e2e[m["moves"]]["workloads"]
        assert m["better"] == "lower"
        assert m["layer"] in {x["layer"] for x in bench["per_layer"][:9]}
        spec = json.load(open(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json")))
        assert len(spec["what"]) > 80
    # and nothing that was there has moved
    assert [m["name"] for m in bench["per_layer"][:9]] == [
        "mfu_pct.train", "device_idle_pct.train", "step_device_ms.train",
        "mfu_pct.serve", "device_idle_pct.serve", "decode_attn_roofline",
        "decode_step_ms.serve", "prefill_ms.serve", "ttft_ms_p95.serve"]


# ------------------------------------------ traced rehearsals, on a CPU

@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """The tiny benchmark with this PR's entries appended, each under
    the tiny cell that stands for its own: files and entries alone."""
    base = tmp_path_factory.mktemp("tiny") / "b"
    shutil.copytree(os.path.join(HERE, "tiny"), base)
    shutil.copytree(os.path.join(BENCH, "metrics"), base / "metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer"][-len(NEW):]
    bench = json.load(open(base / "bench_tiny.json"))
    for m in entries:
        tiny = "tiny_serve" if m["workloads"] == [SERVE] else "tiny_train"
        bench["per_layer"].append(dict(m, workloads=[tiny]))
    (base / "bench_tiny.json").write_text(json.dumps(bench))
    return str(base / "bench_tiny.json")


@pytest.mark.parametrize("cell", ["tiny_serve", "tiny_train"])
def test_a_traced_rehearsal_counts_the_metrics_a_cpu_can(capsys, cell,
                                                         tiny_bench):
    rc = harness.main(["--bench-file", tiny_bench, "--workload", cell,
                       "--seed", "5", "--seconds", "1", "--trace", "1",
                       "--allow-cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["metrics"] == {}
    mine = {n for n, (c, _, on_cpu) in NEW.items()
            if on_cpu and (c == SERVE) == (cell == "tiny_serve")}
    assert mine and mine <= set(line["rehearsal_counts"])
    # the CPU's trace has no XLA Modules line: left out, not zero
    assert not {n for n, (_, _, on_cpu) in NEW.items() if not on_cpu} \
        & set(line["rehearsal_counts"])
