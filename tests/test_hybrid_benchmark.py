"""The nemotron_h family in the harness (CPU, the tiny configuration
``benchmarks/tests/tiny/configs/tiny-nemotron-h.json``; no chip is asked
for): one ``--allow-cpu`` rehearsal of the closed-loop driver over a
decoder that keeps recurrent states beside K/V, one traced rehearsal
that names the cell's per-layer metrics, and ``--stand-in`` runs that
have to say ``correct: false``."""
import json
import math
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")

from benchmarks import run as harness            # noqa: E402


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_nemotron_h") / "b"
    shutil.copytree(os.path.join(BENCH, "tests", "tiny"), root)
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "metrics")
    return str(root / "bench_tiny_nemotron_h.json")


def _run(capsys, bench, *, seed=2147485102, trace=0, stand_in=None):
    argv = ["--bench-file", bench, "--workload", "tiny_sessions", "--seed",
            str(seed), "--seconds", "1", "--trace", str(trace),
            "--allow-cpu"] + (["--stand-in", stand_in] if stand_in else [])
    rc = harness.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_rehearsal_serves_the_tiny_hybrid_and_is_correct(capsys, tree):
    rc, line = _run(capsys, tree)
    assert rc == 0 and line["correct"] and line["rehearsal"]
    assert line["failed"] == 0 and line["attempted"] > 6
    assert line["checks"]["gap_max"]["value"] <= 1e-4
    assert line["log"]["window_compiles"] == 0
    assert line["log"]["decoded_tokens"] > 100


def test_traced_rehearsal_names_the_cells_metrics(capsys, tree):
    """The CPU has no Mosaic kernel, so the three rooflines find nothing
    to read and are left out without raising; the program's counters,
    the state's bytes among them, are read."""
    rc, line = _run(capsys, tree, trace=1)
    assert rc == 0 and line["correct"]
    counted = set(line["rehearsal_counts"])
    assert {"moe_experts_touched_pct.serve",
            "moe_pairs_per_expert_max.serve", "engine_host_pct.serve",
            "ssm_state_gb_per_step.serve"} <= counted
    assert not {"moe_experts_roofline", "gqa_decode_attn_roofline",
                "ssm_decode_roofline"} & counted


@pytest.mark.parametrize("control", ["int8", "fp8", "state_bf16"])
def test_a_lower_precision_stand_in_is_not_correct(capsys, tree, control):
    """``state_bf16`` keeps only the recurrent state in bfloat16: its
    tokens lie as near the reference's as the program's (the gaps cannot
    tell it), and it fails by the bytes its state holds a slot."""
    rc, line = _run(capsys, tree, stand_in=control)
    assert rc == 0 and line["correct"] is False
    assert line["stand_in"] == control
    assert line["log"]["program"]["gap_max"] <= 1e-4


def test_a_program_that_keeps_its_state_in_bfloat16_is_not_correct(
        capsys, tree, monkeypatch):
    """The planted fault: the program's mixer declares a bfloat16 state
    and the cache allocates it so. Every request is answered, and the
    run is not ``correct`` whatever its tokens read: a slot of the
    served cache holds less than the configuration's float32 state
    (``serving/cache/state_bytes`` over ``serving/cache/slots``)."""
    from benchmarks.models import nemotron_h
    from bigdl_tpu.nn.ssm import Mamba2Mixer

    declared = Mamba2Mixer.cache_arrays
    monkeypatch.setattr(Mamba2Mixer, "cache_arrays", lambda self: tuple(
        (name, shape, "bfloat16" if name == "ssm" else dtype)
        for name, shape, dtype in declared(self)))
    rc, line = _run(capsys, tree)
    assert rc == 0 and line["correct"] is False and line["failed"] == 0
    assert math.isnan(line["checks"]["gap_max"]["value"])
    cfg = harness.load_json(os.path.dirname(tree), "configs",
                            "tiny-nemotron-h.json")
    assert 0 < nemotron_h.served_state_bytes() \
        < 3 * nemotron_h.state_bytes(cfg)


def test_the_new_readers_return_nothing_where_nothing_is_recorded():
    """On a program without the records (the parent commit's), the new
    metrics' readers return None and do not raise."""
    import types

    from benchmarks.models import gpt2, nemotron_h
    from benchmarks.readers import ring_record_median_gb, ssm_roofline
    from bigdl_tpu import telemetry

    telemetry.tracer().clear()
    ctx = types.SimpleNamespace(
        reduced={"ops": {"bigdl_ssm_decode.1": 0.5}}, peaks={
            "hbm_bytes_per_s": 819e9}, family=nemotron_h, config={},
        log={})
    assert ssm_roofline.read(ctx, "bigdl_ssm_decode") is None
    assert ring_record_median_gb.read(
        ctx, record="serving/ssm/step", field="state_bytes",
        kind="decode") is None
    ctx.family = gpt2                        # a family without the bytes
    assert ssm_roofline.read(ctx, "bigdl_ssm_decode") is None
    ctx.family, ctx.reduced = nemotron_h, {"ops": {}}    # no such kernel
    assert ssm_roofline.read(ctx, "bigdl_ssm_decode") is None


def test_the_by_hand_profile_runs_at_the_tiny_size(tree, tmp_path):
    """``benchmarks/profile_programs.py`` (what ``PERF.md`` section 5's
    prefill account was read from): both programs alone under the
    profiler, written where ``--out`` says."""
    from benchmarks import profile_programs

    out = tmp_path / "profile.txt"
    assert profile_programs.main([
        "--workload", "tiny_sessions", "--bench-file", tree, "--prefills",
        "2", "--decodes", "3", "--out", str(out)]) == 0
    text = out.read_text()
    assert "== prefill: 2 calls" in text and "== decode: 3 calls" in text
