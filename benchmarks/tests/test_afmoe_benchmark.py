"""The afmoe family in the harness (CPU, a tiny configuration beside
``tiny-gpt2``; no chip is asked for): one ``--allow-cpu`` rehearsal of
the closed-loop driver over a decoder with rings, a global entry and a
share of the experts, one traced rehearsal that names the cell's
per-layer metrics, and ``--stand-in`` runs that have to say ``correct:
false``. Run with ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests``.
"""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import run as harness            # noqa: E402
from benchmarks.models import afmoe              # noqa: E402

CELL = "trinity_ep8_serve_agent"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_afmoe") / "b"
    shutil.copytree(os.path.join(HERE, "tiny"), root)
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "metrics")
    return str(root / "bench_tiny_afmoe.json")


def _run(capsys, bench, *, seed=2147485102, trace=0, stand_in=None):
    argv = ["--bench-file", bench, "--workload", "tiny_agent", "--seed",
            str(seed), "--seconds", "1", "--trace", str(trace),
            "--allow-cpu"] + (["--stand-in", stand_in] if stand_in else [])
    rc = harness.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_rehearsal_serves_the_tiny_decoder_and_is_correct(capsys, tree):
    rc, line = _run(capsys, tree)
    assert rc == 0 and line["correct"] and line["rehearsal"]
    assert line["failed"] == 0 and line["attempted"] > 6
    assert line["checks"]["gap_max"]["value"] <= 1e-4
    assert line["log"]["window_compiles"] == 0
    assert line["log"]["decoded_tokens"] > 100


def test_traced_rehearsal_names_the_cells_metrics(capsys, tree):
    """The CPU has no Mosaic kernel, so the two rooflines find nothing
    to read and are left out without raising; the program's counters and
    spans are read."""
    rc, line = _run(capsys, tree, trace=1)
    assert rc == 0 and line["correct"]
    counted = set(line["rehearsal_counts"])
    assert {"moe_experts_touched_pct.serve",
            "moe_pairs_per_expert_max.serve",
            "engine_host_pct.serve"} <= counted
    assert not {"moe_experts_roofline",
                "gqa_decode_attn_roofline"} & counted


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_a_lower_precision_stand_in_is_not_correct(capsys, tree, control):
    rc, line = _run(capsys, tree, stand_in=control)
    assert rc == 0 and line["correct"] is False
    assert line["stand_in"] == control
    assert line["log"]["program"]["gap_max"] <= 1e-4


def test_the_cell_is_the_issues():
    """ISSUE 28's traffic, cell and metrics as BENCHMARK.json has them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("trinity-large-ep8", "closed_agent", 1)
    t = json.load(open(os.path.join(BENCH, "traffic",
                                    "closed_agent.json")))
    assert t["clients"] == t["slots"] and t["slots"] in (48, 40, 32)
    assert (t["max_len"], t["length_buckets"]) == (6144, [4096, 6144])
    assert t["prompt_len"] == {"dist": "uniform", "lo": 3200, "hi": 4000}
    assert t["new_tokens"] == {"dist": "uniform", "lo": 1024, "hi": 2048}
    assert (t["shared_prefix"], t["pool"], t["check_requests"]) == \
        (0, 4096, 8)
    assert (t["warm_seconds"], t["trace_seconds"]) == (5.0, 10.0)
    assert t["weights_dtype"] == t["kv_dtype"] == "bfloat16"
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "serve_tokens_per_s", "mfu_pct.serve", "device_idle_pct.serve",
        "engine_host_pct.serve", "moe_experts_roofline",
        "gqa_decode_attn_roofline", "moe_experts_touched_pct.serve",
        "moe_pairs_per_expert_max.serve"}
    # not itl_ms_p95, nor what moves it: 3% of this mix's gaps hold a
    # prefill, so the 95th percentile sits in the thin tail of plain
    # steps and spread 14% over six seeds on the chip (PERF.md section 2)


def test_the_configuration_keeps_every_published_width():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "trinity-large-ep8.json")))
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        for row in map(json.loads, open(catalog)):
            if row["name"] == "Trinity-Large-Preview":
                published = row["config"]
    if published is not None:
        differs = {k for k, v in published.items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    z = afmoe.dims(cfg)
    assert (z["h"], z["H"], z["Hkv"], z["d"], z["F"], z["Fe"], z["Er"],
            z["k"], z["W"], z["scale"]) == (3072, 48, 8, 128, 12288, 3072,
                                            256, 4, 4096, 2.448)
    assert (z["L"], z["dense"], z["E"], z["V"]) == (5, 1, 32, 25024)
    assert z["window"] == [True, True, True, True, False]


# ------------------------------------------------ the new readers alone

class _Ctx:
    """What a reader is handed, by hand."""

    def __init__(self, ops):
        self.reduced = {"ops": ops}
        self.peaks = harness.peaks_for("TPU v5 lite")
        self.family = afmoe
        self.config = json.load(open(os.path.join(
            BENCH, "configs", "trinity-large-ep8.json")))
        self.traffic = {"weights_dtype": "bfloat16"}
        self.log = {}


def test_the_ring_readers_on_planted_records_and_on_none():
    """Two decode calls and a prefill call in the ring: the roofline is
    the larger of bytes and operations over the kernel's time, the
    medians take decode calls only; with no record (the parent: no
    expert layer, no such record) each returns nothing and raises
    nothing."""
    from bigdl_tpu import telemetry

    from benchmarks.readers import moe_roofline, ring_record_median

    ctx = _Ctx({"%bigdl_moe_gmm.3 = bf16[704,3072] custom-call": 0.010,
                "%fusion.1 = f32[8]": 1.0})
    tracer = telemetry.enable()
    tracer.clear()
    try:
        assert moe_roofline.read(ctx, "bigdl_moe_gmm") is None
        assert ring_record_median.read(
            ctx, "serving/moe/step", "experts_touched") is None
        for kind, touched, pairs, most in (("decode", 60.0, 90.0, 3.0),
                                           ("decode", 68.0, 100.0, 5.0),
                                           ("prefill", 128.0, 2000.0, 90.0)):
            tracer.record("serving/moe/step", 0.0, args={
                "kind": kind, "layers": 4, "experts_touched": touched,
                "local_pairs": pairs, "pairs_per_expert_max": most})
        # bytes: 256 experts x 56.6 MB / 819 GB/s = 17.7 ms; operations:
        # 2190 pairs x 56.6 MFLOP / 197 TFLOP/s = 0.63 ms; over 10 ms
        got = moe_roofline.read(ctx, "bigdl_moe_gmm")
        assert got == pytest.approx(
            100 * 256 * 3 * 3072 * 3072 * 2 / 819e9 / 0.010)
        assert ctx.log["moe_window"]["calls"] == 3
        assert ring_record_median.read(
            ctx, "serving/moe/step", "experts_touched", kind="decode",
            per="layers", pct_of_config="num_experts") == pytest.approx(
                100 * 16 / 32)
        assert ring_record_median.read(
            ctx, "serving/moe/step", "pairs_per_expert_max",
            kind="decode") == 4.0
        assert moe_roofline.read(_Ctx({"%fusion.1 = f32[8]": 1.0}),
                                 "bigdl_moe_gmm") is None
    finally:
        telemetry.disable()
        tracer.clear()
