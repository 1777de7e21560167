"""The benchmark's own tests (CPU, tiny sizes; no chip is asked for).

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
The tiny configuration and its traffic live under ``tests/tiny`` and are
never a cell. Limits in ``tests/tiny/traffic`` were set from CPU
readings of this tiny model, as PERF.md sets the cells' from the chip's.
"""
import glob
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny")

from benchmarks import run as harness            # noqa: E402
from benchmarks import trace_reduce as tr        # noqa: E402
from benchmarks.drivers import serve_closed      # noqa: E402
from benchmarks.models import gpt2               # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def tiny_tree(tmp_path_factory):
    """The tiny benchmark as a tree the harness can run: its own config
    and traffic, and the cells' metric files as they are (no copies of
    them are kept under tests/)."""
    root = tmp_path_factory.mktemp("tiny") / "b"
    shutil.copytree(TINY, root)
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "metrics")
    _run.tree = str(root)
    yield
    _run.tree = None


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _run(capsys, workload, *, seed=3, trace=0, control=0, bench=None,
         _break=None, seconds=1, stand_in=None):
    argv = ["--bench-file", bench or os.path.join(_run.tree,
                                                  "bench_tiny.json"),
            "--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--control", str(control),
            "--allow-cpu"] + (["--stand-in", stand_in] if stand_in else [])
    rc = harness.main(argv, _break=_break)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


# ----------------------------------------------------- trace reduction

def test_trace_reduction_on_the_recorded_trace():
    r = tr.reduce_trace(os.path.join(HERE, "tiny_trace.xplane.txt"))
    assert r["planes"] == ["/device:TPU:0"]
    assert r["window_s"] == pytest.approx(1000e-6)
    # busy: [100,500] u [700,900] us; the op at 1500 us is outside
    assert r["busy_s"] == pytest.approx(600e-6)
    ops = {tr.short_name(k): v for k, v in r["ops"].items()}
    # the while spans its body: self time 400 - 100 - 200
    assert ops["while.5 s32[]"] == pytest.approx(100e-6)
    assert ops["fusion.1 bf16[4,8]"] == pytest.approx(250e-6)
    assert ops["ragged_decode.3 f32[4,2,8]"] == pytest.approx(200e-6)
    assert "copy-start f32[8]" not in ops        # async line not counted
    assert r["modules"]["jit_step(123)"][0] == 2
    # the breakdown: one name for an operation of every unrolled layer
    assert r["top_ops"][0] == ["fusion bf16[4,8]", pytest.approx(250e-6)]
    assert tr.kind_name("%bitcast_reduce_fusion.214 = (bf16[16,64]{1,0}, "
                        "bf16[4,16]) fusion(") == \
        "bitcast_reduce_fusion bf16[16,64]"
    gaps = dict(map(tuple, r["gaps"]))
    assert gaps["bench/client_wait"] == pytest.approx(200e-6)
    assert gaps["bench/submit"] == pytest.approx(150e-6)
    assert gaps["host:unannotated"] == pytest.approx(50e-6)
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.idle_gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_a_trace_without_window_or_device_plane_fails(tmp_path):
    src = open(os.path.join(HERE, "tiny_trace.xplane.txt")).read()
    no_window = tmp_path / "a.xplane.txt"
    no_window.write_text(src.replace("bench/window", "other"))
    with pytest.raises(RuntimeError, match="bench/window"):
        tr.reduce_trace(str(no_window))
    no_device = tmp_path / "b.xplane.txt"
    no_device.write_text(src.replace("/device:TPU:0", "/device:OTHER"))
    with pytest.raises(RuntimeError, match="no device plane"):
        tr.reduce_trace(str(no_device))


# ------------------------------------------------- operations and bytes

def test_required_operations_against_hand_worked_values():
    m, s = _cfg("gpt2-medium"), _cfg("gpt2-small")
    assert gpt2.matmul_params(m) == 12 * 24 * 1024 ** 2 + 50257 * 1024
    # 6 x 353.45M + 12 x 24 x 1024 x 1024 = 2.42 GFLOP a token
    assert gpt2.train_flops_per_token(m, 1024) == pytest.approx(2.4227e9,
                                                                rel=1e-4)
    assert gpt2.train_flops_per_sample(m, 1024) == pytest.approx(2.481e12,
                                                                 rel=1e-3)
    assert gpt2.param_count(m) == pytest.approx(354.8e6, rel=2e-3)
    assert gpt2.param_count(s) == pytest.approx(124.4e6, rel=2e-3)
    # serving: 2 x (12 x 12 x 768^2 + 50257 x 768) + 4 x 12 x 768 x c
    assert gpt2.serve_flops_per_token(s, 100) == pytest.approx(
        2 * (84934656 + 38597376) + 4 * 12 * 768 * 100)
    span = sum(gpt2.serve_flops_per_token(s, p + 1) for p in range(5, 40))
    assert gpt2.serve_flops_span(s, 5, 40) == pytest.approx(span)
    # c x 2 x L x h x 4 bytes
    assert gpt2.kv_read_bytes(s, 1000, 4) == 1000 * 2 * 12 * 768 * 4


def test_an_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(KeyError, match="not in benchmarks/peaks.json"):
        harness.peaks_for("TPU v9 imaginary")


# ------------------------------------------------------ names and units

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_names_units_and_files_keep_to_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(json.dumps(bench)) < 64 << 10
    names = []
    for c in bench["configs"]:
        names.append(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(_NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert _UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        spec = json.load(open(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    assert all(_NAME.match(n) for n in names), names
    for path in glob.glob(os.path.join(BENCH, "**", "*"), recursive=True):
        rel = os.path.relpath(path, ROOT)
        if "__pycache__" not in rel:
            assert _PATH.match(rel), rel


def test_the_harness_takes_nothing_from_bench_py():
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        if os.path.basename(path).startswith("test_"):
            continue
        src = open(path).read()
        assert not re.search(r"^\s*(import bench\b|from bench\b)", src,
                             re.M), path


# ------------------------------------------------------------- traffic

def test_every_seed_draws_the_same_sizes_in_another_order():
    t = json.load(open(os.path.join(TINY, "traffic", "closed_tiny.json")))
    a = serve_closed.make_requests(t, 1, 1024)
    b = serve_closed.make_requests(t, 2 ** 31 + 7, 1024)
    n = t["clients"]
    size = lambda reqs: sorted((len(p), m) for p, m in reqs)
    # block by block of `clients` requests the same sizes, shuffled
    for i in range(n, 8 * n, n):
        assert size(a[i:i + n]) == size(b[i:i + n])
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert all(4 <= len(p) <= 36 and 8 <= m <= 24 for p, m in a[n:])
    # each client's first request is part-way through its output
    assert sorted(len(p) for p, _ in a[:n]) == sorted(len(p) for p, _ in b[:n])
    assert all(1 <= m <= 24 for _, m in a[:n])


def test_the_served_mix_is_the_public_benchmarks():
    """closed64: vLLM's sonnet defaults, 550 in, 150 out, the first 200
    ids of every prompt shared; other ids and weights with every seed."""
    t = json.load(open(os.path.join(BENCH, "traffic", "closed64.json")))
    assert "benchmark_serving.py" in t["source"]
    a = serve_closed.make_requests(t, 1, 50257)
    b = serve_closed.make_requests(t, 2 ** 31 + 7, 50257)
    n = t["clients"]
    assert {(len(p), m) for p, m in a[n:]} == {(550, 150)}
    assert all(len(p) + m <= t["max_len"] for p, m in a)
    assert all((p[:200] == a[0][0][:200]).all() for p, _ in a)
    assert (a[1][0][200:] != a[2][0][200:]).mean() > 0.99
    assert (a[0][0][:200] != b[0][0][:200]).mean() > 0.99
    firsts = [m for _, m in a[:n]]
    assert firsts == [m for _, m in b[:n]] and len(set(firsts)) > n // 4
    assert all(1 <= m <= 150 for m in firsts)


def test_live_tokens_follow_the_token_log():
    R = serve_closed._Req
    r = R(0, np.zeros(10, np.int32), 3)
    r.t_submit, r.times, r.t_done = 1.0, [1.1, 1.2, 1.4], 1.4
    assert serve_closed.live_tokens([r], [1.0, 1.15, 1.3, 1.5]) == \
        [0, 11, 12, 0]


def test_tails_count_every_request_and_a_failed_one_as_the_worst():
    R = serve_closed._Req
    ok = R(0, np.zeros(4, np.int32), 3)
    ok.t_submit, ok.times, ok.t_done = 1.0, [1.1, 1.2, 1.4], 1.4
    ok.tokens = np.zeros(3)
    bad = R(1, np.zeros(4, np.int32), 3)
    bad.t_submit, bad.error = 1.5, RuntimeError("died")
    s = serve_closed.summarise([ok, bad], 0.0, 2.0)
    assert s["sent"] == 2 and s["completed"] == 1 and s["failed"] == 1
    assert s["tokens_completed"] == 3 and s["tokens_delivered"] == 3
    # the rate counts a token where it lands, not where its request ends
    assert serve_closed.summarise([ok], 0.0, 1.3)["tokens_delivered"] == 2
    assert serve_closed.summarise([ok], 0.0, 1.3)["tokens_completed"] == 0
    assert s["ttft_ms"] == pytest.approx([100.0, 100.0])
    assert s["gaps_ms"] == pytest.approx([100.0, 200.0])
    assert serve_closed.percentile(list(range(1, 101)), 95) == 95


# ------------------------------------- the harness end to end, on a CPU

def test_without_a_tpu_nothing_runs_and_nothing_is_printed():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gpt2m_train_b4s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


@pytest.mark.parametrize("cell,e2e", [
    ("tiny_train", {"train_samples_per_s", "setup_s"}),
    ("tiny_serve", {"serve_tokens_per_s", "itl_ms_p95", "setup_s"})])
def test_rehearsal_end_to_end(capsys, cell, e2e):
    rc, line = _run(capsys, cell, seed=2 ** 31 + 11)
    assert rc == 0 and line["correct"] is True and line["rehearsal"]
    assert set(line["metrics"]) == e2e
    assert line["log"]["window_compiles"] == 0
    assert list(line)[-1] == "checks"
    # the traced run: on a CPU no device metric is printed under its name
    rc, line = _run(capsys, cell, seed=5, trace=1)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {} and line["rehearsal_counts"]
    assert line["device"]["busy_s"] > 0 and line["breakdown"]["device_ops"]


def test_a_cell_and_a_metric_are_added_as_files_alone(capsys, tmp_path):
    """A later PR adds a traffic file, a metric file and three entries;
    it edits nothing that is there."""
    shutil.copytree(_run.tree, tmp_path / "b")
    base = tmp_path / "b"
    t = json.load(open(base / "traffic" / "train_tiny.json"))
    t.update(batch=2, trace_windows=2)
    (base / "traffic" / "train_tiny_b2.json").write_text(json.dumps(t))
    (base / "metrics" / "busy_per_step_ms.new.json").write_text(
        json.dumps({"reader": "step_device_ms"}))
    bench = json.load(open(base / "bench_tiny.json"))
    bench["workloads"].append({"name": "tiny_train_b2",
                               "config": "tiny-gpt2",
                               "traffic": "train_tiny_b2", "chips": 1,
                               "why": "added by files alone"})
    bench["end_to_end"][0]["workloads"].append("tiny_train_b2")
    bench["per_layer"].append({
        "name": "busy_per_step_ms.new", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step builder",
        "moves": "train_samples_per_s", "workloads": ["tiny_train_b2"]})
    (base / "bench_tiny.json").write_text(json.dumps(bench))
    rc, line = _run(capsys, "tiny_train_b2", trace=1,
                    bench=str(base / "bench_tiny.json"))
    assert rc == 0 and line["correct"] is True
    assert line["rehearsal_counts"] == ["busy_per_step_ms.new"]


# ------------------------- the comparison that decides `correct` fails

@pytest.mark.parametrize("cell,stand_in", [
    ("tiny_train", "bf16_masters"), ("tiny_train", "fp8"),
    ("tiny_train", "fault_half_batch"), ("tiny_train", "fault_frozen"),
    ("tiny_serve", "int8")])
def test_the_controls_come_out_as_not_correct(capsys, cell, stand_in):
    """The reference in the next precision down, or with a fault
    planted, put in the program's place: the run itself says ``correct``
    false, while the program's own readings, kept in the log, pass.
    (The chip's readings are in PERF.md.)"""
    rc, line = _run(capsys, cell, seed=11, stand_in=stand_in)
    assert rc == 0 and line["correct"] is False
    assert line["stand_in"] == stand_in
    lim = {k: v["limit"] for k, v in line["checks"].items()}
    assert all(line["log"]["program"][k] <= v for k, v in lim.items()
               if k in line["log"]["program"])
    if stand_in == "fault_frozen":
        assert line["checks"]["dparam_gap"]["value"] == pytest.approx(1.0)


def test_an_unknown_stand_in_is_refused(capsys):
    with pytest.raises(SystemExit, match="this mix names"):
        _run(capsys, "tiny_train", stand_in="int3")


def _frozen(opt):
    method = opt.optim_method
    method.update = lambda grads, state, params, lr: (params, state)


def _half_batch(opt):
    inner = opt.criterion.apply
    opt.criterion.apply = lambda out, tgt: inner(
        out[: out.shape[0] // 2], tgt[: tgt.shape[0] // 2])


def _altered_token(svc):
    decode = svc.engine.decode

    def wrong(servable, kv, tokens, positions, active):
        logits, attend = decode(servable, kv, tokens, positions, active)
        logits = np.array(logits)
        logits[:, 7] += 1e3          # every decoded token becomes id 7
        return logits, attend
    svc.engine.decode = wrong


@pytest.mark.parametrize("cell,fault", [
    ("tiny_train", _frozen), ("tiny_train", _half_batch),
    ("tiny_serve", _altered_token)])
def test_a_broken_timed_path_is_not_correct(capsys, cell, fault):
    """The rest of a run driven with the timed path broken underneath:
    a step that returns its state unchanged; half of the batch left
    out, the mean taken over the rest; a token altered where it is
    produced. (One chip: there is no exchange to leave out.)"""
    rc, line = _run(capsys, cell, seed=7, _break=fault)
    assert rc == 0 and line["correct"] is False
