"""The autotuner's contract: typed bounded spaces, ZERO-execution
static pruning (asserted via a backend_compile counter), per-candidate
failure isolation, deterministic byte-identical artifacts, and the
consumers (`perf --config`, the serving facade) actually applying the
winner."""
import json
import os

import pytest

from bigdl_tpu.autotune import (Candidate, Fingerprint,
                                FingerprintMismatchError, ServingSpace,
                                SpaceError, TrainSpace, TunedConfig,
                                TunedConfigError, enumerate_candidates,
                                load_tuned, save_tuned, static_prune)
from bigdl_tpu.autotune.defaults import (DEFAULT_TRAIN_CONFIG,
                                         INFEASIBLE_BATCH,
                                         SMOKE_HBM_BUDGET_BYTES,
                                         smoke_serving_space,
                                         smoke_train_space)
from bigdl_tpu.autotune.measure import measure_candidates
from bigdl_tpu.tools.autotune import run_autotune

# ----------------------------------------------------------- helpers

#: a foreign environment no CI host matches
_FOREIGN_FP = Fingerprint(device_kind="TPU v9", platform="tpu",
                          device_count=8, mesh_shape=(8,),
                          package_version="9.9.9")


def det_runner(cand, seed, iters):
    """Deterministic pseudo-measurement: stable across processes (no
    clocks, no RNG state) but sensitive to candidate, seed and iters."""
    h = sum(ord(c) * (i + 1) for i, c in enumerate(cand.cid))
    return float((h % 1000) + seed * 10 + iters)


def smoke_spaces():
    return {"train": smoke_train_space(),
            "serving": smoke_serving_space()}


# ------------------------------------------------------------- space

def test_space_bounds_raise_typed_errors():
    with pytest.raises(SpaceError):
        TrainSpace(steps_per_sync=(0,))
    with pytest.raises(SpaceError):
        TrainSpace(zero_stage=(4,))
    with pytest.raises(SpaceError):
        TrainSpace(precision=("f64",))
    with pytest.raises(SpaceError):
        TrainSpace(batch_size=(0,))
    with pytest.raises(SpaceError):  # ladder must ascend strictly
        ServingSpace(max_len=64, length_buckets=((64, 32),))
    with pytest.raises(SpaceError):  # top rung must equal max_len
        ServingSpace(max_len=64, length_buckets=((32,),))
    with pytest.raises(SpaceError):
        ServingSpace(speculation_k=(9,))


def test_enumeration_is_deterministic():
    a_valid, a_invalid = enumerate_candidates(smoke_train_space())
    b_valid, b_invalid = enumerate_candidates(smoke_train_space())
    assert [c.cid for c in a_valid] == [c.cid for c in b_valid]
    assert [(c.cid, r) for c, r in a_invalid] == \
        [(c.cid, r) for c, r in b_invalid]
    assert len(a_valid) + len(a_invalid) == 8  # the bounded smoke space
    # the hand-picked default point is IN the space, so the winner can
    # never lose to it on the same seeded windows
    assert any(all(c.config.get(k) == v
                   for k, v in DEFAULT_TRAIN_CONFIG.items())
               for c in a_valid)
    # every train candidate carries its model twin
    assert all(c.config["model"] == "mlp" for c in a_valid)


def test_constraints_reject_with_reasons():
    # flash on an attention-free model has nothing to dispatch
    valid, invalid = enumerate_candidates(
        TrainSpace(steps_per_sync=(1,), flash=(True,), model="mlp"))
    assert not valid and len(invalid) == 1
    assert "flash" in invalid[0][1]
    # ZeRO needs the batch to split across the data mesh
    valid, invalid = enumerate_candidates(
        TrainSpace(zero_stage=(2,), batch_size=(3,)), ndev=2)
    assert not valid and "divisible" in invalid[0][1]
    # speculation manages its own cache seeding
    valid, invalid = enumerate_candidates(ServingSpace(
        max_len=64, length_buckets=((64,),), speculation_k=(2,),
        prefix_cache_bytes=(1 << 20,)))
    assert not valid and "prefix_cache" in invalid[0][1]


# ------------------------------------------------------------- prune

def test_static_prune_rejects_infeasible_with_zero_compiles():
    """The footprint gate is eval_shape-only: the deliberately
    oversized smoke batch is rejected before ANY XLA compilation (and
    the counter that says so is shown to see a compile that happens)."""
    import jax
    import numpy as np
    from _compile_counter import count_compiles

    valid, _ = enumerate_candidates(smoke_train_space())
    with count_compiles() as calls:
        report = static_prune(valid,
                              hbm_budget=SMOKE_HBM_BUDGET_BYTES,
                              contract_checks=False)
    assert calls == [], f"static prune compiled {len(calls)} programs"
    with count_compiles() as forced:
        jax.jit(lambda x: x * 5 - 2)(np.ones(7, np.float32))
    assert len(forced) >= 1
    assert {p.candidate.config["batch_size"] for p in report.pruned} \
        == {INFEASIBLE_BATCH}
    assert {c.config["batch_size"] for c in report.kept} == {16}
    # every drop is auditable: stage + a budget-bearing reason
    for p in report.pruned:
        assert p.stage == "hbm"
        assert str(SMOKE_HBM_BUDGET_BYTES) in p.reason


def test_contract_gate_passes_feasible_candidates():
    """Survivors are lowered and checked against the compiled-program
    contract (compiles happen; executions don't)."""
    valid, _ = enumerate_candidates(smoke_train_space())
    feasible = [c for c in valid
                if c.config["batch_size"] != INFEASIBLE_BATCH][:2]
    report = static_prune(feasible,
                          hbm_budget=SMOKE_HBM_BUDGET_BYTES)
    assert [c.cid for c in report.kept] == [c.cid for c in feasible]


# ----------------------------------------------------------- measure

def test_crashing_candidate_is_isolated():
    """One exploding window never takes down the sweep: the failure is
    classified (fatal fails fast, transient gets one retry) and every
    other candidate still gets measured."""
    valid, _ = enumerate_candidates(smoke_train_space())
    feasible = [c for c in valid
                if c.config["batch_size"] != INFEASIBLE_BATCH]
    bad_cid = feasible[0].cid
    attempts = {}

    def runner(cand, seed, iters):
        attempts[cand.cid] = attempts.get(cand.cid, 0) + 1
        if cand.cid == bad_cid:
            raise RuntimeError("window exploded")
        return det_runner(cand, seed, iters)

    results = measure_candidates(feasible, seed=0, iters=1,
                                 runner=runner)
    assert len(results) == len(feasible)
    by_cid = {r.candidate.cid: r for r in results}
    bad = by_cid[bad_cid]
    assert not bad.ok and bad.error_kind == "transient"
    assert "window exploded" in bad.error
    assert attempts[bad_cid] == 2  # transient => one retry
    assert all(r.ok for cid, r in by_cid.items() if cid != bad_cid)


def test_fatal_failure_is_not_retried():
    valid, _ = enumerate_candidates(smoke_train_space())
    cand = [c for c in valid if c.config["batch_size"] == 16][0]
    attempts = []

    def runner(c, seed, iters):
        attempts.append(1)
        raise ValueError("mis-wired candidate")  # FATAL_TYPES

    (res,) = measure_candidates([cand], runner=runner)
    assert not res.ok and res.error_kind == "fatal"
    assert len(attempts) == 1


# ---------------------------------------------- determinism + artifact

def test_same_seed_identical_leaderboard_and_bytes(tmp_path):
    """The acceptance bound: same seed + same (injected) runner =>
    identical leaderboard and byte-identical tuned.json."""
    logs = []
    kw = dict(seed=7, iters=2, spaces=smoke_spaces(),
              hbm_budget=SMOKE_HBM_BUDGET_BYTES, runner=det_runner,
              log=logs.append)
    a = run_autotune(("train", "serving"), **kw)
    b = run_autotune(("train", "serving"), **kw)
    assert a.leaderboard == b.leaderboard
    assert a.to_json() == b.to_json()
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    save_tuned(a, str(pa))
    save_tuned(b, str(pb))
    assert pa.read_bytes() == pb.read_bytes()
    # every dropped candidate was logged with its stage + reason
    pruned_lines = [l for l in logs if l.startswith("# pruned ")]
    assert len(pruned_lines) == len(a.pruned) + len(b.pruned)
    for line in pruned_lines:
        entry = json.loads(line[len("# pruned "):])
        assert entry["stage"] and entry["reason"]
    # round-trip: the loaded artifact reproduces the winners
    loaded = load_tuned(str(pa), fingerprint=a.fingerprint)
    assert set(loaded.winners) == {"train", "serving"}
    assert loaded.seed == 7


def test_winner_beats_default_on_same_seed():
    """The default config is a point in the smoke space, so the sweep's
    winner is >= it by construction on the same seeded windows."""
    cfg = run_autotune(("train",), seed=3, iters=1,
                       spaces=smoke_spaces(),
                       hbm_budget=SMOKE_HBM_BUDGET_BYTES,
                       runner=det_runner, log=lambda *_: None)
    ok = [e for e in cfg.leaderboard if e["ok"]]
    best = max(e["objective"] for e in ok)
    default = [e for e in ok
               if all(e["config"].get(k) == v
                      for k, v in DEFAULT_TRAIN_CONFIG.items())]
    assert default and best >= default[0]["objective"]
    assert cfg.winner("train")  # present and typed


def test_fingerprint_mismatch_is_typed(tmp_path):
    cfg = TunedConfig(fingerprint=_FOREIGN_FP, seed=0,
                      winners={"train": dict(DEFAULT_TRAIN_CONFIG)})
    path = str(tmp_path / "tuned.json")
    save_tuned(cfg, path)
    with pytest.raises(FingerprintMismatchError) as ei:
        load_tuned(path)
    # the typed error carries the per-field diff for the message
    assert "device_kind" in ei.value.mismatches
    # explicit escape hatches: inspect anyway, or pin the fingerprint
    assert load_tuned(path, allow_mismatch=True).winners["train"]
    assert load_tuned(path, fingerprint=_FOREIGN_FP).seed == 0


def test_unknown_schema_version_is_refused(tmp_path):
    cfg = TunedConfig(fingerprint=_FOREIGN_FP, seed=0,
                      winners={"train": {}})
    raw = json.loads(cfg.to_json())
    raw["schema_version"] = 99
    path = tmp_path / "tuned.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(TunedConfigError, match="schema_version"):
        load_tuned(str(path), allow_mismatch=True)


def test_missing_regime_winner_is_typed():
    cfg = TunedConfig(fingerprint=_FOREIGN_FP, seed=0,
                      winners={"train": {}})
    with pytest.raises(TunedConfigError, match="serving"):
        cfg.winner("serving")


def test_save_is_atomic_no_tmp_left_behind(tmp_path):
    cfg = TunedConfig(fingerprint=_FOREIGN_FP, seed=0)
    path = str(tmp_path / "tuned.json")
    save_tuned(cfg, path)
    assert os.listdir(tmp_path) == ["tuned.json"]


# --------------------------------------------------------- consumers

def _tuned_artifact(tmp_path, train_winner=None, serving_winner=None):
    winners = {}
    if train_winner is not None:
        winners["train"] = train_winner
    if serving_winner is not None:
        winners["serving"] = serving_winner
    cfg = TunedConfig(fingerprint=Fingerprint.current(), seed=0,
                      winners=winners)
    path = str(tmp_path / "tuned.json")
    save_tuned(cfg, path)
    return path


def test_perf_config_applies_the_winner(tmp_path, capsys):
    """`perf --config tuned.json` applies K / precision / batch /
    kernels onto the run — spied through build_train_step and the JSON
    tail (the CLI flags all say otherwise)."""
    path = _tuned_artifact(tmp_path, train_winner={
        "steps_per_sync": 2, "zero_stage": 0,
        "precision": "bf16_mixed", "flash": False, "batch_size": 4,
        "model": "mlp"})
    from bigdl_tpu.optim import optimizer as opt_mod
    from bigdl_tpu.tools import perf
    from bigdl_tpu import kernels
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator
    seen = {}
    orig = opt_mod.build_train_step

    def spying(model, criterion, optim, **kw):
        seen.update(kw)
        return orig(model, criterion, optim, **kw)

    # perf.main mutates process globals by design (compute dtype, kernel
    # config, seed) — snapshot them so later tests see the defaults.
    saved_dtype = Engine.compute_dtype()
    saved_kernels = kernels.get_config()
    saved_seed = RandomGenerator.get_seed()
    opt_mod.build_train_step = spying
    try:
        perf.main(["--model", "lenet", "--batch-size", "32",
                   "--iterations", "1", "--warmup", "0",
                   "--config", path])
    finally:
        opt_mod.build_train_step = orig
        Engine.set_compute_dtype(saved_dtype)
        kernels.configure(saved_kernels)
        RandomGenerator.set_seed(saved_seed)
    assert seen["precision"] is not None  # bf16_mixed policy applied
    assert seen["zero"] is None
    tail = json.loads([l for l in capsys.readouterr().out.splitlines()
                       if l.startswith("{")][-1])
    assert tail["steps_per_sync"] == 2     # not the CLI default 1
    assert tail["batch_size"] == 4         # not the CLI's 32
    assert tail["dtype"] == "bf16_mixed"
    assert tail["kernels"] == "off"
    assert set(tail["tuned_applied"]) == {
        "steps_per_sync", "zero", "precision", "batch_size", "kernels"}


def test_serving_facade_applies_the_winner(tmp_path):
    from bigdl_tpu.generation import GenerationConfig, apply_tuned_config
    path = _tuned_artifact(tmp_path, serving_winner={
        "length_buckets": [32, 64], "slots": 2, "speculation_k": 0,
        "prefix_cache_bytes": 1 << 20})
    cfg = apply_tuned_config(path, base=GenerationConfig(max_queue=7))
    assert cfg.length_buckets == (32, 64)
    assert cfg.max_len == 64        # follows the ladder's top rung
    assert cfg.slots == 2
    assert cfg.prefix_cache_bytes == 1 << 20
    assert cfg.max_queue == 7       # untouched base fields survive


def test_serving_facade_refuses_speculative_winner(tmp_path):
    from bigdl_tpu.generation import apply_tuned_config
    path = _tuned_artifact(tmp_path, serving_winner={
        "length_buckets": [64], "slots": 4, "speculation_k": 2,
        "prefix_cache_bytes": 0})
    with pytest.raises(TunedConfigError, match="[Ss]pecul"):
        apply_tuned_config(path)


def test_apply_tuned_optimizer_goes_through_setters():
    from bigdl_tpu.autotune import apply_tuned_optimizer
    from bigdl_tpu.parallel import ZeroConfig

    calls = {}

    class FakeOpt:
        def set_steps_per_sync(self, k):
            calls["k"] = k

        def set_zero(self, z):
            calls["zero"] = z

        def set_precision(self, p):
            calls["precision"] = p

    cfg = TunedConfig(fingerprint=_FOREIGN_FP, seed=0, winners={
        "train": {"steps_per_sync": 8, "zero_stage": 2,
                  "precision": "f32"}})
    apply_tuned_optimizer(cfg, FakeOpt())
    assert calls["k"] == 8
    assert isinstance(calls["zero"], ZeroConfig) \
        and calls["zero"].stage == 2
    assert calls["precision"] is None  # f32 == no mixed policy


# ----------------------------------------------------------- wiring

def test_autotune_instruments_are_audited():
    """check --telemetry-audit sees the sweep's instruments via the
    same collector it audits everything else with."""
    from bigdl_tpu.tools.check import collect_instrument_names
    names = set(collect_instrument_names())
    assert {"autotune/sweep/candidates_total",
            "autotune/sweep/pruned_static",
            "autotune/sweep/measured",
            "autotune/sweep/best_objective"} <= names


def test_flash_decision_pairs_equal_configs():
    from bigdl_tpu.autotune.measure import MeasureResult
    from bigdl_tpu.tools.autotune import flash_decision

    def result(flash, obj):
        items = dict(DEFAULT_TRAIN_CONFIG, flash=flash,
                     model="transformer_lm")
        cand = Candidate("train", tuple(sorted(items.items())))
        return MeasureResult(cand, ok=True, objective=obj,
                             objective_name="train_steps_per_sec")

    d = flash_decision([result(True, 200.0), result(False, 100.0)])
    assert d["decision"] == "on"
    assert d["pairs"][0]["speedup"] == 2.0
    d = flash_decision([result(True, 50.0), result(False, 100.0)])
    assert d["decision"] == "off"
    assert flash_decision([])["decision"] == "no-evidence"
