"""Windowed step driver (Optimizer.set_steps_per_sync): K fused train
steps per host sync must be OBSERVABLY identical to the per-step loop —
seeded K=1 vs K∈{4,8} runs produce the same final params/losses on both
the host-feed and device-feed paths, windows flush at every
validation/checkpoint/epoch boundary, loss-dependent triggers force
per-step fallback, and K-step mode compiles exactly one program per
(K, shape) pair. Plus the window plumbing itself: trigger dependency
metadata/peek, ``stack_windows``, and the prefetch stager's clean exit
when the consumer abandons the iterator mid-stream."""
import threading
import time

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.dataset.device_dataset import DeviceCachedArrayDataSet
from bigdl_tpu.dataset.prefetch import (batch_signature, device_prefetch,
                                        stack_windows)
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.optim import (LocalOptimizer, SGD, Loss, every_epoch,
                             max_iteration, min_loss, several_iteration)
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.utils.random import RandomGenerator


# ---------------------------------------------------------------- helpers

def _toy_xy(n=96, d=8, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, d).astype(np.float32) * 3
    X = np.stack([centers[i % classes]
                  + rng.randn(d).astype(np.float32) * 0.5
                  for i in range(n)])
    y = np.array([i % classes + 1 for i in range(n)], np.float32)
    return X, y


def _mlp():
    return nn.Sequential().add(nn.Linear(8, 16)).add(nn.Tanh()) \
        .add(nn.Linear(16, 3)).add(nn.LogSoftMax())


def _host_ds(n=96, batch=32, seed=0):
    X, y = _toy_xy(n, seed=seed)
    return DataSet.array([Sample(X[i], y[i]) for i in range(n)]) \
        .transform(SampleToMiniBatch(batch))


def _img_model():
    return nn.Sequential().add(nn.Reshape([64])).add(nn.Linear(64, 3)) \
        .add(nn.LogSoftMax())


def _device_ds(n=64, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    imgs = rng.randint(0, 255, (n, 1, 8, 8), np.uint8)
    labels = (rng.randint(0, 3, n) + 1).astype(np.float32)
    return DeviceCachedArrayDataSet(imgs, labels, batch, crop=(8, 8),
                                    flip=True, mean=(0.0,), std=(255.0,))


def _params_of(model):
    import jax
    return [np.asarray(l)
            for l in jax.tree_util.tree_leaves(model.get_parameters())]


def _run_host(k, iters=12, end_when=None):
    RandomGenerator.set_seed(11)
    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion(),
                         batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(end_when or max_iteration(iters))
    opt.set_steps_per_sync(k)
    model = opt.optimize()
    return _params_of(model), opt


def _run_device(k, iters=10, n=64):
    RandomGenerator.set_seed(23)
    opt = LocalOptimizer(_img_model(), _device_ds(n=n),
                         nn.ClassNLLCriterion(), batch_size=16)
    opt.set_optim_method(SGD(learning_rate=0.05))
    opt.set_end_when(max_iteration(iters))
    opt.set_steps_per_sync(k)
    model = opt.optimize()
    return _params_of(model), opt


# ---------------------------------------------- K=1 vs K>1 equivalence

@pytest.mark.parametrize("k", [4, 8])
def test_host_feed_windowed_matches_per_step(k):
    p1, o1 = _run_host(1)
    pk, ok = _run_host(k)
    assert o1.driver_state["neval"] == ok.driver_state["neval"]
    assert o1.driver_state["epoch"] == ok.driver_state["epoch"]
    assert np.isclose(o1.driver_state["Loss"], ok.driver_state["Loss"],
                      rtol=1e-5, atol=1e-7)
    for a, b in zip(p1, pk):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k", [4, 8])
def test_device_feed_windowed_matches_per_step(k):
    p1, o1 = _run_device(1)
    pk, ok = _run_device(k)
    assert o1.driver_state["neval"] == ok.driver_state["neval"]
    assert o1.driver_state["epoch"] == ok.driver_state["epoch"]
    for a, b in zip(p1, pk):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_windowed_loss_sequence_matches_per_step():
    """Every per-step Loss the summary would see, not just the final
    one: the replay must hand triggers/summaries the true sequence."""
    seen = {}
    for k in (1, 8):
        RandomGenerator.set_seed(31)
        opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion(),
                             batch_size=32)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(9))
        opt.set_steps_per_sync(k)

        class Spy:
            def __init__(self):
                self.rows = []

            def add_scalar(self, tag, value, step):
                if tag == "Loss":
                    self.rows.append((step, value))

            def add_histogram(self, *a):
                pass

        spy = Spy()
        opt.set_train_summary(spy)
        opt.optimize()
        seen[k] = spy.rows
    assert len(seen[1]) == len(seen[8]) == 9
    for (s1, l1), (s8, l8) in zip(seen[1], seen[8]):
        assert s1 == s8
        assert np.isclose(l1, l8, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------- window planning

def _plan(opt, k, state, bsz, ds_size, end_when, shard=None):
    return opt._plan_window(k, state, bsz, ds_size, end_when,
                            shard_size=shard)


def test_window_flushes_at_validation_boundary():
    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion())
    opt.validation_trigger = several_iteration(3)
    st = {"epoch": 1, "neval": 1, "recordsProcessedThisEpoch": 0}
    # post-step-2 state has neval=3 -> trigger fires -> window is 2
    assert _plan(opt, 8, st, 8, 10**6, max_iteration(100)) == 2
    st["neval"] = 3
    assert _plan(opt, 8, st, 8, 10**6, max_iteration(100)) == 3


def test_window_flushes_at_checkpoint_and_end_boundaries():
    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion())
    opt.checkpoint_trigger = several_iteration(5)
    st = {"epoch": 1, "neval": 1, "recordsProcessedThisEpoch": 0}
    assert _plan(opt, 8, st, 8, 10**6, max_iteration(100)) == 4
    opt.checkpoint_trigger = None
    assert _plan(opt, 8, st, 8, 10**6, max_iteration(6)) == 6
    assert _plan(opt, 4, st, 8, 10**6, max_iteration(100)) == 4


def test_window_flushes_at_epoch_rollover_and_shard_boundary():
    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion())
    st = {"epoch": 1, "neval": 1, "recordsProcessedThisEpoch": 0}
    # 96-record epoch, batch 32: the 3rd step completes the epoch
    assert _plan(opt, 8, st, 32, 96, max_iteration(100)) == 3
    # shard of 64 records, batch 16: rotation due after step 4
    assert _plan(opt, 8, st, 16, 10**6, max_iteration(100), shard=64) == 4


def test_every_epoch_peek_does_not_mutate():
    t = every_epoch()
    assert not t({"epoch": 1})          # latches the baseline
    assert t.peek({"epoch": 2})         # preview: would fire
    assert t.peek({"epoch": 2})         # ... and again: no mutation
    assert t({"epoch": 2})              # the real call still fires once
    assert not t({"epoch": 2})


def test_trigger_dependency_metadata():
    assert several_iteration(5).depends_on == {"neval"}
    assert min_loss(0.1).depends_on == {"Loss"}
    assert not min_loss(0.1).plannable()
    assert several_iteration(5).plannable()
    both = several_iteration(5).or_(every_epoch())
    assert both.depends_on == {"neval", "epoch"}
    assert both.plannable()
    unknown = Trigger(lambda s: False)
    assert unknown.depends_on is None and not unknown.plannable()
    assert several_iteration(5).and_(unknown).depends_on is None


# ----------------------------------------------------- per-step fallback

def test_loss_dependent_end_trigger_forces_per_step():
    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion())
    k, why = opt._window_limit(8, min_loss(0.01), False)
    assert k == 1 and "Loss" in why


def test_unknown_trigger_forces_per_step():
    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion())
    opt.validation_trigger = Trigger(lambda s: s.get("neval", 1) % 7 == 0)
    k, why = opt._window_limit(8, max_iteration(10), False)
    assert k == 1 and "undeclared" in why


def test_parameter_histogram_summary_forces_per_step():
    class HistSummary:
        def add_scalar(self, *a):
            pass

        def add_histogram(self, *a):
            pass

        def get_summary_trigger(self, name):
            return several_iteration(5) if name == "Parameters" else None

    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion())
    opt.set_train_summary(HistSummary())
    k, why = opt._window_limit(8, max_iteration(10), False)
    assert k == 1 and "Parameters" in why


def test_plateau_schedule_forces_per_step():
    from bigdl_tpu.optim.optim_method import Plateau
    opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learning_rate=0.1,
                             learning_rate_schedule=Plateau()))
    k, why = opt._window_limit(8, max_iteration(10), False)
    assert k == 1 and "Plateau" in why


def test_fallback_run_still_trains():
    # a K=8 ask with a min_loss end trigger must run (per-step) and stop
    p, opt = _run_host(8, end_when=min_loss(0.05).or_(max_iteration(40)))
    assert opt.driver_state["neval"] > 1


# --------------------------------------- boundary-equivalence end-to-end

def test_validation_fires_at_identical_steps_and_scores():
    rows = {}
    for k in (1, 8):
        RandomGenerator.set_seed(17)
        opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion(),
                             batch_size=32)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(9))
        opt.set_validation(several_iteration(3), _host_ds(seed=1),
                           [Loss(nn.ClassNLLCriterion())])
        opt.set_steps_per_sync(k)
        calls = []
        orig = opt._validate

        def spy(params, mstate, ev, _o=orig, _c=calls, _opt=opt):
            _c.append(_opt.driver_state["neval"])
            return _o(params, mstate, ev)

        opt._validate = spy
        opt.optimize()
        rows[k] = (calls, opt.driver_state.get("score"))
    assert rows[1][0] == rows[8][0] == [3, 6, 9]
    assert np.isclose(rows[1][1], rows[8][1], rtol=1e-5)


def test_actual_batch_sizes_guard_trigger_boundaries():
    """Optimizer configured with batch_size=32 but the dataset yields
    64-row batches: plan simulation (configured size) under-counts
    records, so the gather must re-peek triggers with ACTUAL sizes — a
    records-dependent trigger still fires at the per-step loop's step."""
    rows = {}
    for k in (1, 8):
        RandomGenerator.set_seed(37)
        opt = LocalOptimizer(_mlp(), _host_ds(n=192, batch=64),
                             nn.ClassNLLCriterion(),
                             batch_size=32)  # mismatched on purpose
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(4))
        trig = Trigger(
            lambda s: s.get("recordsProcessedThisEpoch", 0) >= 64,
            depends_on=frozenset({"recordsProcessedThisEpoch"}))
        opt.set_validation(trig, _host_ds(seed=1),
                           [Loss(nn.ClassNLLCriterion())])
        opt.set_steps_per_sync(k)
        calls = []
        orig = opt._validate

        def spy(params, mstate, ev, _o=orig, _c=calls, _opt=opt):
            _c.append(_opt.driver_state["neval"])
            return _o(params, mstate, ev)

        opt._validate = spy
        opt.optimize()
        rows[k] = calls
    assert rows[1] == rows[8]
    assert rows[1]  # the trigger really fired


def test_checkpoints_written_at_identical_steps(tmp_path):
    import os
    dirs = {}
    for k in (1, 8):
        path = str(tmp_path / f"ck{k}")
        RandomGenerator.set_seed(19)
        opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion(),
                             batch_size=32)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(8))
        opt.set_checkpoint(path, several_iteration(4))
        opt.set_steps_per_sync(k)
        opt.optimize()
        dirs[k] = sorted(os.listdir(path))
    assert dirs[1] == dirs[8]
    assert dirs[1]  # something was actually written


def test_rotating_feed_windowed_matches_per_step():
    """Windows over a RotatingDeviceDataSet flush at shard boundaries
    (the slot arrays are window-invariant scan arguments), so K=8 runs
    in shard-sized windows and still matches the per-step run."""
    from bigdl_tpu.dataset import RotatingDeviceDataSet, ShardRotator

    m_per = 16  # shard size; batch 8 -> windows capped at 2 steps
    protos = np.random.RandomState(42).randn(4, 3, 8, 8)

    def provider(i):
        r = np.random.RandomState(50 + i)
        xs = np.clip(protos[i % 4] * 40 + 128
                     + r.randn(m_per, 3, 8, 8) * 10, 0, 255)
        return xs.astype(np.uint8), np.full(m_per, float(i % 4 + 1),
                                            np.float32)

    def run(k):
        RandomGenerator.set_seed(29)
        rot = ShardRotator(provider, 4, 8, crop=(8, 8), flip=False,
                           mean=(128,) * 3, std=(64,) * 3,
                           chunk_bytes=8 * 3 * 8 * 8,
                           shuffle_shards=False)
        ds = RotatingDeviceDataSet(rot)
        model = (nn.Sequential().add(nn.Reshape((3 * 8 * 8,)))
                 .add(nn.Linear(3 * 8 * 8, 4)).add(nn.LogSoftMax()))
        opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(),
                             batch_size=8)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(9))
        opt.set_steps_per_sync(k)
        trained = opt.optimize()
        return _params_of(trained), opt

    p1, o1 = run(1)
    p8, o8 = run(8)
    assert o1.dataset._consumed_shards == o8.dataset._consumed_shards
    for a, b in zip(p1, p8):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- compile counter

def _count_compiles(fn):
    from _compile_counter import count_compiles

    with count_compiles() as compiles:
        fn()
    return len(compiles)


def test_windowed_mode_compiles_one_program_per_k_shape():
    # warm every eager-op/helper cache with an identical run, then
    # count: steady K=4 traffic (8 steps = 2 full windows) is exactly
    # ONE compiled program; K=8 over 12 steps on a 16-step epoch
    # (windows of 8 then 4 at the end boundary) is exactly two — one
    # per (K, shape) pair
    _run_device(4, iters=8)
    assert _count_compiles(lambda: _run_device(4, iters=8)) == 1
    _run_device(8, iters=12, n=256)
    assert _count_compiles(lambda: _run_device(8, iters=12, n=256)) == 2


def test_windowed_phase_sums_match_metrics_to_the_digit():
    """K>1 records ONE data_wait/compute pair per window (amortized
    granularity) — but the trace's phase SUMS must still equal the
    Metrics sums exactly, so tools.diagnose's invariant holds."""
    import bigdl_tpu.telemetry as telemetry
    telemetry.enable()
    try:
        telemetry.tracer().clear()
        RandomGenerator.set_seed(41)
        opt = LocalOptimizer(_mlp(), _host_ds(), nn.ClassNLLCriterion(),
                             batch_size=32)
        opt.set_optim_method(SGD(learning_rate=0.1))
        opt.set_end_when(max_iteration(8))
        opt.set_steps_per_sync(8)
        opt.optimize()
        spans = {"optimizer/data_wait": 0.0, "optimizer/compute": 0.0}
        counts = {"optimizer/data_wait": 0, "optimizer/compute": 0}
        for rec in list(telemetry.tracer().spans()):
            if rec.name in spans:
                spans[rec.name] += rec.dur
                counts[rec.name] += 1
        assert counts["optimizer/compute"] >= 1
        # windows, not steps: 8 fused steps -> far fewer records than 8
        assert counts["optimizer/compute"] < 8
        assert np.isclose(spans["optimizer/data_wait"],
                          sum(opt.metrics.values["data time"]), atol=1e-12)
        assert np.isclose(spans["optimizer/compute"],
                          sum(opt.metrics.values["computing time"]),
                          atol=1e-12)
    finally:
        telemetry.disable()


# ------------------------------------------------------- stack_windows

def _mb(i, b=4, d=3):
    x = np.full((b, d), i, np.float32)
    y = np.full((b,), i, np.float32)
    return MiniBatch(x, y)


def test_stack_windows_groups_and_tails():
    out = list(stack_windows(iter([_mb(i) for i in range(7)]), 3))
    assert [b.input.shape for b in out] == [(3, 4, 3), (3, 4, 3),
                                            (1, 4, 3)]
    np.testing.assert_array_equal(out[0].input[1], _mb(1).input)
    np.testing.assert_array_equal(out[2].target[0], _mb(6).target)


def test_stack_windows_flushes_on_shape_change():
    batches = [_mb(0), _mb(1), _mb(2, b=2), _mb(3, b=2), _mb(4)]
    out = list(stack_windows(iter(batches), 4))
    assert [b.input.shape for b in out] == [(2, 4, 3), (2, 2, 3),
                                            (1, 4, 3)]


def test_stack_minibatches_rejects_mixed_none_targets_either_order():
    from bigdl_tpu.dataset import stack_minibatches
    with_t = _mb(0)
    without_t = MiniBatch(_mb(1).input, None)
    for pair in ([with_t, without_t], [without_t, with_t]):
        with pytest.raises(ValueError, match="mix None"):
            stack_minibatches(pair)


def test_device_resident_batches_fall_back_to_per_step():
    """A pipeline yielding device-resident MiniBatches must not be
    host-stacked (hidden device->host round-trip per batch): the
    window gather detects jax.Array leaves and runs per-step."""
    import jax.numpy as jnp
    from bigdl_tpu.optim.optimizer import _window_stackable
    host = _mb(0)
    dev = MiniBatch(jnp.asarray(host.input), jnp.asarray(host.target))
    assert _window_stackable(host)
    assert not _window_stackable(dev)


def test_stack_windows_multi_input_and_signature():
    a = MiniBatch([np.zeros((2, 3), np.float32),
                   np.zeros((2,), np.int32)], np.ones((2,), np.float32))
    b = MiniBatch([np.ones((2, 3), np.float32),
                   np.ones((2,), np.int32)], np.zeros((2,), np.float32))
    assert batch_signature(a) == batch_signature(b)
    (w,) = stack_windows(iter([a, b]), 2)
    assert isinstance(w.input, list)
    assert w.input[0].shape == (2, 2, 3) and w.input[1].shape == (2, 2)
    assert stack_windows(iter([]), 3) is not None  # generator, no blowup
    with pytest.raises(ValueError):
        list(stack_windows(iter([a]), 0))


# ------------------------------------------- prefetch abandoned-consumer

def _slow_batches(n=100):
    for i in range(n):
        yield _mb(i)


def test_device_prefetch_close_midstream_joins_stager():
    before = set(threading.enumerate())
    it = device_prefetch(_slow_batches(), size=2)
    next(it)  # consume one, leave the stager blocked on a full queue
    time.sleep(0.2)  # let the stager fill the queue and park on put()
    it.close()  # GeneratorExit -> stop event -> drain -> join
    deadline = time.time() + 5.0
    while time.time() < deadline:
        leaked = set(threading.enumerate()) - before
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"stager thread leaked: {leaked}"


def test_device_prefetch_normal_exhaustion_still_clean():
    before = set(threading.enumerate())
    out = list(device_prefetch(iter([_mb(i) for i in range(5)]), size=2))
    assert len(out) == 5
    time.sleep(0.1)
    assert set(threading.enumerate()) <= before


def test_device_prefetch_error_still_propagates():
    def boom():
        yield _mb(0)
        raise RuntimeError("upstream died")

    it = device_prefetch(boom(), size=2)
    next(it)
    with pytest.raises(RuntimeError, match="upstream died"):
        next(it)
