"""End-to-end training tests (reference: optim/DistriOptimizerSpec,
LocalOptimizerSpec — convergence on toy problems, SURVEY.md §4.3)."""
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.optim import (Adam, DistriOptimizer, Evaluator, LocalOptimizer,
                             SGD, Top1Accuracy, max_epoch, max_iteration)
from bigdl_tpu.utils.engine import Engine


def _toy_classification(n=256, d=8, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, d).astype(np.float32) * 3
    X, y = [], []
    for i in range(n):
        c = i % classes
        X.append(centers[c] + rng.randn(d).astype(np.float32) * 0.5)
        y.append(c + 1)  # 1-based labels
    return np.stack(X), np.array(y, np.float32)


def test_local_optimizer_converges_mlp():
    X, y = _toy_classification()
    samples = [Sample(X[i], y[i]) for i in range(len(X))]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(32))

    model = nn.Sequential() \
        .add(nn.Linear(8, 16)) \
        .add(nn.Tanh()) \
        .add(nn.Linear(16, 3)) \
        .add(nn.LogSoftMax())
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_epoch(15))
    trained = opt.optimize()

    res = Evaluator(trained).test(
        DataSet.array([Sample(X[i], y[i]) for i in range(len(X))]),
        [Top1Accuracy()], batch_size=64)
    acc, _ = res["Top1Accuracy"].result()
    assert acc > 0.95, f"accuracy {acc}"


def test_distri_optimizer_8dev_mesh_converges():
    import jax
    Engine.reset()
    Engine.init()  # 8 virtual CPU devices from conftest
    assert Engine.device_count() == 8
    X, y = _toy_classification(n=512)
    samples = [Sample(X[i], y[i]) for i in range(len(X))]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(64))

    model = nn.Sequential() \
        .add(nn.Linear(8, 16)) \
        .add(nn.ReLU()) \
        .add(nn.Linear(16, 3)) \
        .add(nn.LogSoftMax())
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=64)
    opt.set_optim_method(Adam(learning_rate=0.05))
    opt.set_end_when(max_iteration(120))
    trained = opt.optimize()

    res = Evaluator(trained).test(DataSet.array(samples), [Top1Accuracy()],
                                  batch_size=64)
    acc, _ = res["Top1Accuracy"].result()
    assert acc > 0.9, f"accuracy {acc}"


def test_lenet_trains_and_checkpoint_resume(tmp_path):
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.optim import several_iteration
    rng = np.random.RandomState(1)
    # synthetic 28x28 "digits": class = which quadrant is bright
    X = rng.rand(128, 28, 28).astype(np.float32) * 0.1
    y = np.zeros(128, np.float32)
    for i in range(128):
        c = i % 4
        r, col = divmod(c, 2)
        X[i, r * 14:(r + 1) * 14, col * 14:(col + 1) * 14] += 0.9
        y[i] = c + 1
    samples = [Sample(X[i], y[i]) for i in range(128)]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(32))

    model = LeNet5(10)
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
    opt.set_end_when(max_iteration(40))
    opt.set_checkpoint(str(tmp_path / "ckpt"), several_iteration(20))
    trained = opt.optimize()

    res = Evaluator(trained).test(DataSet.array(samples), [Top1Accuracy()],
                                  batch_size=64)
    acc, _ = res["Top1Accuracy"].result()
    assert acc > 0.9, f"accuracy {acc}"

    # checkpoint exists and can be loaded
    from bigdl_tpu.utils.serialization import (find_latest_checkpoint,
                                               load_checkpoint)
    latest = find_latest_checkpoint(str(tmp_path / "ckpt"))
    assert latest is not None
    ck = load_checkpoint(latest)
    assert "params" in ck and "driver_state" in ck


def test_validation_and_triggers():
    X, y = _toy_classification(n=128)
    samples = [Sample(X[i], y[i]) for i in range(len(X))]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(32))
    val = DataSet.array(samples)

    from bigdl_tpu.optim import every_epoch
    model = nn.Sequential().add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_epoch(3))
    opt.set_validation(every_epoch(), val, [Top1Accuracy()])
    opt.optimize()
    assert "score" in opt.driver_state


def test_validation_score_uses_first_method():
    """driver_state['score'] must be the FIRST validation method's result
    (DistriOptimizer.scala:382-397 uses head) — not a max() across
    heterogeneous methods, which with Loss in the set would exceed any
    accuracy and corrupt maxScore/Plateau decisions."""
    from bigdl_tpu.optim import Loss, every_epoch

    X, y = _toy_classification(n=128)
    samples = [Sample(X[i], y[i]) for i in range(len(X))]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(32))
    val = DataSet.array(samples)

    model = nn.Sequential().add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_epoch(1))
    # First method is Top1 (<=1.0); Loss of an untrained 3-class model is
    # ~ln(3) > 1, so max() across both would pick the Loss value.
    opt.set_validation(every_epoch(), val,
                       [Top1Accuracy(), Loss(nn.ClassNLLCriterion())])
    opt.optimize()
    assert opt.driver_state["score"] <= 1.0


def test_failure_retry_from_checkpoint(tmp_path):
    """Fault injection (reference ExceptionTest / DistriOptimizerSpec:461):
    a layer that throws at a scripted iteration; training must resume from
    checkpoint and complete."""
    X, y = _toy_classification(n=64)
    samples = [Sample(X[i], y[i]) for i in range(len(X))]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(32))

    calls = {"n": 0, "thrown": False}

    class ExceptionLayer(nn.Module):
        def forward_fn(self, params, input, *, training=False, rng=None):
            return input

        def init(self, rng):
            return {}

    model = nn.Sequential().add(ExceptionLayer()) \
        .add(nn.Linear(8, 3)).add(nn.LogSoftMax())

    from bigdl_tpu.optim import several_iteration
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(30))
    opt.set_checkpoint(str(tmp_path / "ck"), several_iteration(5))
    opt.retry_interval_s = 0.0

    real_impl = opt._optimize_impl

    def flaky_impl():
        calls["n"] += 1
        if not calls["thrown"] and opt.driver_state["neval"] > 1:
            pass
        return real_impl()

    # inject: throw once at iteration 12 via a wrapped step
    orig_put = opt._prep_io

    def flaky_prep(batch):
        if opt.driver_state["neval"] == 12 and not calls["thrown"]:
            calls["thrown"] = True
            raise RuntimeError("injected failure at iteration 12")
        return orig_put(batch)

    opt._prep_io = flaky_prep
    trained = opt.optimize()
    assert calls["thrown"], "failure was not injected"
    assert opt.driver_state["neval"] > 30


def test_device_oom_fails_at_once_even_with_a_checkpoint(tmp_path):
    """With a checkpoint configured a RuntimeError is retried — but not
    the ones XLA raises for a refused compile or a device out of
    memory: those replay identically, so the first diagnostic is
    raised at once, with no back-off."""
    import jax

    X, y = _toy_classification(n=64)
    ds = DataSet.array([Sample(X[i], y[i]) for i in range(len(X))]) \
        .transform(SampleToMiniBatch(32))
    model = nn.Sequential().add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    from bigdl_tpu.optim import several_iteration
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_end_when(max_iteration(4))
    opt.set_checkpoint(str(tmp_path / "ck"), several_iteration(2))
    opt.retry_interval_s = 60.0      # a retry would hang the test
    attempts = []

    def oom():
        attempts.append(1)
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")

    opt._optimize_impl = oom
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="RESOURCE_EXHAUSTED"):
        opt.optimize()
    assert attempts == [1]


def test_convergence_dataset_is_a_learnable_split():
    """tools/convergence's prototype task: the class prototypes are the
    TASK and must be identical across splits (a train/val mismatch here
    silently turns a converging run into chance-level — the bug
    class this guards). The full run is for the chip; it is far too
    slow for CI."""
    from bigdl_tpu.tools.convergence import make_dataset

    xs_a, ys_a = make_dataset(600, seed=0)
    xs_b, ys_b = make_dataset(600, seed=1)
    assert xs_a.shape == (600, 3, 32, 32) and xs_a.dtype == np.uint8
    assert set(np.unique(ys_a)).issubset(set(np.arange(1, 11.0)))
    # different seeds draw different samples...
    assert not np.array_equal(xs_a, xs_b)
    # ...of the SAME task: per-class pixel means across splits correlate
    # (the +-3px translation of white-noise prototypes smears alignment,
    # so r lands ~0.4; DISTINCT prototype sets give r ~ 0 +- 0.02, which
    # is exactly the train/val-mismatch bug this guards against)
    for c in (1.0, 2.0):
        ma = xs_a[ys_a == c].mean(0).astype(np.float32).ravel()
        mb = xs_b[ys_b == c].mean(0).astype(np.float32).ravel()
        r = np.corrcoef(ma, mb)[0, 1]
        assert r > 0.2, f"class {c} prototypes differ across splits: r={r}"
    # same seed reproduces exactly (checkpoint/resume replays the data)
    xs_c, ys_c = make_dataset(600, seed=0)
    np.testing.assert_array_equal(xs_a, xs_c)
    np.testing.assert_array_equal(ys_a, ys_c)


def test_freeze_and_layerwise_scale_through_training():
    """setScaleW/setScaleB/freeze flow through the compiled step
    (DistriOptimizer.scala:768 isLayerwiseScaled): a frozen layer's
    params are bit-identical after training; a 0.5-scaled weight moves
    exactly half as far as an unscaled clone on the same batch."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import LocalOptimizer, SGD, max_iteration
    from bigdl_tpu.utils.random import RandomGenerator

    rng = np.random.RandomState(0)
    xs = rng.randn(32, 6).astype(np.float32)
    ys = (rng.randint(0, 2, 32) + 1).astype(np.float32)
    ds = DataSet.array([Sample(xs[i], ys[i]) for i in range(32)]) \
        .transform(SampleToMiniBatch(32))

    def build():
        RandomGenerator.set_seed(5)
        return (nn.Sequential()
                .add(nn.Linear(6, 8).set_name("frozen").freeze())
                .add(nn.Tanh())
                .add(nn.Linear(8, 2).set_name("head"))
                .add(nn.LogSoftMax()))

    m = build()
    m.ensure_initialized()
    before = np.asarray(m.get_parameters()["0"]["weight"]).copy()
    head_before = np.asarray(m.get_parameters()["2"]["weight"]).copy()
    opt = LocalOptimizer(m, ds, nn.ClassNLLCriterion(), batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(1))
    opt.optimize()
    after = np.asarray(m.get_parameters()["0"]["weight"])
    head_after = np.asarray(m.get_parameters()["2"]["weight"])
    np.testing.assert_array_equal(before, after)     # frozen: untouched
    assert np.abs(head_after - head_before).max() > 0  # head trained

    # scale 0.5 halves the update exactly (same data, same init)
    m_full = build()
    opt = LocalOptimizer(m_full, ds, nn.ClassNLLCriterion(),
                         batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(1))
    opt.optimize()
    delta_full = np.asarray(m_full.get_parameters()["2"]["weight"]) \
        - head_before

    m_half = build()
    m_half.modules[2].set_scale_w(0.5).set_scale_b(0.5)
    opt = LocalOptimizer(m_half, ds, nn.ClassNLLCriterion(),
                         batch_size=32)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(1))
    opt.optimize()
    delta_half = np.asarray(m_half.get_parameters()["2"]["weight"]) \
        - head_before
    np.testing.assert_allclose(delta_half, 0.5 * delta_full,
                               atol=1e-6)
