"""Shape/dtype checker: golden layer-path diagnostics, the zero-compile
guarantee, and the Optimizer / ModelRegistry pre-flight wiring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.analysis import ShapeCheckError, check_module, spec


# ------------------------------------------------- golden-message tests

def test_miswired_sequential_names_exact_layer_path():
    m = (nn.Sequential()
         .add(nn.Linear(16, 32))
         .add(nn.ReLU())
         .add(nn.Linear(64, 10).set_name("head")))
    with pytest.raises(ShapeCheckError) as ei:
        m.check(spec(("b", 16)))
    msg = str(ei.value)
    # the exact offending layer path, not the container or a sibling
    assert "`sequential[2]/head`" in msg
    assert "Linear" in msg
    assert "(32,) and (64,)" in msg  # the underlying dot_general mismatch


def test_ragged_concat_names_branch_and_inner_layer():
    m = nn.Concat(
        2,
        nn.Linear(8, 4),
        nn.Sequential().add(nn.Linear(8, 6)).add(nn.Linear(5, 6)))
    report = check_module(m, spec(("b", 8)))
    assert not report.ok
    [d] = report.errors
    assert d.path == "concat[1]/sequential[1]/linear"
    assert d.layer == "Linear"


def test_dtype_mismatch_float_params_int_input():
    m = nn.Sequential().add(nn.Linear(8, 4).set_name("proj"))
    report = check_module(m, spec(("b", 8), jnp.int32))
    assert not report.ok
    [d] = report.errors
    assert d.path == "sequential[0]/proj"
    assert "dtype mismatch" in d.message
    assert "integer input" in d.message


def test_embedding_accepts_integer_input():
    m = nn.Sequential().add(nn.LookupTable(100, 16)).add(nn.Linear(16, 4))
    report = check_module(m, spec(("b", 7), jnp.int32))
    assert report.ok and report.symbolic


def test_miswired_graph_names_node():
    from bigdl_tpu.nn.graph import Graph, Input
    inp = Input()()
    h = nn.Linear(10, 4).set_name("enc")(inp)
    out = nn.Linear(8, 2).set_name("dec")(h)  # expects 8, gets 4
    g = Graph(inp, out)
    report = check_module(g, spec(("b", 10)))
    assert not report.ok
    [d] = report.errors
    assert d.path == "graph/dec"


def test_good_model_reports_symbolic_output_shape():
    m = nn.Sequential().add(nn.Linear(16, 32)).add(nn.Linear(32, 10))
    report = m.check(spec(("b", 16)))
    assert report.ok and report.symbolic
    assert tuple(str(d) for d in report.output.shape)[-1] == "10"
    assert "b" in str(report.output.shape[0])


def test_multi_input_spec_table():
    m = nn.ParallelTable(nn.Linear(4, 2), nn.Linear(6, 2))
    report = check_module(
        m, [spec(("b", 4)), spec(("b", 6))])
    assert report.ok
    bad = check_module(m, [spec(("b", 4)), spec(("b", 5))])
    assert not bad.ok
    assert bad.errors[0].path == "paralleltable[1]/linear"


def test_two_tuple_of_specs_is_multi_input_not_one_spec():
    """A TUPLE of exactly two spec() results must parse as two inputs
    (regression: the (shape, dtype) pair branch used to swallow it)."""
    m = nn.ParallelTable(nn.Linear(4, 2), nn.Linear(6, 2))
    report = check_module(m, (spec(("b", 4)), spec(("b", 6))))
    assert report.ok
    # and an explicit dtype class (not np.dtype instance) still works
    report = check_module(
        nn.Sequential().add(nn.Linear(4, 2)), (("b", 4), jnp.float32))
    assert report.ok


# --------------------------------------------------- zero-compile guard

def test_check_triggers_no_xla_compilation():
    """Module.check rejects a mis-wired model (and accepts ResNet-50)
    without compiling anything — asserted via the compile counter,
    which is then shown to see a compile that does happen."""
    import jax
    from _compile_counter import count_compiles

    good = nn.Sequential().add(nn.Linear(16, 32)).add(nn.Linear(32, 10))
    bad = nn.Sequential().add(nn.Linear(16, 32)).add(nn.Linear(7, 10))
    from bigdl_tpu.models import ResNet
    rn = ResNet(100, depth=20, dataset="CIFAR10")

    with count_compiles() as calls:
        assert good.check(spec(("b", 16))).ok
        assert not check_module(bad, spec(("b", 16))).ok
        assert rn.check(spec(("b", 3, 32, 32)), training=True).ok
    assert calls == [], f"check compiled {len(calls)} XLA programs"
    # the guard guards: the same counter around a forced compile trips
    with count_compiles() as forced:
        jax.jit(lambda x: x * 3 + 1)(np.ones(5, np.float32))
    assert len(forced) >= 1


def test_check_leaves_module_usable():
    """The apply-interception is fully undone: eager forward still works
    and params adopt as usual after a failed check."""
    m = nn.Sequential().add(nn.Linear(4, 3)).add(nn.Linear(9, 2))
    with pytest.raises(ShapeCheckError):
        m.check(spec(("b", 4)))
    assert "apply" not in m.__dict__
    assert all("apply" not in c.__dict__ for c in m.modules)
    ok = nn.Sequential().add(nn.Linear(4, 3))
    ok.check(spec(("b", 4)))
    out = ok.forward(np.ones((2, 4), np.float32))
    assert out.shape == (2, 3)


# ------------------------------------------------------ pre-flight hooks

def test_optimizer_preflight_rejects_before_training():
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    bad = (nn.Sequential().add(nn.Reshape((16,)))
           .add(nn.Linear(16, 8)).add(nn.Linear(4, 2).set_name("clf")))
    samples = [Sample(np.ones((4, 4), np.float32), np.float32(1.0))
               for _ in range(8)]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(4))
    opt = LocalOptimizer(bad, ds, nn.CrossEntropyCriterion(), batch_size=4)
    opt.set_preflight_spec(spec(("b", 4, 4)))
    with pytest.raises(ShapeCheckError) as ei:
        opt.optimize()
    assert "`sequential[2]/clf`" in str(ei.value)
    # without the spec the check is opt-in: config error surfaces later
    assert bad._params is None  # preflight failed before any init


def test_registry_preflight_rejects_and_stages_nothing():
    from bigdl_tpu.serving import ModelRegistry

    reg = ModelRegistry()
    bad = nn.Sequential().add(nn.Linear(8, 4)).add(nn.Linear(5, 2))
    with pytest.raises(ShapeCheckError):
        reg.load("clf", bad, input_spec=spec(("b", 8)))
    assert reg.names() == []  # nothing staged, nothing resolvable

    good = nn.Sequential().add(nn.Linear(8, 4)).add(nn.Linear(4, 2))
    s = reg.load("clf", good, input_spec=spec(("b", 8)))
    assert reg.current("clf") is s


def test_registry_preflight_checks_live_module_via_detached_clone():
    """A user-passed live module is checked through a topology clone —
    the interception never shadows `apply` on the caller's instances."""
    from unittest.mock import patch

    from bigdl_tpu.analysis import shapecheck
    from bigdl_tpu.serving import ModelRegistry

    good = nn.Sequential().add(nn.Linear(8, 4)).add(nn.Linear(4, 2))
    touched = []
    orig = shapecheck._Interceptor.__init__

    def spying(self, root):
        touched.append(root)
        orig(self, root)

    with patch.object(shapecheck._Interceptor, "__init__", spying):
        ModelRegistry().load("clf", good, input_spec=spec(("b", 8)))
    assert touched and all(t is not good for t in touched)
    # ... while a registry-private quantized rewrite is checked directly
    q_reg = ModelRegistry()
    touched.clear()
    with patch.object(shapecheck._Interceptor, "__init__", spying):
        q_reg.load("q", good, input_spec=spec(("b", 8)), quantize=True)
    assert touched and all(t is not good for t in touched)


def test_bare_shape_tuple_and_struct_specs():
    m = nn.Sequential().add(nn.Linear(8, 2))
    assert check_module(m, (4, 8)).ok  # bare concrete shape, float32
    assert check_module(
        m, jax.ShapeDtypeStruct((4, 8), jnp.float32)).ok
