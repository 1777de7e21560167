"""PP/SP as first-class Optimizer product surface (the reference's
parallelism was reachable from Optimizer(...).optimize() —
optim/DistriOptimizer.scala:728; these tests hold the net-new pipeline
and sequence parallelism to the same bar)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.models import PipelinedTransformerLM, TransformerLM
from bigdl_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def devices8():
    d = jax.devices()
    if len(d) < 8:
        pytest.skip("needs 8 virtual devices")
    return d[:8]


def _token_dataset(n, seq, vocab, batch_size, seed=0):
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, vocab, (n, seq + 1))
    samples = [Sample(toks[i, :-1].astype(np.int32),
                      toks[i, 1:].astype(np.int32)) for i in range(n)]
    return DataSet.array(samples).transform(SampleToMiniBatch(batch_size))


def _loss_on_first_batch(model, n, seq, vocab, batch_size, seed=0):
    """Initial-params loss on the dataset's first batch — the oracle the
    trained loss must beat (same generator as _token_dataset)."""
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, vocab, (n, seq + 1))
    x = jnp.asarray(toks[:batch_size, :-1].astype(np.int32))
    y = jnp.asarray(toks[:batch_size, 1:].astype(np.int32))
    crit = nn.SequenceCrossEntropyCriterion()
    out, _ = model.apply(model.get_parameters(), model.get_state(), x)
    return float(crit.apply(out, y))


def test_pipelined_lm_dense_fallback_forward():
    lm = PipelinedTransformerLM(vocab_size=50, hidden_size=16,
                                num_layers=2, num_heads=2,
                                max_len=8).evaluate()
    logits = np.asarray(lm.forward(np.random.randint(0, 50, (2, 8))))
    assert logits.shape == (2, 8, 50)
    assert np.isfinite(logits).all()


def test_pipelined_lm_pp_matches_dense(devices8):
    """Pipelined forward AND grads must equal the sequential-scan path
    on identical params — PP changes the schedule, never the math."""
    mesh = make_mesh([4], ["pipe"], devices8[:4])
    lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                num_layers=4, num_heads=2, max_len=8,
                                n_microbatches=4, mesh=mesh).training()
    lm.ensure_initialized()
    params = lm.get_parameters()
    dense = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                   num_layers=4, num_heads=2, max_len=8,
                                   n_microbatches=4, mesh=None).training()
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 32, (8, 8)))
    tgts = jnp.asarray(np.random.RandomState(1).randint(0, 32, (8, 8)))
    crit = nn.SequenceCrossEntropyCriterion()

    def loss(model, p):
        out = model.forward_fn(p, toks)
        return crit.apply(out, tgts)

    lp, gp = jax.value_and_grad(lambda p: loss(lm, p))(params)
    ld, gd = jax.value_and_grad(lambda p: loss(dense, p))(params)
    assert abs(float(lp) - float(ld)) < 1e-5
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)


def test_optimizer_trains_dp_tp_pp_composed(devices8):
    """THE product bar: one Optimizer call trains a pipelined model on a
    (data x pipe x model) mesh with composed DP+TP+PP shardings."""
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import Optimizer

    mesh = make_mesh([2, 2, 2], ["data", "pipe", "model"], devices8)
    lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                num_layers=4, num_heads=2, max_len=8,
                                n_microbatches=2, mesh=mesh)
    ds = _token_dataset(32, 8, 32, batch_size=8)
    opt = Optimizer(lm, ds, nn.SequenceCrossEntropyCriterion(),
                    batch_size=8, mesh=mesh,
                    sharding_rules=lm.sharding_rules(model_axis="model"))
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_iteration(8))
    lm.ensure_initialized()
    init_loss = _loss_on_first_batch(lm, 32, 8, 32, batch_size=8)
    opt.optimize()
    final = opt.driver_state["Loss"]
    assert np.isfinite(final)
    # layout really is composed: block weights carry pipe AND model axes
    p = lm.get_parameters()
    assert p["blocks"]["wq"].shape == (4, 16, 16)
    assert final < init_loss - 0.3, \
        f"composed training did not move the loss: {init_loss} -> {final}"


def test_sp_ring_reaches_optimizer(devices8):
    """TransformerLM(ring_axis=...) trains through the plain Optimizer on
    a (data x seq) mesh — attention auto-wraps in shard_map over seq."""
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import Optimizer

    mesh = make_mesh([2, 4], ["data", "seq"], devices8)
    lm = TransformerLM(vocab_size=32, hidden_size=16, num_layers=2,
                       num_heads=4, max_len=16, ring_axis="seq",
                       mesh=mesh)
    ds = _token_dataset(16, 16, 32, batch_size=4)
    opt = Optimizer(lm, ds, nn.SequenceCrossEntropyCriterion(),
                    batch_size=4, mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_iteration(6))
    lm.ensure_initialized()
    init_loss = _loss_on_first_batch(lm, 16, 16, 32, batch_size=4)
    opt.optimize()
    final = opt.driver_state["Loss"]
    assert np.isfinite(final)
    assert final < init_loss - 0.3, \
        f"SP training did not move the loss: {init_loss} -> {final}"


def test_sp_ulysses_matches_local_forward(devices8):
    """sp_impl='ulysses': the auto-wrapped SP forward equals the local
    (single-device) forward on identical params."""
    mesh = make_mesh([4], ["seq"], devices8[:4])
    lm = TransformerLM(vocab_size=32, hidden_size=16, num_layers=2,
                       num_heads=4, max_len=16, ring_axis="seq",
                       sp_impl="ulysses", mesh=mesh).evaluate()
    lm.ensure_initialized()
    params = lm.get_parameters()
    local = TransformerLM(vocab_size=32, hidden_size=16, num_layers=2,
                          num_heads=4, max_len=16).evaluate()
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 32, (2, 16)))
    out_sp, _ = lm.apply(params, lm.get_state(), toks)
    out_lc, _ = local.apply(params, local.get_state(), toks)
    np.testing.assert_allclose(np.asarray(out_sp), np.asarray(out_lc),
                               atol=2e-5)


def test_sp_ring_matches_local_forward(devices8):
    mesh = make_mesh([4], ["seq"], devices8[:4])
    lm = TransformerLM(vocab_size=32, hidden_size=16, num_layers=2,
                       num_heads=4, max_len=16, ring_axis="seq",
                       sp_impl="ring", mesh=mesh).evaluate()
    lm.ensure_initialized()
    params = lm.get_parameters()
    local = TransformerLM(vocab_size=32, hidden_size=16, num_layers=2,
                          num_heads=4, max_len=16).evaluate()
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 32, (2, 16)))
    out_sp, _ = lm.apply(params, lm.get_state(), toks)
    out_lc, _ = local.apply(params, local.get_state(), toks)
    np.testing.assert_allclose(np.asarray(out_sp), np.asarray(out_lc),
                               atol=2e-5)


def test_mesh_bearing_model_snapshot_roundtrip(tmp_path, devices8):
    """A mesh is runtime placement, not model identity: snapshots of
    mesh-constructed models must save and load on any topology."""
    from bigdl_tpu.utils.serialization import load_module, save_module

    mesh = make_mesh([4], ["pipe"], devices8[:4])
    lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                num_layers=4, num_heads=2, max_len=8,
                                n_microbatches=2, mesh=mesh)
    lm.ensure_initialized()
    path = str(tmp_path / "pp_snap")
    save_module(path, lm)
    back = load_module(path)
    assert back.mesh is None  # reattach on the load topology
    toks = np.random.RandomState(0).randint(0, 32, (2, 8))
    a = np.asarray(back.evaluate().forward(toks))
    b = np.asarray(lm.evaluate().forward(toks))
    np.testing.assert_allclose(a, b, atol=2e-5)


def test_interleaved_schedule_matches_dense(devices8):
    """The interleaved (virtual-stage) schedule shrinks the pipeline
    bubble from (S-1)/(M+S-1) to (S-1)/(V*M+S-1); it must remain a pure
    re-scheduling — forward and grads equal the sequential scan."""
    mesh = make_mesh([4], ["pipe"], devices8[:4])
    lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                num_layers=8, num_heads=2, max_len=8,
                                n_microbatches=4, mesh=mesh,
                                pp_schedule="interleaved",
                                pp_rounds=2).training()
    lm.ensure_initialized()
    params = lm.get_parameters()
    dense = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                   num_layers=8, num_heads=2, max_len=8,
                                   n_microbatches=4, mesh=None).training()
    toks = jnp.asarray(np.random.RandomState(3).randint(0, 32, (8, 8)))
    tgts = jnp.asarray(np.random.RandomState(4).randint(0, 32, (8, 8)))
    crit = nn.SequenceCrossEntropyCriterion()

    def loss(model, p):
        return crit.apply(model.forward_fn(p, toks), tgts)

    lp, gp = jax.jit(jax.value_and_grad(
        lambda p: loss(lm, p)))(params)
    ld, gd = jax.value_and_grad(lambda p: loss(dense, p))(params)
    assert abs(float(lp) - float(ld)) < 1e-5
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)


def test_interleaved_trains_through_optimizer(devices8):
    """--ppSchedule interleaved is product surface: the stock Optimizer
    trains it on a (data x pipe) mesh."""
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import Optimizer

    mesh = make_mesh([2, 4], ["data", "pipe"], devices8)
    lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                num_layers=8, num_heads=2, max_len=8,
                                n_microbatches=4, mesh=mesh,
                                pp_schedule="interleaved", pp_rounds=2)
    ds = _token_dataset(32, 8, 32, batch_size=8)
    opt = Optimizer(lm, ds, nn.SequenceCrossEntropyCriterion(),
                    batch_size=8, mesh=mesh,
                    sharding_rules=lm.sharding_rules())
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_iteration(8))
    lm.ensure_initialized()
    init_loss = _loss_on_first_batch(lm, 32, 8, 32, batch_size=8)
    opt.optimize()
    assert opt.driver_state["Loss"] < init_loss - 0.3


def test_interleaved_needs_enough_microbatches(devices8):
    """M < S is schedule-infeasible (a round-v activation would need to
    re-enter stage 0 before it arrives) — fail fast, not silently."""
    mesh = make_mesh([4], ["pipe"], devices8[:4])
    lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                num_layers=8, num_heads=2, max_len=8,
                                n_microbatches=2, mesh=mesh,
                                pp_schedule="interleaved", pp_rounds=2)
    lm.ensure_initialized()
    with pytest.raises(AssertionError, match="microbatches"):
        jax.eval_shape(
            lambda p: lm.forward_fn(p, jnp.zeros((8, 8), jnp.int32)),
            lm.get_parameters())


def _grads_vs_dense(mesh, model_kw, rules_kw, devices8, atol=2e-4):
    """Shared harness: PipelinedTransformerLM grads on a composed mesh
    must equal its own dense-scan twin on identical params/batch."""
    from bigdl_tpu.parallel import shard_params
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(3)
    lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                num_layers=4, num_heads=2, max_len=16,
                                n_microbatches=2, mesh=mesh, **model_kw)
    lm.ensure_initialized()
    host_p = jax.tree.map(np.asarray, lm.get_parameters())
    p = shard_params(lm.get_parameters(), mesh,
                     lm.sharding_rules(**rules_kw))
    dense = PipelinedTransformerLM(
        vocab_size=32, hidden_size=16, num_layers=4, num_heads=2,
        max_len=16, n_microbatches=2, mesh=None,
        **{k: v for k, v in model_kw.items() if k != "ring_axis"})
    crit = nn.SequenceCrossEntropyCriterion()
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 32, (8, 16)).astype(np.int32)
    tgts = rs.randint(0, 32, (8, 16)).astype(np.int32)

    def loss(model, pp):
        out, st = model.apply(pp, model.initial_state(), toks)
        base = crit.apply(out, tgts)
        if model.moe_experts:
            base = base + 0.01 * model.aux_loss(st)
        return base

    gp = jax.jit(jax.grad(lambda pp: loss(lm, pp)))(p)
    gd = jax.grad(lambda pp: loss(dense, pp))(host_p)
    for a, b in zip(jax.tree.leaves(jax.tree.map(np.asarray, gp)),
                    jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=atol)


def test_pp_composes_with_ring_sp(devices8):
    """SP inside the pipeline: ring attention runs its manual
    collectives within each stage (seq axis manual alongside pipe) —
    the SP∦PP gap closed."""
    mesh = make_mesh([2, 2, 2], ["data", "pipe", "seq"], devices8)
    _grads_vs_dense(mesh, {"ring_axis": "seq"}, {}, devices8)


def test_pp_composes_with_ulysses_sp(devices8):
    mesh = make_mesh([2, 2, 2], ["data", "pipe", "seq"], devices8)
    _grads_vs_dense(mesh, {"ring_axis": "seq", "sp_impl": "ulysses"},
                    {}, devices8)


def test_pp_composes_with_moe_ep(devices8):
    """MoE inside the pipeline: stacked routed experts GSPMD-sharded
    over the model axis, the load-balance aux threaded through the
    pipeline ring — bit-comparable to the dense microbatch-looped
    fallback."""
    mesh = make_mesh([2, 2, 2], ["data", "pipe", "model"], devices8)
    _grads_vs_dense(mesh, {"moe_experts": 2},
                    {"model_axis": "model", "expert_axis": "model"},
                    devices8)


def test_full_product_pp_sp_ep(devices8):
    """DP x PP x SP x EP constructible in ONE model on one mesh."""
    mesh = make_mesh([2, 2, 2], ["data", "pipe", "seq"], devices8)
    _grads_vs_dense(mesh, {"ring_axis": "seq", "moe_experts": 2},
                    {"expert_axis": "seq"}, devices8)


def test_interleaved_composes_with_moe_ep(devices8):
    """The interleaved schedule's aux threading (valid-mask + psum/m
    over V rounds) must ALSO equal the dense microbatch-looped aux —
    the two-process composed test's oracle runs the same interleaved
    code, so only this dense cross-check can catch aux-math bugs."""
    mesh = make_mesh([2, 2, 2], ["data", "pipe", "model"], devices8)
    _grads_vs_dense(mesh, {"moe_experts": 2,
                           "pp_schedule": "interleaved", "pp_rounds": 2},
                    {"model_axis": "model", "expert_axis": "model"},
                    devices8)
