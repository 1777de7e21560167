"""Parallelism tests on the virtual 8-device CPU mesh (SURVEY.md §4 —
multi-node simulated in one process, like the reference's multi-partition
single-JVM DistriOptimizerSpec)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.attention import dot_product_attention
from bigdl_tpu.parallel import (make_mesh, ring_attention_sharded,
                                shard_params, spec_for, validate_rules)


@pytest.fixture(scope="module")
def devices8():
    d = jax.devices()
    if len(d) < 8:
        pytest.skip("needs 8 virtual devices")
    return d[:8]


def test_ring_attention_matches_full(devices8):
    rng = np.random.RandomState(0)
    B, H, S, D = 2, 4, 64, 16
    q, k, v = [jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3)]
    mesh = Mesh(np.array(devices8), ("seq",))
    for causal in (False, True):
        ref = dot_product_attention(q, k, v, causal=causal)
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_ring_attention_grad_matches(devices8):
    rng = np.random.RandomState(1)
    B, H, S, D = 1, 2, 32, 8
    q, k, v = [jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3)]
    mesh = Mesh(np.array(devices8), ("seq",))
    g_ring = jax.grad(lambda q: ring_attention_sharded(
        q, k, v, mesh, causal=True).sum())(q)
    g_full = jax.grad(lambda q: dot_product_attention(
        q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               atol=2e-5)


def test_transformer_lm_forward():
    from bigdl_tpu.models import TransformerLM
    model = TransformerLM(vocab_size=100, hidden_size=32, num_layers=2,
                          num_heads=4, max_len=64).evaluate()
    tokens = np.random.randint(0, 100, (2, 16))
    logits = np.asarray(model.forward(tokens))
    assert logits.shape == (2, 16, 100)
    assert np.isfinite(logits).all()


def test_transformer_moe_aux_loss():
    from bigdl_tpu.models import TransformerLM
    model = TransformerLM(vocab_size=50, hidden_size=32, num_layers=2,
                          num_heads=4, max_len=32, moe_experts=4,
                          moe_every=2).training()
    tokens = np.random.randint(0, 50, (2, 8))
    model.forward(tokens)
    aux = float(model.aux_loss(model.get_state()))
    # balanced routing gives aux ~= 1.0 (E * sum f_e * P_e with f=P=1/E)
    assert 0.5 < aux < 4.0


def test_moe_routes_topk():
    m = nn.MoE(16, 32, num_experts=4, top_k=2)
    x = np.random.randn(2, 6, 16).astype(np.float32)
    out = np.asarray(m.forward(x))
    assert out.shape == (2, 6, 16)
    assert np.isfinite(out).all()


def test_sharding_rules_engine(devices8):
    from bigdl_tpu.models import TransformerLM
    mesh = make_mesh([2, 4], ["data", "model"], devices8)
    model = TransformerLM(vocab_size=64, hidden_size=32, num_layers=2,
                          num_heads=4, max_len=32)
    model.ensure_initialized()
    params = model.get_parameters()
    rules = model.sharding_rules()
    assert validate_rules(params, mesh, rules) == []
    sharded = shard_params(params, mesh, rules)
    wq = sharded["block_0"]["attn"]["wq"]
    assert wq.sharding.spec == P(None, "model")
    emb = sharded["embed"]
    assert emb.sharding.spec == P("model", None)
    ln = sharded["block_0"]["ln1"]["weight"]
    assert ln.sharding.spec == P()


def test_spec_rank_matching():
    rules = [("w_up", P("model", None, None)), ("w_up", P(None, "model"))]
    assert spec_for("block_0/mlp/w_up", 3, rules) == P("model", None, None)
    assert spec_for("block_0/mlp/w_up", 2, rules) == P(None, "model")
    assert spec_for("unmatched", 2, rules) == P()


def test_dp_tp_train_step(devices8):
    """Full train step: dp×tp mesh, sharded params, loss decreases."""
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step

    mesh = make_mesh([2, 4], ["data", "model"], devices8)
    model = TransformerLM(vocab_size=64, hidden_size=32, num_layers=2,
                          num_heads=4, max_len=16).training()
    model.ensure_initialized()

    optim = SGD(learning_rate=0.1)
    params = shard_params(model.get_parameters(), mesh,
                          model.sharding_rules())
    opt_state = optim.init_state(params)
    mstate = jax.device_put(model.get_state(), NamedSharding(mesh, P()))
    bsh = NamedSharding(mesh, P("data"))
    tokens = jax.device_put(
        jnp.asarray(np.random.randint(0, 64, (8, 16))), bsh)
    targets = jax.device_put(
        jnp.asarray(np.random.randint(0, 64, (8, 16))), bsh)
    step = build_train_step(model, nn.SequenceCrossEntropyCriterion(),
                            optim)
    rng = jax.random.PRNGKey(0)
    losses = []
    for i in range(8):
        params, opt_state, mstate, loss = step(
            params, opt_state, mstate, rng, 0.1, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # param layout survived the step (XLA kept the TP sharding)
    assert params["block_0"]["attn"]["wq"].sharding.spec == P(None, "model")


def test_sp_ring_train_step(devices8):
    """Sequence-parallel training: mesh (data=2, seq=4), ring attention
    inside shard_map, gradients match the unsharded reference."""
    from bigdl_tpu.models import TransformerLM

    mesh = make_mesh([2, 4], ["data", "seq"], devices8)
    model = TransformerLM(vocab_size=32, hidden_size=16, num_layers=1,
                          num_heads=2, max_len=32,
                          ring_axis="seq").evaluate()
    model.ensure_initialized()
    params = model.get_parameters()
    mstate = model.get_state()
    tokens = np.random.randint(0, 32, (4, 32))

    ref_model = TransformerLM(vocab_size=32, hidden_size=16, num_layers=1,
                              num_heads=2, max_len=32).evaluate()
    ref_model.set_parameters(params).set_state(mstate)
    ref = np.asarray(ref_model.forward(tokens))

    def fwd(p, tok_shard, pos0):
        # inside shard_map: positions are global; slice pos_embed by shard
        x = p["embed"][tok_shard.astype(jnp.int32)]
        s = tok_shard.shape[1]
        pos = jax.lax.dynamic_slice_in_dim(p["pos_embed"], pos0, s)
        x = x + pos[None]
        blk = model.blocks[0]
        x, _ = blk.apply(p["block_0"], {}, x)
        x = model.ln_f.forward_fn(p["ln_f"], x)
        return x @ p["embed"].T

    def sharded_fwd(p, tokens):
        def inner(p, tok):
            pos0 = jax.lax.axis_index("seq") * tok.shape[1]
            return fwd(p, tok, pos0)
        return jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P("data", "seq")),
            out_specs=P("data", "seq", None),
            check_vma=False))(p, tokens)

    out = np.asarray(sharded_fwd(params, jnp.asarray(tokens)))
    np.testing.assert_allclose(out, ref, atol=3e-4)


def test_moe_topk_clamped_to_experts():
    m = nn.MoE(8, 16, num_experts=1, top_k=2)
    out = np.asarray(m.forward(np.random.randn(1, 4, 8).astype(np.float32)))
    assert out.shape == (1, 4, 8) and np.isfinite(out).all()


def test_moe_every_one_places_moe_in_all_layers():
    from bigdl_tpu.models import TransformerLM
    lm = TransformerLM(vocab_size=16, hidden_size=16, num_layers=2,
                       num_heads=2, max_len=8, moe_experts=2, moe_every=1)
    assert all(b.moe_experts == 2 for b in lm.blocks)


def test_pos_embed_rule_not_shadowed():
    from bigdl_tpu.models import TransformerLM
    lm = TransformerLM(vocab_size=16, hidden_size=16, num_layers=1,
                       num_heads=2, max_len=10)
    rules = lm.sharding_rules()
    assert spec_for("pos_embed", 2, rules) == P()
    assert spec_for("embed", 2, rules) == P("model", None)
    assert spec_for("momentum/embed", 2, rules) == P("model", None)


def test_untied_lm_head_uncorrelated_init():
    from bigdl_tpu.models import TransformerLM
    lm = TransformerLM(vocab_size=32, hidden_size=32, num_layers=1,
                       num_heads=2, max_len=8, tie_embeddings=False)
    p = lm.get_parameters()
    corr = np.corrcoef(np.asarray(p["embed"]).ravel(),
                       np.asarray(p["lm_head"]).T.ravel())[0, 1]
    assert abs(corr) < 0.1


def test_ring_axis_rejects_dropout():
    with pytest.raises(ValueError):
        nn.MultiHeadAttention(32, 4, dropout=0.1, ring_axis="seq")


def test_sequence_cross_entropy_criterion():
    logits = np.random.randn(2, 5, 7).astype(np.float32)
    targets = np.random.randint(0, 7, (2, 5))
    c = nn.SequenceCrossEntropyCriterion()
    loss = float(c.forward(logits, targets))
    # manual reference
    from scipy.special import log_softmax
    lp = log_softmax(logits, axis=-1)
    ref = -np.mean([lp[b, s, targets[b, s]] for b in range(2)
                    for s in range(5)])
    assert abs(loss - ref) < 1e-5


def test_zero1_helper_shards_dim0(devices8):
    from bigdl_tpu.parallel import shard_opt_state_zero1
    mesh = make_mesh([8], ["data"], devices8)
    tree = {"momentum": {"w": jnp.zeros((16, 4)), "b": jnp.zeros((3,))}}
    out = shard_opt_state_zero1(tree, mesh, "data")
    assert out["momentum"]["w"].sharding.spec == P("data", None)
    assert out["momentum"]["b"].sharding.spec == P()  # 3 not divisible by 8


def test_moe_aux_loss_produces_router_gradients():
    """Review regression: the load-balance loss must reach the router
    through build_train_step's objective."""
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step

    model = TransformerLM(vocab_size=32, hidden_size=16, num_layers=2,
                          num_heads=2, max_len=8, moe_experts=4,
                          moe_every=2).training()
    model.ensure_initialized()
    optim = SGD(learning_rate=0.0)  # lr 0: isolate gradient check
    params = model.get_parameters()
    opt_state = optim.init_state(params)
    mstate = model.get_state()
    step = build_train_step(model, nn.SequenceCrossEntropyCriterion(),
                            optim, aux_loss_weight=1.0)
    # compare grads with and without aux by direct jax.grad
    import jax as _jax

    def loss_with_aux(p):
        out, st = model.apply(p, mstate, jnp.zeros((2, 8), jnp.int32),
                              training=True, rng=_jax.random.PRNGKey(0))
        from bigdl_tpu.optim.optimizer import _collect_aux_losses
        return _collect_aux_losses(st)

    g = _jax.grad(loss_with_aux)(params)
    router_g = np.asarray(g["block_1"]["mlp"]["router"])
    assert np.abs(router_g).max() > 0.0


def test_sequence_ce_clamps_out_of_range():
    logits = np.random.randn(2, 3, 5).astype(np.float32)
    bad_targets = np.array([[0, 4, 7], [5, 1, 2]])  # 7 and 5 out of range
    loss = float(nn.SequenceCrossEntropyCriterion().forward(
        logits, bad_targets))
    assert np.isfinite(loss)


def test_pretrained_child_adopted_in_all_composites():
    """Pre-materialized child weights survive wrapping in any composite."""
    lin = nn.Linear(4, 4)
    w0 = np.asarray(lin.get_parameters()["weight"]).copy()
    seq = nn.Sequential().add(lin)
    np.testing.assert_array_equal(
        np.asarray(seq.get_parameters()["0"]["weight"]), w0)
    td = nn.TimeDistributed(nn.Linear(4, 4))
    inner = td.layer if hasattr(td, "layer") else None
    if inner is not None:
        wi = np.asarray(inner.get_parameters()["weight"]).copy()
        np.testing.assert_array_equal(
            np.asarray(td.get_parameters()["layer"]["weight"]), wi)


def test_pipeline_parallel_matches_sequential(devices8):
    """GPipe pipeline over 4 stages == sequential layer application."""
    from bigdl_tpu.parallel import pipeline_forward

    mesh = make_mesh([4], ["pipe"], devices8[:4])
    L, D = 8, 16  # 8 layers, 2 per stage
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(L, D, D).astype(np.float32) * 0.2)
    bs = jnp.asarray(rng.randn(L, D).astype(np.float32) * 0.1)

    def block_fn(layer_params, x):
        w, b = layer_params
        return jnp.tanh(x @ w + b)

    x = jnp.asarray(rng.randn(16, D).astype(np.float32))
    got = pipeline_forward(block_fn, (ws, bs), x, mesh,
                           n_microbatches=4)
    ref = x
    for i in range(L):
        ref = jnp.tanh(ref @ ws[i] + bs[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_pipeline_parallel_grad_flows(devices8):
    from bigdl_tpu.parallel import pipeline_forward
    mesh = make_mesh([4], ["pipe"], devices8[:4])
    L, D = 4, 8
    rng = np.random.RandomState(1)
    ws = jnp.asarray(rng.randn(L, D, D).astype(np.float32) * 0.3)

    def block_fn(w, x):
        return jnp.tanh(x @ w)

    x = jnp.asarray(rng.randn(8, D).astype(np.float32))

    def loss(ws):
        return pipeline_forward(block_fn, ws, x, mesh,
                                n_microbatches=2).sum()

    g = jax.grad(loss)(ws)

    def ref_loss(ws):
        h = x
        for i in range(L):
            h = jnp.tanh(h @ ws[i])
        return h.sum()

    g_ref = jax.grad(ref_loss)(ws)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)


def test_flash_attention_path_matches_einsum_on_tpu():
    """When a real TPU is present, the pallas flash path must agree with
    the einsum reference; on CPU the flash path must cleanly bypass."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import dot_product_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 1024, 128), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 1024, 128), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 1024, 128), jnp.float32)
    ref = dot_product_attention(q, k, v, causal=True, use_flash=False)
    got = dot_product_attention(q, k, v, causal=True, use_flash=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-2 if jax.devices()[0].platform
                               == "tpu" else 1e-6, rtol=1e-2)


def test_user_aux_loss_key_does_not_join_objective():
    """The aux-loss contract is namespaced (AUX_LOSS_KEY): a user state
    leaf coincidentally named "aux_loss" must NOT be added to the loss,
    while the reserved key must (VERDICT r2 weak #7)."""
    from bigdl_tpu.nn import AUX_LOSS_KEY
    from bigdl_tpu.optim.optimizer import _collect_aux_losses

    user_tree = {"layer": {"aux_loss": jnp.asarray(7.0)}}
    assert float(_collect_aux_losses(user_tree)) == 0.0

    opted_in = {"layer": {AUX_LOSS_KEY: jnp.asarray(3.0)},
                "other": {"aux_loss": jnp.asarray(7.0)}}
    assert float(_collect_aux_losses(opted_in)) == 3.0


def test_flash_routing_is_memory_keyed():
    """The pallas kernel is an HBM escape hatch, not a speedup (measured
    on v5e: XLA einsum wins wall-clock at every length it can compile) —
    routing keys on score-matrix bytes, not sequence length."""
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import _flash_eligible

    small = jnp.zeros((2, 8, 2048, 128), jnp.bfloat16)   # 128 MB scores
    big = jnp.zeros((1, 8, 32768, 128), jnp.bfloat16)    # 17 GB scores
    assert not _flash_eligible(small, None, 0.0, False)
    assert _flash_eligible(big, None, 0.0, False)
    # masks/dropout/untileable shapes stay on the einsum path
    assert not _flash_eligible(big, object(), 0.0, False)
    assert not _flash_eligible(big, None, 0.1, True)
    odd = jnp.zeros((1, 8, 32768, 96), jnp.bfloat16)
    assert not _flash_eligible(odd, None, 0.0, False)


def test_ulysses_attention_matches_full():
    """All-to-all sequence parallelism: seq-sharded qkv re-shard to
    head-sharded, full attention per head group, shard back — exact
    equality with single-device attention (the second long-context
    layout next to ring attention)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from bigdl_tpu.nn.attention import dot_product_attention
    from bigdl_tpu.parallel import ulysses_attention_sharded

    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ("seq",))
    rs = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rs.randn(2, 8, 64, 16).astype(np.float32))
               for _ in range(3)]
    for causal in (False, True):
        out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from bigdl_tpu.parallel import ulysses_attention_sharded

    mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))
    q = jnp.zeros((1, 4, 64, 16))  # 4 heads on an 8-way axis
    with np.testing.assert_raises(Exception):
        np.asarray(ulysses_attention_sharded(q, q, q, mesh))


def test_pipeline_is_differentiable_for_training():
    """PP is training-capable, not a forward-only primitive: gradients
    through the microbatched ppermute pipeline match the dense stack's
    (a GPipe step is just jax.grad through pipeline_forward)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from bigdl_tpu.parallel import pipeline_forward

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("pipe",))
    rs = np.random.RandomState(0)
    L, D = 8, 6
    ws = jnp.asarray(rs.randn(L, D, D).astype(np.float32) * 0.3)
    x = jnp.asarray(rs.randn(8, D).astype(np.float32))
    y = jnp.asarray(rs.randn(8, D).astype(np.float32))

    def block(w, h):
        return jnp.tanh(h @ w)

    def pp_loss(ws):
        out = pipeline_forward(block, ws, x, mesh, n_microbatches=4)
        return jnp.mean((out - y) ** 2)

    def dense_loss(ws):
        h = x
        for i in range(L):
            h = block(ws[i], h)
        return jnp.mean((h - y) ** 2)

    g_pp = jax.jit(jax.grad(pp_loss))(ws)
    g_dense = jax.jit(jax.grad(dense_loss))(ws)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_dense),
                               atol=1e-5)
    # and one SGD step on pipeline grads lowers the pipeline loss
    ws2 = ws - 0.1 * g_pp
    assert float(pp_loss(ws2)) < float(pp_loss(ws))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_segments_match_dense(devices8, causal):
    """Packed segment masks survive the ring rotation: key-side ids
    travel with their K/V block, so cross-document attention stays
    zero exactly as in the dense segment-masked reference."""
    rng = np.random.RandomState(7)
    B, H, S, D = 2, 4, 64, 16
    q, k, v = [jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3)]
    segs = jnp.asarray(np.sort(rng.randint(0, 3, (B, S))).astype(np.int32))
    mesh = Mesh(np.array(devices8), ("seq",))
    ref = dot_product_attention(q, k, v, causal=causal, segments=segs,
                                use_flash=False)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                 segments=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_segments_match_dense(devices8, causal):
    """Ulysses all-gathers the id row after the head re-shard; the
    full-sequence mask it applies is the dense one."""
    from bigdl_tpu.parallel import ulysses_attention_sharded

    rng = np.random.RandomState(8)
    B, H, S, D = 2, 8, 64, 8
    q, k, v = [jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3)]
    segs = jnp.asarray(np.sort(rng.randint(0, 3, (B, S))).astype(np.int32))
    mesh = Mesh(np.array(devices8), ("seq",))
    ref = dot_product_attention(q, k, v, causal=causal, segments=segs,
                                use_flash=False)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=causal,
                                    segments=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_ring_segments_jit_grad_matches_dense(devices8):
    """jit(grad) through the segment-masked ring — the custom-VJP +
    ppermute composition the train step actually runs."""
    rng = np.random.RandomState(9)
    B, H, S, D = 1, 2, 32, 8
    q, k, v = [jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3)]
    segs = jnp.asarray(np.sort(rng.randint(0, 2, (B, S))).astype(np.int32))
    mesh = Mesh(np.array(devices8), ("seq",))
    g_ring = jax.jit(jax.grad(lambda q: ring_attention_sharded(
        q, k, v, mesh, causal=True, segments=segs).sum()))(q)
    g_full = jax.jit(jax.grad(lambda q: dot_product_attention(
        q, k, v, causal=True, segments=segs,
        use_flash=False).sum()))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               atol=2e-5)


def test_mha_adopts_seq_parallel_policy(devices8):
    """A plain MHA (no ring_axis) adopts the installed train-step
    policy: under ``use_sequence_parallel`` on a live seq mesh the
    forward matches the dense module bitwise-tolerant and the policy
    resolves the mesh width as its degree."""
    from bigdl_tpu.parallel import (SeqParallelConfig,
                                    use_sequence_parallel)

    mesh = Mesh(np.array(devices8), ("seq",))
    mha = nn.MultiHeadAttention(64, 8, causal=True)  # 8 heads: ulysses
    params = mha.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(10)
                    .randn(2, 64, 64).astype(np.float32))
    dense = np.asarray(mha.forward_fn(params, x))
    for impl in ("ring", "ulysses"):
        cfg = SeqParallelConfig(axis="seq", impl=impl, mesh=mesh)
        with use_sequence_parallel(cfg):
            out = np.asarray(mha.forward_fn(params, x))
        np.testing.assert_allclose(out, dense, atol=2e-5)
        assert cfg.active_on(mesh) and cfg.degree() == 8
