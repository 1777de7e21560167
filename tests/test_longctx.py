"""Long-context stack tests (chunked prefill, paged decode, the
sequence-parallel train policy).

Chunked prefill must be BIT-identical to single-shot prefill — same
last-token logits, same KV rows — at every prompt length straddling a
chunk boundary, because chunking is a dispatch-shape decision, not a
numeric one. Paged decode must be token-identical to the contiguous
ragged kernel for any page table naming the same rows. The SP policy
(``SeqParallelConfig``) must be a quiet no-op wherever it cannot apply
(no mesh, or no such axis on it), leaving the dense program
bit-identical; the sharded equivalence tests live in
tests/test_parallel.py.
"""
import numpy as np
import pytest

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.generation import GenerationConfig, GenerationService
from bigdl_tpu.generation.engine import DecodeEngine
from bigdl_tpu.generation.kv_cache import KVCache
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.serving import Servable
from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache
from bigdl_tpu.utils.random import RandomGenerator


def _model(vocab=50, hidden=32, layers=2, heads=4, max_len=64, seed=42):
    RandomGenerator.set_seed(seed)
    m = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                      num_layers=layers, num_heads=heads,
                      max_len=max_len).evaluate()
    m.ensure_initialized()
    return m


def _servable(model):
    return Servable("lm", 1, model, model.get_parameters(),
                    model.get_state())


def _engine(chunk=None, buckets=(16, 32, 64), slots=4):
    return DecodeEngine(CompileCache(), BucketLadder(max(buckets),
                                                     buckets=buckets),
                        slots=slots, prefill_rows=2,
                        prefill_chunk=chunk)


# ------------------------------------------------- chunked prefill

def test_chunked_prefill_bitwise_identical_at_every_chunk_boundary():
    """The acceptance invariant: a prompt prefilled in fixed 16-token
    chunks produces the SAME last-token logits and the SAME KV rows as
    the single-shot prefill, at every length straddling a chunk
    boundary (chunk-1 / chunk / chunk+1 / multiples / full rung)."""
    model = _model()
    sv = _servable(model)
    chunked, single = _engine(chunk=16), _engine(chunk=None)
    rng = np.random.RandomState(0)
    for plen in (15, 16, 17, 31, 32, 33, 48, 63, 64):
        prompt = rng.randint(1, 50, plen).astype(np.int32)
        kv_c = KVCache.for_model(model, 4, 64)
        kv_s = KVCache.for_model(model, 4, 64)
        out_c, bucket_c = chunked.prefill(sv, kv_c, [prompt], [1])
        out_s, bucket_s = single.prefill(sv, kv_s, [prompt], [1])
        assert bucket_c == bucket_s
        assert np.array_equal(out_c, out_s), f"logits differ at {plen}"
        # the written KV region is bitwise the single-shot one
        assert np.array_equal(np.asarray(kv_c.k)[:, 1, :, :plen],
                              np.asarray(kv_s.k)[:, 1, :, :plen]), plen
        assert np.array_equal(np.asarray(kv_c.v)[:, 1, :, :plen],
                              np.asarray(kv_s.v)[:, 1, :, :plen]), plen
        assert kv_c.lengths[1] == kv_s.lengths[1] == plen


def test_chunked_prefill_one_program_per_rung():
    """Chunking never mints extra programs: the chunk width is the
    rung's ONE token shape, so a chunked engine compiles exactly as
    many prefill programs as rungs it touched."""
    model = _model()
    sv = _servable(model)
    eng = _engine(chunk=16)
    kv = KVCache.for_model(model, 4, 64)
    rng = np.random.RandomState(1)
    for plen in (10, 20, 40, 60):  # rungs 16, 32, 64, 64
        eng.prefill(sv, kv, [rng.randint(1, 50, plen).astype(np.int32)],
                    [0])
    assert eng.compile_count(sv) == 3  # one per touched rung


def test_prefill_chunk_admission_and_start_validation():
    """The admission rule: the chunk must divide every larger rung
    (else chunk starts drift off the program's token grid), and a
    seeded ``start`` must be a chunk multiple below the prompt."""
    with pytest.raises(ValueError, match="divide"):
        _engine(chunk=12)  # 12 does not divide 16/32/64
    with pytest.raises(ValueError):
        _engine(chunk=0)
    eng = _engine(chunk=16)
    assert eng.chunk_for(16) == 16   # rung <= chunk: single-shot
    assert eng.chunk_for(64) == 16   # larger rungs fill chunkwise
    model = _model()
    sv = _servable(model)
    kv = KVCache.for_model(model, 4, 64)
    prompt = np.arange(1, 41, dtype=np.int32)  # rung 64
    with pytest.raises(ValueError, match="chunk multiple"):
        eng.prefill(sv, kv, [prompt], [0], start=[10])
    with pytest.raises(ValueError, match="chunk multiple"):
        eng.prefill(sv, kv, [prompt], [0], start=[48])  # >= len 40


def test_chunked_service_e2e_long_prompt_tokens_and_metrics():
    """A long prompt generates end-to-end through chunked prefill with
    the same greedy tokens as the unchunked service, the chunk counter
    reports every chunk dispatched, and the compile count stays inside
    the <= 2-programs-per-bucket bound."""
    model = _model()
    prompt = np.random.RandomState(3).randint(1, 50, 60).astype(np.int32)

    def run(chunk):
        svc = GenerationService(config=GenerationConfig(
            slots=2, max_len=64, length_buckets=(16, 32, 64),
            prefill_rows=2, prefill_chunk=chunk))
        svc.load("lm", model)
        try:
            out = list(svc.generate("lm", prompt,
                                    max_new_tokens=4).result(60))
            m = svc.metrics("lm")
        finally:
            svc.shutdown()
        return out, m

    chunked_out, m = run(16)
    single_out, _ = run(None)
    assert chunked_out == single_out
    assert m["prefill_chunks"] == -(-len(prompt) // 16)  # ceil(60/16)
    assert m["compile_count"] <= 2 * 3


# --------------------------------------------------- paged decode

def _decode_reference(q, k, v, lengths):
    """Length-masked dense decode attention in f32."""
    import jax.numpy as jnp
    slots, h, t, d = k.shape
    s = np.einsum("shd,shtd->sht", np.asarray(q, np.float32),
                  np.asarray(k, np.float32)) / np.sqrt(d)
    mask = np.arange(t)[None, None, :] < np.asarray(
        lengths).reshape(-1, 1, 1)
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("sht,shtd->shd", p, np.asarray(v, np.float32))


def test_paged_decode_token_identical_to_contiguous():
    """The paged kernel over an identity page view of a contiguous
    cache agrees with the contiguous ragged kernel reading the same
    cache time-last (``[slots, H, D, T]``, the form ``KVCache`` keeps)
    within f32 reduction tolerance — the two tile the time axis
    differently (pages of 8 rows; one whole-``T`` lane tile), so the
    online soft-max accumulates in another order — and both are tight
    against the dense length-masked reference."""
    import jax
    from bigdl_tpu.kernels.paged_decode import (paged_decode_attention,
                                                paged_view)
    from bigdl_tpu.kernels.ragged_decode import ragged_decode_attention

    rng = np.random.RandomState(5)
    slots, h, t, d, page = 3, 2, 32, 8, 8
    q = np.asarray(rng.randn(slots, h, d), np.float32)
    k = np.asarray(rng.randn(slots, h, t, d), np.float32)
    v = np.asarray(rng.randn(slots, h, t, d), np.float32)
    lengths = np.array([5, 17, 32], np.int32)
    kp, vp, table = paged_view(jax.numpy.asarray(k),
                               jax.numpy.asarray(v), page)
    paged = np.asarray(paged_decode_attention(
        jax.numpy.asarray(q), kp, vp, table, jax.numpy.asarray(lengths),
        interpret=True))
    contig = np.asarray(ragged_decode_attention(
        jax.numpy.asarray(q), jax.numpy.asarray(k.swapaxes(2, 3)),
        jax.numpy.asarray(v.swapaxes(2, 3)), jax.numpy.asarray(lengths),
        interpret=True))
    reference = _decode_reference(q, k, v, lengths)
    np.testing.assert_allclose(paged, contig, atol=2e-6)
    np.testing.assert_allclose(paged, reference, atol=2e-6)
    np.testing.assert_allclose(contig, reference, atol=2e-6)


def test_paged_decode_shuffled_pool_matches_identity():
    """Physical page placement is invisible: permuting the pool and
    renaming the table gives the same output — the table IS the
    address space."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.kernels.paged_decode import (paged_decode_attention,
                                                paged_view)

    rng = np.random.RandomState(6)
    slots, h, t, d, page = 2, 2, 32, 8, 8
    q = jnp.asarray(rng.randn(slots, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(slots, h, t, d).astype(np.float32))
    v = jnp.asarray(rng.randn(slots, h, t, d).astype(np.float32))
    lengths = jnp.asarray(np.array([13, 32], np.int32))
    kp, vp, table = paged_view(k, v, page)
    base = np.asarray(paged_decode_attention(q, kp, vp, table, lengths,
                                             interpret=True))
    perm = rng.permutation(kp.shape[0])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    shuffled = np.asarray(paged_decode_attention(
        q, kp[perm], vp[perm], jnp.asarray(inv)[table], lengths,
        interpret=True))
    assert np.array_equal(base, shuffled)


def test_paged_dispatch_eligibility_and_decline():
    """The dispatch entry: paged decode runs under an enabled config
    with eligible shapes, declines (None) on config-off and on shape
    mismatches — the caller's contiguous-gather escape hatch."""
    import jax.numpy as jnp
    from bigdl_tpu import kernels
    from bigdl_tpu.kernels import dispatch
    from bigdl_tpu.kernels.paged_decode import paged_view

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 2, 16, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 2, 16, 8).astype(np.float32))
    lengths = jnp.asarray(np.array([4, 16], np.int32))
    kp, vp, table = paged_view(k, v, 8)
    with kernels.use(kernels.KernelConfig.all_on()):
        out = dispatch.paged_decode_attention(q, kp, vp, table, lengths)
        assert out is not None and out.shape == (2, 2, 8)
        # wrong table width (slots mismatch) -> shape decline
        assert dispatch.paged_decode_attention(
            q, kp, vp, table[:1], lengths) is None
        # int pools -> dtype decline
        assert dispatch.paged_decode_attention(
            q, kp.astype(jnp.int32), vp.astype(jnp.int32), table,
            lengths) is None
    with kernels.use(kernels.KernelConfig.off()):
        assert dispatch.paged_decode_attention(
            q, kp, vp, table, lengths) is None


# ------------------------------------- sequence-parallel policy

def test_seq_parallel_config_validation_and_context():
    from bigdl_tpu.parallel import (SeqParallelConfig,
                                    active_sequence_parallel,
                                    use_sequence_parallel)

    with pytest.raises(ValueError, match="ring.*ulysses"):
        SeqParallelConfig(impl="megatron")
    cfg = SeqParallelConfig(axis="seq", impl="ulysses")
    assert active_sequence_parallel() is None
    with use_sequence_parallel(cfg):
        assert active_sequence_parallel() is cfg
        with use_sequence_parallel(None):  # nested dense override
            assert active_sequence_parallel() is None
        assert active_sequence_parallel() is cfg
    assert active_sequence_parallel() is None


def test_seq_parallel_noop_without_mesh():
    """Without a resolvable mesh the policy reports inactive and
    degree 1 — ``ZeroConfig.active_on``'s quiet-no-op contract."""
    from bigdl_tpu.parallel import SeqParallelConfig

    cfg = SeqParallelConfig(axis="nonexistent_axis")
    assert not cfg.active_on(None)
    assert cfg.degree() == 1


def test_build_train_step_seq_parallel_noop_is_bitwise_dense():
    """``build_train_step(seq_parallel=...)`` with an inapplicable
    policy runs the IDENTICAL dense program — losses bitwise equal —
    and the degree gauge reads 1 (the paid degree, not the asked-for
    one)."""
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.parallel import SeqParallelConfig

    model = _model(max_len=16)
    model.training()
    crit = nn.SequenceCrossEntropyCriterion()
    optim = SGD(learning_rate=0.1)
    rng = np.random.RandomState(11)
    x = rng.randint(1, 50, (2, 16)).astype(np.int32)
    y = np.roll(x, -1, axis=1)

    losses = []
    for sp in (None, SeqParallelConfig(axis="seq")):
        # fresh trees each run: the step donates its input buffers
        params = jax.tree_util.tree_map(np.asarray,
                                        model.get_parameters())
        opt_state = optim.init_state(params)
        mstate = model.get_state()
        step = build_train_step(model, crit, optim, seq_parallel=sp)
        _, _, _, loss = step(params, opt_state, mstate,
                             jax.random.PRNGKey(0), 0.1, x, y)
        losses.append(np.asarray(loss))
    assert np.array_equal(losses[0], losses[1])
    assert telemetry.gauge("train/seq_parallel/degree").value() == 1


def test_optimizer_set_sequence_parallel_typecheck():
    """The fluent setter: accepts a config or None (returns self for
    chaining), rejects anything else typed."""
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.parallel import SeqParallelConfig
    from bigdl_tpu.tools.chaos import _build_workload

    model, ds, crit = _build_workload("tiny", 42, 8)
    opt = Optimizer(model, ds, crit, batch_size=8)
    assert opt.set_sequence_parallel(
        SeqParallelConfig(axis="seq")) is opt
    assert opt.set_sequence_parallel(None) is opt
    with pytest.raises(TypeError):
        opt.set_sequence_parallel("ring")
