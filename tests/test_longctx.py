"""Long-context stack tests (chunked prefill, the one cached attention
step, the sequence-parallel train policy).

Chunked prefill must agree with single-shot prefill — same last-token
logits, same KV rows, to float32 reduction order — at every prompt
length straddling a chunk boundary, because chunking is a
dispatch-shape decision, not a numeric one. The cached step
(``nn.attention.cached_attention``, shared by every servable decoder)
must agree with a plain numpy soft-max over the columns it wrote. The
SP policy (``SeqParallelConfig``) must be a quiet no-op wherever it
cannot apply (no mesh, or no such axis on it), leaving the dense
program bit-identical; the sharded equivalence tests live in
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

# float32 reduction order differs between programs of different shapes
# (a [1, 16] chunk against a [2, 64] shot; the one-shot prompt attends
# its own tokens, a chunk the columns written before it): observed 1e-7
# to 6e-7 on values of order 1, so this is ample
SHAPE_TOL = dict(rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("plen", [15, 16, 17, 31, 32, 33, 48, 63, 64])
def test_chunked_prefill_agrees_at_every_chunk_boundary(plen):
    """The acceptance invariant: a prompt prefilled in fixed 16-token
    chunks produces the same last-token logits and the same KV rows as
    the single-shot prefill (``SHAPE_TOL``), at every length straddling
    a chunk boundary (chunk-1 / chunk / chunk+1 / multiples / full
    rung)."""
    model = _model()
    sv = _servable(model)
    chunked, single = _engine(chunk=16), _engine(chunk=None)
    prompt = np.random.RandomState(plen).randint(1, 50, plen) \
        .astype(np.int32)
    kv_c = KVCache.for_model(model, 4, 64)
    kv_s = KVCache.for_model(model, 4, 64)
    out_c, bucket_c = chunked.prefill(sv, kv_c, [prompt], [1])
    out_s, bucket_s = single.prefill(sv, kv_s, [prompt], [1])
    assert bucket_c == bucket_s
    np.testing.assert_allclose(out_c, out_s, **SHAPE_TOL)
    assert np.argmax(out_c) == np.argmax(out_s)
    # the written KV region is the single-shot one
    for got, want in ((kv_c.k, kv_s.k), (kv_c.v, kv_s.v)):
        np.testing.assert_allclose(
            np.asarray(got)[:, 1, :, :, :plen],
            np.asarray(want)[:, 1, :, :, :plen], **SHAPE_TOL)
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


# ------------------------------------------- the one cached step

def _softmax_reference(q, k, v, offsets):
    """Plain numpy: query i of row b sits at position offsets[b] + i
    and attends the columns j <= that of ``k`` / ``v`` ``[B, H, D,
    T]``; float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    s = q.shape[2]
    scores = np.einsum("bhqd,bhdt->bhqt", q, k) / np.sqrt(q.shape[3])
    qpos = np.asarray(offsets)[:, None] + np.arange(s)[None]
    seen = np.arange(k.shape[3])[None, None, :] <= qpos[:, :, None]
    scores = np.where(seen[:, None], scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bhqt,bhdt->bhqd", w, v)


@pytest.mark.parametrize("case,s,offsets,fresh", [
    ("decode", 1, [5, 0, 11], False),
    ("chunk", 4, [4, 8, 0], False),
    ("fresh", 8, [0, 0, 0], True),
])
def test_cached_step_agrees_with_numpy_softmax(case, s, offsets, fresh):
    """``cached_attention`` with as many K/V heads as query heads and no
    window (``MultiHeadAttention``'s use of it): the new columns land at
    each row's offset, nothing else of the entry moves, and the output
    is the plain soft-max over the written columns — for one new token
    a row, a chunk in the middle of a prompt, and a ``fresh`` prompt
    (which must not see the stale columns its rows still hold)."""
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import cached_attention

    rng = np.random.RandomState(len(case))
    b, h, d, t = 3, 2, 8, 16
    q = rng.randn(b, h, s, d).astype(np.float32)
    k_t = rng.randn(b, h, d, s).astype(np.float32)
    v_t = rng.randn(b, h, d, s).astype(np.float32)
    cache = {"k": rng.randn(b, h, d, t).astype(np.float32),
             "v": rng.randn(b, h, d, t).astype(np.float32)}
    offsets = np.asarray(offsets, np.int32)
    out, new = cached_attention(
        jnp.asarray(q), jnp.asarray(k_t), jnp.asarray(v_t),
        {n: jnp.asarray(a) for n, a in cache.items()},
        jnp.asarray(offsets), attend_len=12 if case == "decode" else t,
        fresh=fresh)
    want = {n: a.copy() for n, a in cache.items()}
    for r, off in enumerate(offsets):
        want["k"][r, :, :, off:off + s] = k_t[r]
        want["v"][r, :, :, off:off + s] = v_t[r]
    for n in ("k", "v"):
        assert np.array_equal(np.asarray(new[n]), want[n]), n
    np.testing.assert_allclose(
        np.asarray(out),
        _softmax_reference(q, want["k"], want["v"], offsets),
        rtol=1e-5, atol=1e-6)


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
