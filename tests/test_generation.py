"""Generation serving (bigdl_tpu.generation): bucketed KV-cache decode
with continuous batching. Pins the subsystem's load-bearing claims —
greedy decode from the cache is token-bit-identical to full-sequence
re-forward at every step, K length-buckets compile at most 2K programs
(asserted via the compile counter, warmup covers them all), slot
alloc/free never double-assigns, admission under a full cache queues
rather than drops, deadlines and loop deaths fail streams TYPED, and
registry hot-swap under live decode finishes old-version slots on the
old snapshot."""
import time

import numpy as np
import pytest

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu import faults, kernels
from bigdl_tpu.generation import (GenerationConfig, GenerationService,
                                  KVCache, SamplingParams, Sampler,
                                  SlotAllocator, TokenStream)
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.serving import DeadlineExceeded, QueueFull, WorkerDied
from bigdl_tpu.utils.random import RandomGenerator


def _model(vocab=50, hidden=32, layers=2, heads=4, max_len=32, seed=42):
    RandomGenerator.set_seed(seed)
    m = TransformerLM(vocab_size=vocab, hidden_size=hidden,
                      num_layers=layers, num_heads=heads,
                      max_len=max_len).evaluate()
    m.ensure_initialized()
    return m


def _service(model=None, **cfg):
    defaults = dict(slots=4, max_len=16, length_buckets=(16,),
                    prefill_rows=2)
    defaults.update(cfg)
    svc = GenerationService(config=GenerationConfig(**defaults))
    svc.load("lm", model if model is not None else _model())
    return svc


def _greedy_reference(model, prompt, n, pad_to=16):
    """Full-sequence greedy re-forward, one token at a time (padded to
    one fixed length so the reference compiles once; trailing pad
    tokens cannot reach position len-1 under the causal mask)."""
    import jax

    @jax.jit
    def fwd(p, s, t):
        logits, _ = model.apply(p, s, t, training=False)
        return logits

    params, state = model.get_parameters(), model.get_state()
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(toks)] = toks
        logits = np.asarray(fwd(params, state, padded))
        nxt = int(np.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


# ---------------------------------------------------------------- slots

def test_slot_allocator_never_double_assigns():
    rng = np.random.RandomState(0)
    alloc = SlotAllocator(5)
    held = set()
    for _ in range(500):
        if held and (rng.rand() < 0.5 or not alloc.free_count):
            s = held.pop()
            alloc.free(s)
        elif alloc.free_count:
            s = alloc.alloc()
            assert s not in held, "slot handed out twice"
            held.add(s)
        assert held == set(alloc.live)
        assert len(held) + alloc.free_count == 5
    with pytest.raises(RuntimeError):
        alloc.free(99)  # freeing a non-live slot is an accounting bug
    for s in sorted(held):
        alloc.free(s)
    for _ in range(5):
        alloc.alloc()
    with pytest.raises(RuntimeError):
        alloc.alloc()  # full cache never over-allocates


def test_kv_cache_geometry_and_occupancy():
    m = _model()
    kv = KVCache.for_model(m, slots=4, max_len=16)
    # one [slots, H, D, T] array per layer, time last
    assert [a.shape for a in kv.k] == [(4, 4, 8, 16)] * 2
    assert [a.shape for a in kv.v] == [(4, 4, 8, 16)] * 2
    assert kv.nbytes() == 2 * 2 * 4 * 4 * 8 * 16 * 4
    import jax
    spec = KVCache.spec_for_model(m, slots=4, max_len=16)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), spec) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), kv.entries)
    assert kv.lengths.tolist() == [0, 0, 0, 0]
    assert kv.occupancy() == 0.0
    kv.allocator.alloc()
    assert kv.occupancy() == pytest.approx(0.25)
    with pytest.raises(ValueError):
        KVCache.for_model(m, slots=4, max_len=64)  # > model.max_len


# ------------------------------------------------- decode exactness

def test_greedy_decode_bit_identical_to_full_reforward_every_step():
    """The acceptance invariant: greedy decode from the KV cache
    yields the SAME token as a full-sequence re-forward at every
    single step."""
    model = _model()
    svc = _service(model)
    try:
        prompt = np.array([3, 7, 1, 4, 9], np.int32)
        out = svc.generate("lm", prompt, max_new_tokens=8).result(60)
        assert list(out) == _greedy_reference(model, prompt, 8)
        # a second, differently-shaped prompt through the same programs
        prompt2 = np.array([11, 2], np.int32)
        out2 = svc.generate("lm", prompt2, max_new_tokens=5).result(60)
        assert list(out2) == _greedy_reference(model, prompt2, 5)
    finally:
        svc.shutdown()


def test_prefill_and_decode_logits_agree_with_full_forward():
    """Engine-level exactness: prefill and decode-step logits agree
    with the padded full-sequence forward to float32 reduction order
    (they are different programs by design: the prefill multiplies one
    row by the head and attends its own tokens, a decode step is a
    single-query product; observed 1e-7 to 6e-7 on logits of order 1,
    so ``atol=1e-5`` is ample), and the greedy token is the same."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache
    from bigdl_tpu.serving.registry import ModelRegistry

    model = _model()
    sv = ModelRegistry().load("m", model)
    eng = DecodeEngine(CompileCache(), BucketLadder(16, (16,)),
                       slots=4, prefill_rows=2)
    kv = KVCache.for_model(model, 4, 16)
    prompt = np.array([3, 7, 1, 4, 9], np.int32)
    logits, _ = eng.prefill(sv, kv, [prompt], [0])

    @jax.jit
    def fwd(p, s, t):
        out, _ = model.apply(p, s, t, training=False)
        return out

    toks = list(prompt)
    for step in range(5):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :len(toks)] = toks
        full = np.asarray(fwd(sv.params, sv.state,
                              jnp.asarray(padded)))[0, len(toks) - 1]
        np.testing.assert_allclose(logits[0], full, atol=1e-5, rtol=0)
        nxt = int(np.argmax(logits[0]))
        assert nxt == int(np.argmax(full))
        toks.append(nxt)
        tokens = np.zeros(4, np.int32)
        tokens[0] = nxt
        positions = np.zeros(4, np.int32)
        positions[0] = kv.lengths[0]
        active = np.zeros(4, bool)
        active[0] = True
        out, _ = eng.decode(sv, kv, tokens, positions, active)
        kv.lengths[0] += 1
        logits = out[:1]
    # anchor against the UNPADDED exact-length re-forward too: the
    # greedy token agrees there as well (one eager forward)
    exact, _ = model.apply(sv.params, sv.state,
                           jnp.asarray([toks]), training=False)
    exact = np.asarray(exact)[0, len(toks) - 1]
    np.testing.assert_allclose(logits[0], exact, atol=1e-5, rtol=0)
    assert int(np.argmax(logits[0])) == int(np.argmax(exact))


def test_a_greedy_step_takes_ids_and_a_sampled_request_gets_logits():
    """The decode program returns every slot's argmax beside its logits:
    ``ids_only`` hands back those ``[slots]`` ids (equal to ``np.argmax``
    of the logits the same step returns, ties and all), the loop asks
    for them only while every live request is greedy, and a request
    with a temperature still samples from full logits rows."""
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache
    from bigdl_tpu.serving.registry import ModelRegistry

    model = _model()
    sv = ModelRegistry().load("m", model)
    eng = DecodeEngine(CompileCache(), BucketLadder(16, (16,)),
                       slots=4, prefill_rows=2)
    kv = KVCache.for_model(model, 4, 16)
    eng.prefill(sv, kv, [np.array([3, 7, 1], np.int32),
                         np.array([5, 2], np.int32)], [0, 2])
    tokens = np.array([9, 0, 4, 0], np.int32)
    active = np.array([True, False, True, False])
    positions = kv.lengths.copy()
    logits, _ = eng.decode(sv, kv, tokens, positions, active)
    ids, _ = eng.decode(sv, kv, tokens, positions, active, ids_only=True)
    assert ids.shape == (4,) and ids.dtype == np.int32
    assert ids.tolist() == np.argmax(logits, axis=-1).tolist()

    svc = _service(model)
    try:
        prompt = np.array([3, 7, 1, 4, 9], np.int32)
        greedy = svc.generate("lm", prompt, max_new_tokens=6).result(60)
        assert list(greedy) == _greedy_reference(model, prompt, 6)
        a = svc.generate("lm", prompt, max_new_tokens=6, temperature=0.9,
                         seed=11).result(60)
        b = svc.generate("lm", prompt, max_new_tokens=6, temperature=0.9,
                         seed=11).result(60)
        assert list(a) == list(b) and len(a) == 6
    finally:
        svc.shutdown()


# ------------------------------------------------- the compile bound

def test_k_buckets_compile_at_most_2k_under_generation_burst():
    """K length-buckets => at most 2K compiled programs, warmup covers
    every pair, and a ragged burst afterwards compiles NOTHING new —
    asserted via the compile counter, not trusted."""
    buckets = (4, 8, 16)  # K = 3
    svc = _service(length_buckets=buckets, slots=3, prefill_rows=2)
    try:
        warm = svc.compile_count("lm")
        assert warm <= 2 * len(buckets)
        rng = np.random.RandomState(3)
        streams = [svc.generate("lm",
                                rng.randint(1, 50, rng.randint(1, 12)),
                                max_new_tokens=int(rng.randint(1, 6)))
                   for _ in range(12)]
        for s in streams:
            s.result(timeout=60)
        assert svc.compile_count("lm") == warm, \
            "a generation burst after warmup must never compile"
        assert svc.compile_count("lm") <= 2 * len(buckets)
    finally:
        svc.shutdown()


def test_warmup_counts_pairs_and_is_idempotent():
    model = _model()
    svc = _service(model, length_buckets=(8, 16))
    try:
        assert svc.compile_count("lm") == 4  # 2 rungs x (prefill+decode)
        assert svc.warmup("lm") == 0  # everything already compiled
    finally:
        svc.shutdown()


# ------------------------------------- continuous-batching invariants

def test_admission_under_full_cache_queues_rather_than_drops():
    """More requests than slots: every one completes — the full cache
    QUEUES admissions into freed slots, step by step."""
    svc = _service(slots=2, prefill_rows=2, max_queue=64)
    try:
        rng = np.random.RandomState(0)
        streams = [svc.generate("lm", rng.randint(1, 50, 4),
                                max_new_tokens=4) for _ in range(10)]
        outs = [s.result(timeout=60) for s in streams]
        assert all(len(o) == 4 for o in outs)
        m = svc.metrics("lm")
        assert m["request_count"] == 10 and m["finished"] == 10
        assert m["rejected"] == 0
    finally:
        svc.shutdown()


def test_queue_full_rejects_typed_at_the_admission_bound():
    svc = _service(slots=1, prefill_rows=1, max_queue=1)
    try:
        with faults.armed("serving/decode=delay:30,times:1000"):
            a = svc.generate("lm", [1, 2, 3], max_new_tokens=8)
            time.sleep(0.15)  # a occupies the only slot
            b = svc.generate("lm", [4, 5], max_new_tokens=2)
            with pytest.raises(QueueFull):
                svc.generate("lm", [6], max_new_tokens=2)
            assert svc.metrics("lm")["rejected"] == 1
            a.result(timeout=60)
            b.result(timeout=60)
    finally:
        svc.shutdown()


def test_deadline_expired_generation_evicts_with_typed_error():
    """A deadline that passes mid-generation evicts the slot and fails
    the stream with DeadlineExceeded (partial tokens retained); a
    deadline that passes in the queue fails the same way."""
    svc = _service(slots=1, prefill_rows=1, max_queue=8)
    try:
        with faults.armed("serving/decode=delay:40,times:1000"):
            s = svc.generate("lm", [1, 2, 3], max_new_tokens=16,
                             timeout_ms=150)
            q = svc.generate("lm", [4, 5], max_new_tokens=16,
                             timeout_ms=60)  # expires while queued
            with pytest.raises(DeadlineExceeded):
                s.result(timeout=60)
            assert 1 <= len(s.tokens()) < 16  # partial progress kept
            with pytest.raises(DeadlineExceeded):
                q.result(timeout=60)
        assert svc.metrics("lm")["timed_out"] == 2
        # the expired slots were freed: the loop keeps serving
        assert len(svc.generate("lm", [7, 8],
                                max_new_tokens=3).result(60)) == 3
    finally:
        svc.shutdown()


def test_hot_swap_under_live_decode_finishes_old_version_slots():
    """Swap while slots decode: the in-flight generation finishes on
    the snapshot it prefilled with (v1 greedy reference), the next
    admission decodes the new version (v2 reference)."""
    m1 = _model(seed=42)
    m2 = _model(seed=7)
    svc = _service(m1, slots=2, prefill_rows=1)
    try:
        prompt = np.array([3, 7, 1], np.int32)
        with faults.armed("serving/decode=delay:25,times:1000"):
            live = svc.generate("lm", prompt, max_new_tokens=8)
            live.first(timeout=30)  # admitted: it occupies a v1 slot
            svc.load("lm", m2)      # hot-swap under live decode
            after = svc.generate("lm", prompt, max_new_tokens=8)
            v1_out = live.result(timeout=60)
            v2_out = after.result(timeout=60)
        assert list(v1_out) == _greedy_reference(m1, prompt, 8)
        assert list(v2_out) == _greedy_reference(m2, prompt, 8)
        # the drained v1 group released its cache: no live slots remain
        assert svc.metrics("lm")["live_slots"] == 0
    finally:
        svc.shutdown()


def test_decode_fault_fails_streams_typed_and_loop_restarts():
    """PR-5 supervision semantics on the decode loop: an injected
    serving/decode fault fails every in-flight stream with a typed
    WorkerDied (never a hang), and the restarted loop keeps serving."""
    svc = _service(slots=2, prefill_rows=2)
    try:
        with faults.armed("serving/decode=nth:2,raise:RuntimeError"):
            a = svc.generate("lm", [1, 2, 3], max_new_tokens=8)
            b = svc.generate("lm", [4, 5], max_new_tokens=8)
            for s in (a, b):
                with pytest.raises(WorkerDied):
                    s.result(timeout=60)
        m = svc.metrics("lm")
        assert m["worker_restarts"] == 1
        # restarted: the same name serves again, correctly
        out = svc.generate("lm", [1, 2, 3], max_new_tokens=4).result(60)
        assert len(out) == 4
    finally:
        svc.shutdown()


# ------------------------------------------------- sampling + streams

def test_seeded_sampling_deterministic_and_topk1_is_greedy():
    svc = _service()
    try:
        prompt = [3, 7, 1]
        greedy = svc.generate("lm", prompt, max_new_tokens=6).result(60)
        t1 = svc.generate("lm", prompt, max_new_tokens=6,
                          temperature=0.7, top_k=1, seed=9).result(60)
        assert np.array_equal(t1, greedy), \
            "top_k=1 sampling must reduce to greedy"
        a = svc.generate("lm", prompt, max_new_tokens=6,
                         temperature=0.9, top_k=5, seed=11).result(60)
        b = svc.generate("lm", prompt, max_new_tokens=6,
                         temperature=0.9, top_k=5, seed=11).result(60)
        assert np.array_equal(a, b), "same seed => same stream"
    finally:
        svc.shutdown()


def test_sampler_validation_and_distribution_support():
    with pytest.raises(ValueError):
        SamplingParams(top_k=0).validate()
    s = Sampler(SamplingParams(temperature=1.0, top_k=2, seed=3))
    logits = np.array([0.0, 5.0, 4.0, -1.0], np.float32)
    draws = {s.sample(logits) for _ in range(64)}
    assert draws <= {1, 2}, "top-k must restrict the support"


def test_eos_token_evicts_the_slot():
    model = _model()
    probe = _service(model)
    try:
        first = int(probe.generate("lm", [3, 7, 1],
                                   max_new_tokens=1).result(60)[0])
    finally:
        probe.shutdown()
    svc = _service(model, eos_token=first)
    try:
        s = svc.generate("lm", [3, 7, 1], max_new_tokens=8)
        out = s.result(timeout=60)
        assert s.finish_reason == "eos"
        assert list(out) == [first]  # the EOS token is included
        assert svc.metrics("lm")["live_slots"] == 0
    finally:
        svc.shutdown()


def test_token_stream_iteration_futures_and_ttft():
    svc = _service()
    try:
        s = svc.generate("lm", [2, 4], max_new_tokens=4)
        f1 = s.token_future(1)
        f9 = s.token_future(9)  # beyond the generation
        toks = list(s)
        assert toks == list(s.result(60))
        assert len(toks) == 4
        assert s.first() == toks[0]
        assert f1.result(timeout=10) == toks[1]
        assert f9.result(timeout=10) is None  # finished earlier: None
        assert s.ttft_ms is not None and s.ttft_ms >= 0.0
        assert s.finish_reason == "max_tokens"
    finally:
        svc.shutdown()


def test_prompt_validation_and_max_new_cap():
    svc = _service()  # max_len = 16
    try:
        with pytest.raises(ValueError):
            svc.generate("lm", [])
        with pytest.raises(ValueError):
            svc.generate("lm", list(range(1, 17)))  # no room to decode
        s = svc.generate("lm", list(range(1, 13)),
                         max_new_tokens=100)  # capped to 16 - 12
        assert len(s.result(timeout=60)) == 4
    finally:
        svc.shutdown()


# ----------------------------------------------- telemetry + lifecycle

def test_generation_telemetry_spans_and_gauges():
    telemetry.tracer().clear()
    telemetry.enable()
    try:
        svc = _service()
        svc.generate("lm", [1, 2, 3], max_new_tokens=3).result(60)
        svc.shutdown()
        names = {rec.name for rec in telemetry.tracer().spans()}
        assert "serving/prefill" in names and "serving/decode" in names
        assert "serving/decode/kv" in names
        m = svc.metrics("lm")
        assert m["tokens"] == 3
        assert 0.0 < m["padding_efficiency"] <= 1.0
        assert "ttft_ms_p50" in m and "token_ms_p99" in m
        assert telemetry.audit_names(svc.metrics_registry) == []
    finally:
        telemetry.disable()
        telemetry.tracer().clear()


@pytest.mark.parametrize("attend_len, positions, want", [
    # a 4-column ring and a global entry of 512 columns, 2 layers each;
    # one tile a block at these sizes, whatever the rung
    (256, [2, 299, 0, 40], (2 * (3 + 4) + 2 * (3 + 41),
                            2 * (4 + 4) + 2 * (256 + 256))),
    (512, [2, 299, 0, 40], (2 * (3 + 4) + 2 * (3 + 41),
                            2 * (4 + 4) + 2 * (512 + 512))),
])
def test_decode_step_records_its_cache_columns(attend_len, positions,
                                               want):
    """``serving/decode/kv``: the columns the live slots attend (``c``,
    ``min(c, window)`` in a ring) and the same in whole tiles of the
    kernel's own tile function, per layer; nothing with the tracer
    off."""
    from types import SimpleNamespace

    from bigdl_tpu.generation.engine import _record_kv
    from bigdl_tpu.generation.kv_cache import KVCache
    from bigdl_tpu.kernels.ragged_decode import block_columns, kv_tile

    layout = [("kv", 2, 16, 4)] * 2 + [("kv", 2, 16, 512)] * 2
    kv = KVCache(4, 512, layout, dtype="float32")
    model = SimpleNamespace(num_heads=4)
    positions = np.asarray(positions, np.int32)
    active = np.array([True, False, False, True])
    assert kv_tile(block_columns(512, attend_len), 16, 2, 4) == attend_len
    telemetry.tracer().clear()
    _record_kv(model, kv, positions, active, attend_len)
    assert not telemetry.tracer().spans()
    telemetry.enable()
    try:
        _record_kv(model, kv, positions, active, attend_len, 3)
        (rec,) = [r for r in telemetry.tracer().spans()
                  if r.name == "serving/decode/kv"]
        assert (rec.args["valid_columns"],
                rec.args["fetched_columns"]) == want
        # two live slots, four layers; the kernel wrote in three
        assert (rec.args["written_columns"],
                rec.args["kernel_written_columns"]) == (8, 6)
    finally:
        telemetry.disable()
        telemetry.tracer().clear()


@pytest.mark.parametrize("config, share", [
    (kernels.KernelConfig.off(), 0.0),                 # _write_columns
    (kernels.KernelConfig(decode_attention=True), 1.0),    # the kernel
], ids=["fallback_wrote", "kernel_wrote"])
def test_decode_step_records_who_wrote_its_columns(config, share):
    """``serving/decode/kv``'s ``kernel_written_columns`` over
    ``written_columns``, as ``decode_kv_write_in_kernel_share.serve``
    reads it: 0 from a service whose decode program fell back to
    ``_write_columns`` in every layer, 1 from one whose decode kernel
    took the write - counted by the dispatch when each program was
    traced and kept by the engine beside the program."""
    telemetry.tracer().clear()
    telemetry.enable()
    try:
        with kernels.use(config):
            svc = _service()
            svc.generate("lm", [1, 2, 3], max_new_tokens=4).result(60)
            svc.shutdown()
        recs = [r.args for r in telemetry.tracer().spans()
                if r.name == "serving/decode/kv"]
        assert len(recs) >= 3
        for a in recs:
            assert a["written_columns"] == 2    # one live slot, 2 layers
            assert a["kernel_written_columns"] \
                == share * a["written_columns"]
    finally:
        telemetry.disable()
        telemetry.tracer().clear()


def test_unload_releases_generation_programs():
    svc = _service()
    try:
        assert svc.cache.compile_count() > 0
        svc.generate("lm", [1, 2], max_new_tokens=2).result(60)
        svc.unload("lm")
        assert svc.cache.compile_count() == 0, \
            "unload must release every compiled generation program"
        with pytest.raises(KeyError):
            svc.generate("lm", [1, 2])
    finally:
        svc.shutdown()


def test_prefill_failure_fails_admitted_streams_typed_not_hang():
    """Regression: a prefill that raises AFTER its requests were
    popped from the queue (admitted, slots allocated) must fail those
    streams typed — never strand them pending forever."""
    svc = _service()
    try:
        real_prefill = svc.engine.prefill
        boom = {"armed": True}

        def failing_prefill(*a, **kw):
            if boom.pop("armed", False):
                raise RuntimeError("injected prefill failure")
            return real_prefill(*a, **kw)

        svc.engine.prefill = failing_prefill
        s = svc.generate("lm", [1, 2, 3], max_new_tokens=3)
        with pytest.raises(WorkerDied):
            s.result(timeout=30)
        # the restarted loop serves the next request normally
        assert len(svc.generate("lm", [1, 2, 3],
                                max_new_tokens=3).result(60)) == 3
    finally:
        svc.shutdown()


def test_load_warmup_cache_is_adopted_by_the_serving_group():
    """The load-time warmup buffers ARE the serving cache — one
    full-size K/V allocation per version, not warmup + serving
    copies."""
    svc = _service()
    try:
        sv2 = svc.load("lm", _model(seed=9))  # v2, warmed + activated
        assert sv2.key in svc._warm_caches
        warmed = svc._warm_caches[sv2.key]
        svc.generate("lm", [1, 2], max_new_tokens=2).result(60)
        assert sv2.key not in svc._warm_caches  # handed to the loop
        assert warmed.allocator.free_count == warmed.slots  # and usable
    finally:
        svc.shutdown()


def test_shutdown_without_drain_fails_streams_typed():
    svc = _service(slots=1, prefill_rows=1)
    try:
        with faults.armed("serving/decode=delay:30,times:1000"):
            s = svc.generate("lm", [1, 2, 3], max_new_tokens=16)
            s.first(timeout=30)
            q = svc.generate("lm", [4, 5], max_new_tokens=4)
            svc.shutdown(drain=False)
            for stream in (s, q):
                with pytest.raises(RuntimeError):
                    stream.result(timeout=30)
    finally:
        svc.shutdown()


def test_shared_registry_with_inference_service():
    """GenerationService(svc) rides an InferenceService's registry:
    one load, scored AND generated."""
    from bigdl_tpu.serving import InferenceService, ServingConfig

    model = _model()
    inf = InferenceService(config=ServingConfig(max_batch_size=4))
    inf.registry.load("lm", model)
    gen = GenerationService(inf, config=GenerationConfig(
        slots=2, max_len=16, length_buckets=(16,), prefill_rows=1))
    try:
        out = gen.generate("lm", [3, 7, 1], max_new_tokens=3).result(60)
        assert list(out) == _greedy_reference(model, [3, 7, 1], 3)
        assert gen.registry is inf.registry
        assert gen.metrics_registry is inf.metrics_registry
    finally:
        gen.shutdown()
        inf.shutdown()


def test_full_sequence_path_unchanged_by_cache_support():
    """The no-cache forward is byte-identical before/after this PR's
    signature change: cache kwargs default to the legacy path."""
    import jax.numpy as jnp
    model = _model()
    params, state = model.get_parameters(), model.get_state()
    toks = jnp.asarray([[3, 7, 1, 4]])
    a, _ = model.apply(params, state, toks, training=False)
    b, _ = model.apply(params, state, toks, training=False,
                       cache=None, positions=None)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_online_generation_example():
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from examples.online_generation import main
    metrics = main(["--requests", "5", "--max-new", "6", "--slots", "2",
                    "--max-len", "32", "--buckets", "16,32"])
    assert metrics["finished"] >= 7  # burst + sampled + swap checks
    assert metrics["compile_count"] <= 2 * 2 * 2  # 2K per version
