"""Streaming data plane (bigdl_tpu.datapipe): shard/cursor resume
round-trips, seeded windowed-shuffle determinism, sequence-packing
correctness (segment masks BIT-EXACT vs per-sequence unpacked
forwards), K=1 vs K=8 windowed equivalence through a streaming source,
and the prefetch-abandonment no-leak regression over staged pipelines."""
import os
import threading
import time

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import datapipe as dp
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.models import TransformerLM
from bigdl_tpu.optim import SGD, LocalOptimizer, max_iteration
from bigdl_tpu.optim.trigger import several_iteration
from bigdl_tpu.utils.random import RandomGenerator


# ------------------------------------------------------------- helpers

def _write_shards(tmp_path, n_shards=3, lines_per=5):
    paths = []
    for s in range(n_shards):
        p = tmp_path / f"shard-{s}.txt"
        p.write_text("".join(f"s{s}r{i}\n" for i in range(lines_per)))
        paths.append(str(p))
    return paths


def _docs(n=40, lo=4, hi=24, vocab=50, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _tiny_lm(vocab=50, seed=3):
    RandomGenerator.set_seed(seed)
    m = TransformerLM(vocab_size=vocab, hidden_size=16, num_layers=2,
                      num_heads=2, max_len=64).evaluate()
    m.ensure_initialized()
    return m


# ------------------------------------------------- readers & cursors

def test_text_reader_streams_all_shards(tmp_path):
    r = dp.TextLineReader(_write_shards(tmp_path), shuffle_shards=False)
    got = list(r.read_epoch())
    assert got == [f"s{s}r{i}" for s in range(3) for i in range(5)]
    assert r.epoch == 1  # cursor advanced to the next epoch


def test_reader_cursor_resume_roundtrip(tmp_path):
    paths = _write_shards(tmp_path, n_shards=4, lines_per=7)
    ref = dp.TextLineReader(paths, seed=11)
    stream = ref.read(loop=True)
    head = [next(stream) for _ in range(9)]  # partway into some shard
    snap = ref.state()
    want = [next(stream) for _ in range(30)]  # crosses an epoch boundary

    fresh = dp.TextLineReader(paths, seed=11).restore(snap)
    it = fresh.read(loop=True)
    got = [next(it) for _ in range(30)]
    assert got == want
    assert len(set(head)) == 9


def test_reader_state_is_json_plain(tmp_path):
    import json
    r = dp.TextLineReader(_write_shards(tmp_path))
    next(r.read(loop=True))
    assert json.loads(json.dumps(r.state())) == r.state()


def test_reader_epoch_shard_order_reshuffles_deterministically(tmp_path):
    paths = _write_shards(tmp_path, n_shards=6, lines_per=1)
    a = dp.TextLineReader(paths, seed=5)
    e0 = list(a.read_epoch())
    e1 = list(a.read_epoch())
    assert sorted(e0) == sorted(e1)
    assert e0 != e1  # per-epoch shard-order permutation
    b = dp.TextLineReader(paths, seed=5)
    assert list(b.read_epoch()) == e0  # seeded: replayable
    assert list(b.read_epoch()) == e1


def test_reader_multihost_shard_split(tmp_path):
    paths = _write_shards(tmp_path, n_shards=4, lines_per=3)
    parts = [
        set(dp.TextLineReader(paths, process_index=i, process_count=2,
                              shuffle_shards=False).read_epoch())
        for i in range(2)]
    assert parts[0] | parts[1] == \
        {f"s{s}r{i}" for s in range(4) for i in range(3)}
    assert not parts[0] & parts[1]


def test_array_reader_counts_and_samples():
    feats = np.arange(20, dtype=np.float32).reshape(10, 2)
    labels = np.arange(10, dtype=np.float32)
    r = dp.ArrayRecordReader(feats, labels, shard_size=3,
                             shuffle_shards=False)
    assert r.num_records() == 10
    recs = list(r.read_epoch())
    assert len(recs) == 10
    np.testing.assert_array_equal(recs[4].feature(), feats[4])
    assert recs[4].label() == labels[4]


def test_datapipe_read_faultpoint_fires(tmp_path):
    from bigdl_tpu import faults
    r = dp.TextLineReader(_write_shards(tmp_path, 1, 5),
                          shuffle_shards=False)
    faults.arm(faults.parse_schedule("datapipe/read=nth:3,raise:OSError"))
    try:
        with pytest.raises(OSError):
            list(r.read_epoch())
    finally:
        faults.disarm()


# ------------------------------------------------- windowed shuffle

def test_shuffle_seeded_determinism():
    recs = list(range(200))
    a = list(dp.WindowShuffle(32, seed=7)(iter(recs), epoch=0))
    b = list(dp.WindowShuffle(32, seed=7)(iter(recs), epoch=0))
    c = list(dp.WindowShuffle(32, seed=8)(iter(recs), epoch=0))
    assert a == b                       # same seed: bit-identical order
    assert sorted(a) == recs            # a true permutation
    assert a != c                       # different seed: different order
    assert a != recs                    # actually shuffled


def test_shuffle_reseeds_per_epoch():
    recs = list(range(100))
    st = dp.WindowShuffle(25, seed=3)
    e0 = list(st(iter(recs), epoch=0))
    e1 = list(st(iter(recs), epoch=1))
    assert e0 != e1
    # epoch N is reproducible WITHOUT replaying earlier epochs
    assert list(dp.WindowShuffle(25, seed=3)(iter(recs), epoch=1)) == e1


def test_shuffle_bounded_displacement():
    # a record can only move ~buffer_size forward: streaming, not global
    buf = 10
    out = list(dp.WindowShuffle(buf, seed=1)(iter(range(1000)), epoch=0))
    for pos, v in enumerate(out):
        assert pos >= v - buf


# ---------------------------------------------------------- packing

def test_pack_documents_layout_and_targets():
    docs = [np.arange(1, 6, dtype=np.int32),      # x len 4
            np.arange(10, 14, dtype=np.int32),    # x len 3
            np.arange(20, 30, dtype=np.int32)]    # x len 9
    toks, segs, pos, tgt = dp.pack_documents(docs, 8)
    assert toks.shape == segs.shape == pos.shape == tgt.shape
    assert toks.shape[1] == 8
    # doc 1: x = [1..4], y = [2..5], segment 1, positions 0..3
    np.testing.assert_array_equal(toks[0, :4], [1, 2, 3, 4])
    np.testing.assert_array_equal(tgt[0, :4], [2, 3, 4, 5])
    np.testing.assert_array_equal(segs[0, :4], [1, 1, 1, 1])
    np.testing.assert_array_equal(pos[0, :4], [0, 1, 2, 3])
    # doc 2 packs into the same row, new segment id, positions reset
    np.testing.assert_array_equal(toks[0, 4:7], [10, 11, 12])
    np.testing.assert_array_equal(segs[0, 4:7], [2, 2, 2])
    np.testing.assert_array_equal(pos[0, 4:7], [0, 1, 2])
    # pad slot: segment 0, target ignored
    assert segs[0, 7] == 0 and tgt[0, 7] == -1
    # no target ever crosses a document boundary
    for r in range(len(toks)):
        for j in range(8):
            if tgt[r, j] != -1:
                assert segs[r, j] != 0


def test_padding_efficiency_math():
    assert dp.padding_efficiency([4, 8], 8) == pytest.approx(0.75)
    assert dp.padding_efficiency([], 8) == 1.0
    # PTB-like regime: short ragged documents, a long slab — packing
    # must clear 0.9 where pad-to-max wastes most of the batch
    docs = _docs(300, seed=2)
    lens = [len(d) - 1 for d in docs]
    toks, segs, _, _ = dp.pack_documents(docs, 128)
    packed_eff = float((segs > 0).mean())
    assert packed_eff > 0.9 > dp.padding_efficiency(lens, 128)


# float32 reduction order differs between programs of different shapes
# (a document alone in a [1, L] or padded [1, S] row against the same
# document inside a packed slab): observed 1e-7 to 6e-7 on logits of
# order 1. A leak across documents moves them by more than 1e-4 (the
# control below), so this tolerance still tells the two apart.
SHAPE_TOL = dict(rtol=1e-5, atol=1e-6)


def test_packed_forward_agrees_with_unpacked():
    """THE segment-mask correctness assert: every document's logits in
    a packed slab agree with running that document alone (to float32
    reduction order, ``SHAPE_TOL``) — both as a padded row (same slab
    width) and as an unpadded [1, L] forward. Any cross-document
    attention leak, positional-embedding offset, or mask slip moves
    them by orders of magnitude more."""
    m = _tiny_lm()
    p, st = m.get_parameters(), m.get_state()
    docs = _docs(7, lo=4, hi=10, seed=1)
    S = 16
    toks, segs, pos, _ = dp.pack_documents(docs, S)
    packed = np.asarray(m.apply(p, st, [toks, segs, pos],
                                training=False)[0])
    # walk the slabs segment by segment and compare per document
    checked = 0
    for r in range(len(toks)):
        for sid in range(1, int(segs[r].max()) + 1):
            at = np.flatnonzero(segs[r] == sid)
            x = toks[r, at]
            # padded single-document row (same width S)
            t0 = np.zeros((1, S), np.int32)
            s0 = np.zeros((1, S), np.int32)
            p0 = np.zeros((1, S), np.int32)
            n = len(at)
            t0[0, :n], s0[0, :n] = x, 1
            p0[0, :n] = np.arange(n)
            ref = np.asarray(m.apply(p, st, [t0, s0, p0],
                                     training=False)[0])
            np.testing.assert_allclose(packed[r, at], ref[0, :n],
                                       **SHAPE_TOL)
            # truly unpacked [1, L] forward
            ref2 = np.asarray(m.apply(p, st, x[None].astype(np.int32),
                                      training=False)[0])
            np.testing.assert_allclose(packed[r, at], ref2[0],
                                       **SHAPE_TOL)
            checked += 1
    assert checked >= 7


def test_packed_forward_differs_without_segment_mask():
    """Control for the agreement assert: the SAME packed tokens with a
    single all-ones segment plane (mask off) must NOT reproduce the
    per-document forwards — otherwise the exactness test proves
    nothing."""
    m = _tiny_lm()
    p, st = m.get_parameters(), m.get_state()
    docs = _docs(6, lo=6, hi=10, seed=4)
    toks, segs, pos, _ = dp.pack_documents(docs, 16)
    masked = np.asarray(m.apply(p, st, [toks, segs, pos],
                                training=False)[0])
    unmasked = np.asarray(m.apply(
        p, st, [toks, np.ones_like(segs), pos], training=False)[0])
    # second-and-later segments see forged history without the mask
    later = segs > 1
    assert later.any()
    assert not np.allclose(masked[later], unmasked[later], atol=1e-4)


def test_bucket_batcher_layout_and_efficiency():
    docs = [np.arange(1, 5, dtype=np.int32),     # x len 3 -> bucket 4
            np.arange(1, 10, dtype=np.int32),    # x len 8 -> bucket 8
            np.arange(1, 4, dtype=np.int32),     # x len 2 -> bucket 4
            np.arange(1, 30, dtype=np.int32)]    # x len 8 (truncated)
    b = dp.LengthBucketBatcher([4, 8], batch_size=2)
    out = list(b(iter(docs), epoch=0))
    assert len(out) == 2
    widths = sorted(mb.input[0].shape[1] for mb in out)
    assert widths == [4, 8]
    for mb in out:
        toks, segs, pos = mb.input
        assert mb.target.shape == toks.shape
        assert ((segs == 0) == (mb.target == -1)).all()
    assert 0 < b.efficiency <= 1.0


def test_criterion_ignore_index_masks_positions():
    import jax.numpy as jnp
    crit = nn.SequenceCrossEntropyCriterion(ignore_index=-1)
    ref = nn.SequenceCrossEntropyCriterion()
    logits = np.random.RandomState(0).randn(2, 4, 7).astype(np.float32)
    t_full = np.array([[1, 2, 3, 4], [5, 6, 0, 1]], np.int32)
    # masking the second row's tail == scoring only the kept positions
    t_mask = t_full.copy()
    t_mask[1, 2:] = -1
    got = float(crit.apply(jnp.asarray(logits), jnp.asarray(t_mask)))
    kept = np.concatenate([logits[0], logits[1, :2]])[None]
    want = float(ref.apply(jnp.asarray(kept),
                           jnp.asarray(np.concatenate(
                               [t_full[0], t_full[1, :2]])[None])))
    assert got == pytest.approx(want, rel=1e-6)


# ------------------------------------------------- pipeline plumbing

def _token_pipeline(seed=7, n=60, vocab=50):
    docs = _docs(n, vocab=vocab, seed=9)

    class DocReader(dp.ShardedReader):
        def _open(self, shard):
            lo, hi = shard
            return iter(docs[lo:hi])

        def _shard_len(self, shard):
            return shard[1] - shard[0]

    shards = [(i, min(i + 10, n)) for i in range(0, n, 10)]
    return dp.Pipeline(DocReader(shards, seed=seed)) \
        .shuffle(buffer_size=16, seed=seed).pack(seq_len=32, batch_rows=4)


def test_pipeline_stream_bit_identical_across_runs():
    a = [mb for _, mb in zip(range(8), _token_pipeline().iterate(True))]
    b = [mb for _, mb in zip(range(8), _token_pipeline().iterate(True))]
    for x, y in zip(a, b):
        for pa, pb in zip(x.input, y.input):
            np.testing.assert_array_equal(pa, pb)
        np.testing.assert_array_equal(x.target, y.target)


def test_pipeline_as_dataset_counts_rows():
    pipe = _token_pipeline()
    ds = pipe.as_dataset(batch_size=4)
    n = sum(mb.size() for mb in _token_pipeline().iterate(False))
    assert ds.size() == n
    assert ds.batch_size == 4
    assert ds.continuous_stream


def test_pipeline_state_roundtrip_restores_stream():
    pipe = _token_pipeline()
    it = pipe.iterate(loop=True)
    for _ in range(3):
        next(it)
    snap = pipe.state()
    # NOTE the contract: restore rewinds to the READER cursor, i.e. the
    # epoch position after the last fully-consumed epoch batch; at
    # epoch boundaries this is exact
    fresh = _token_pipeline().restore(snap)
    assert fresh.state() == snap


def test_staged_windows_layout():
    pipe = _token_pipeline()
    it = pipe.staged(k=2, loop=True)
    try:
        mb = next(it)
        toks = np.asarray(mb.input[0])
        assert toks.shape[:2] == (2, 4)  # [K, B, S]
        assert np.asarray(mb.target).shape[:2] == (2, 4)
    finally:
        it.close()


def test_staged_pipeline_abandonment_leaks_no_threads():
    """PR-4 regression, re-aimed at the datapipe: abandoning a staged
    pipeline mid-epoch must stop the prefetch stager (stop event ->
    drain -> join), not leave a daemon parked on a full queue."""
    before = set(threading.enumerate())
    it = _token_pipeline().staged(k=2, loop=True)
    next(it)
    time.sleep(0.2)  # let the stager park on a full queue
    it.close()
    deadline = time.time() + 5.0
    leaked = set()
    while time.time() < deadline:
        leaked = set(threading.enumerate()) - before
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"stager thread leaked: {leaked}"


# ------------------------------- optimizer integration & K-equivalence

def _sample_pipeline(seed, n=96, batch=16):
    rng = np.random.RandomState(41)
    X = rng.randn(n, 8).astype(np.float32)
    y = (np.arange(n) % 3 + 1).astype(np.float32)
    return dp.Pipeline(dp.ArrayRecordReader(X, y, shard_size=24,
                                            seed=seed)) \
        .shuffle(buffer_size=32, seed=seed) \
        .batch(batch, drop_remainder=True)


def _mlp():
    return nn.Sequential().add(nn.Linear(8, 16)).add(nn.Tanh()) \
        .add(nn.Linear(16, 3)).add(nn.LogSoftMax())


def _run_stream_opt(k, iters=12, checkpoint=None, trigger=None):
    RandomGenerator.set_seed(17)
    ds = _sample_pipeline(seed=5).as_dataset(batch_size=16)
    opt = LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion(),
                         batch_size=16)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(iters))
    opt.set_steps_per_sync(k)
    if checkpoint:
        opt.set_checkpoint(checkpoint, trigger or several_iteration(4))
    model = opt.optimize()
    import jax
    params = [np.asarray(l) for l in
              jax.tree_util.tree_leaves(model.get_parameters())]
    return params, opt


@pytest.mark.parametrize("k", [8])
def test_streaming_source_k1_vs_k8_equivalence(k):
    """The windowed-equivalence harness over the STREAMING source: the
    pipeline's seeded shuffle + cursor make the batch stream identical
    whatever K, so fused windows and per-step sync converge to the
    same params (the PR-4 guarantee extended through the data plane)."""
    p1, o1 = _run_stream_opt(1)
    pk, ok = _run_stream_opt(k)
    assert o1.driver_state["neval"] == ok.driver_state["neval"]
    assert o1.driver_state["epoch"] == ok.driver_state["epoch"]
    for a, b in zip(p1, pk):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_optimizer_checkpoints_and_restores_pipeline_cursor(tmp_path):
    import json
    ck = str(tmp_path / "ck")
    _, opt = _run_stream_opt(1, iters=9, checkpoint=ck)
    latest = None
    from bigdl_tpu.utils.serialization import find_latest_checkpoint
    latest = find_latest_checkpoint(ck)
    assert latest is not None
    with open(os.path.join(latest, "host_state.json")) as f:
        host = json.load(f)
    cursor = host["driver_state"].get("datapipe")
    assert cursor is not None
    assert set(cursor) == {"epoch", "spos", "offset"}

    # a fresh optimizer resuming from this checkpoint must restore the
    # cursor into its OWN pipeline before building the data iterator
    RandomGenerator.set_seed(17)
    ds2 = _sample_pipeline(seed=5).as_dataset(batch_size=16)
    opt2 = LocalOptimizer(_mlp(), ds2, nn.ClassNLLCriterion(),
                          batch_size=16)
    opt2.set_optim_method(SGD(learning_rate=0.1))
    opt2.set_end_when(max_iteration(10))
    opt2.set_checkpoint(ck, several_iteration(100))
    opt2.optimize()
    assert opt2.driver_state["neval"] == 11  # resumed, not restarted
    assert "datapipe" not in opt2.driver_state
    assert ds2.pipeline_state() != {"epoch": 0, "spos": 0, "offset": 0}


def test_as_dataset_uses_cheap_count_for_count_preserving_stages():
    rng = np.random.RandomState(1)
    X = rng.randn(30, 4).astype(np.float32)
    y = np.ones(30, np.float32)
    pipe = dp.Pipeline(dp.ArrayRecordReader(X, y, shard_size=10)) \
        .map(lambda s: s).shuffle(buffer_size=8, seed=1)
    # map/shuffle preserve cardinality: the reader's num_records() must
    # answer without a cold epoch scan
    pipe.count_epoch_records = None  # a scan would now TypeError
    ds = pipe.as_dataset()
    assert ds.size() == 30


def test_eval_iteration_is_repeatable_and_cursor_free():
    """data(train=False) must honor the AbstractDataSet eval contract:
    identical stream on every call, and NO side effect on the training
    cursor (a validation trigger mid-training must not eat an epoch)."""
    pipe = _sample_pipeline(seed=5)
    ds = pipe.as_dataset(batch_size=16)
    before = ds.pipeline_state()
    a = [np.asarray(mb.input) for mb in ds.data(train=False)]
    b = [np.asarray(mb.input) for mb in ds.data(train=False)]
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert ds.pipeline_state() == before


def test_as_dataset_batch_stage_uses_cheap_count():
    rng = np.random.RandomState(1)
    X = rng.randn(30, 4).astype(np.float32)
    y = np.ones(30, np.float32)
    pipe = dp.Pipeline(dp.ArrayRecordReader(X, y, shard_size=10)) \
        .shuffle(buffer_size=8, seed=1).batch(7)  # non-dropping
    pipe.count_epoch_records = None  # a scan would now TypeError
    assert pipe.as_dataset().size() == 30


def test_transformed_pipeline_dataset_still_checkpoints_cursor(tmp_path):
    """`pipe.as_dataset().transform(...)` must not silently lose cursor
    checkpointing: the optimizer walks the wrapper's .base chain."""
    import json
    from bigdl_tpu.dataset.transformer import Lambda
    from bigdl_tpu.utils.serialization import find_latest_checkpoint
    ck = str(tmp_path / "ck")
    RandomGenerator.set_seed(17)
    inner = _sample_pipeline(seed=5).as_dataset(batch_size=16)
    wrapped = inner.transform(Lambda(lambda mb: mb))
    opt = LocalOptimizer(_mlp(), wrapped, nn.ClassNLLCriterion(),
                         batch_size=16)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(6))
    opt.set_checkpoint(ck, several_iteration(3))
    opt.optimize()
    latest = find_latest_checkpoint(ck)
    with open(os.path.join(latest, "host_state.json")) as f:
        host = json.load(f)
    assert host["driver_state"].get("datapipe") is not None


# ------------------------------------------------------------ telemetry

def test_padding_efficiency_gauge_lands_in_registry():
    import bigdl_tpu.telemetry as telemetry
    docs = _docs(30, seed=6)
    dp.pack_documents(docs, 32)
    snap = telemetry.registry().snapshot()
    names = {row["name"] for row in snap}
    assert "data/packing/padding_efficiency" in names
    row = next(r for r in snap
               if r["name"] == "data/packing/padding_efficiency")
    assert 0.5 < row["series"][0]["value"] <= 1.0


def test_diagnose_feed_summary_ingests_datapipe_gauges():
    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu.tools.diagnose import feed_summary
    docs = _docs(30, seed=6)
    dp.pack_documents(docs, 32)
    list(dp.WindowShuffle(8, seed=1)(iter(range(20)), epoch=0))
    feed = feed_summary(telemetry.registry().snapshot())
    assert "padding_efficiency" in feed
    assert "shuffle_buffer_depth" in feed
