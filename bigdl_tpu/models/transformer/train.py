"""TransformerLM flagship training recipe — the one-call surface for
every parallelism the framework has (the role DistriOptimizer.scala:728
played for the reference: parallel training behind Optimizer.optimize()).

Parallelism is CONFIG, not code:

    # single chip
    python -m bigdl_tpu.models.transformer.train --synthetic 20000 -e 1
    # 2-way pipeline x 2-way tensor x data parallel on the rest
    python -m bigdl_tpu.models.transformer.train --synthetic 20000 \
        --pp 2 --tp 2
    # ring-attention sequence parallelism for long context
    python -m bigdl_tpu.models.transformer.train --synthetic 20000 \
        --sp ring --spSize 4 --seqLen 2048
    # Ulysses all-to-all SP instead of ring
    python -m bigdl_tpu.models.transformer.train ... --sp ulysses
    # the full product: pipeline x tensor x sequence x expert x data
    python -m bigdl_tpu.models.transformer.train --synthetic 20000 \
        --pp 2 --tp 2 --sp ring --spSize 2 --moeExperts 4

Corpus input mirrors the RNN recipe (models/rnn/Train.scala:60-133):
``-f dir`` reads ``train.txt`` through the PTB tokenizer/Dictionary.
"""
from __future__ import annotations

import os


def build_mesh_for(pp: int, tp: int, sp_size: int):
    """Carve the available devices into (data[, pipe][, model][, seq]).

    Data parallelism absorbs whatever is left: dp = n // (pp*tp*sp).
    Returns (mesh, axes_present) — mesh is None on a single device with
    no parallelism requested.
    """
    import jax

    from bigdl_tpu.parallel import make_mesh

    n = len(jax.devices())
    need = pp * tp * sp_size
    if n % need:
        raise ValueError(
            f"device count {n} not divisible by pp*tp*spSize={need}")
    dp = n // need
    sizes, names = [dp], ["data"]
    if pp > 1:
        sizes.append(pp)
        names.append("pipe")
    if tp > 1:
        sizes.append(tp)
        names.append("model")
    if sp_size > 1:
        sizes.append(sp_size)
        names.append("seq")
    if sizes == [1]:
        return None, names
    return make_mesh(sizes, names, jax.devices()[:n]), names


def _split_documents(stream, eos_index):
    """1-based token stream -> list of 0-based int32 documents split at
    ``eos_index`` (each document keeps its trailing <eos>) — the
    variable-length view the packing/bucketing input modes consume."""
    import numpy as np

    s = np.asarray(stream).astype(np.int64)
    docs, lo = [], 0
    ends = np.flatnonzero(s == eos_index)
    for e in ends:
        doc = s[lo:e + 1]
        if len(doc) >= 2:
            docs.append((doc - 1).astype(np.int32))
        lo = e + 1
    tail = s[lo:]
    if len(tail) >= 2:
        docs.append((tail - 1).astype(np.int32))
    return docs


def _packed_corpus(args, stream, eos_index):
    """The packing-path replacement for the contiguous ``ptb_arrays``
    layout: documents packed into ``[rows, seqLen]`` slabs with segment
    masks (``--inputMode packed``) or padded one-per-row to the seqLen
    bound (``--inputMode padded``). Prints the padding efficiency both
    layouts would achieve, and leaves the gauge at the chosen one."""
    from bigdl_tpu import datapipe as dp

    docs = _split_documents(stream, eos_index)
    if not docs:
        raise SystemExit("corpus has no documents after <eos> splitting")
    lengths = [min(len(d) - 1, args.seqLen) for d in docs]
    eff_padded = dp.padding_efficiency(lengths, args.seqLen)
    if args.inputMode == "padded":
        batcher = dp.LengthBucketBatcher([args.seqLen], len(docs))
        (mb,) = list(batcher(iter(docs), 0))
        toks, segs, pos = mb.input
        tgt = mb.target
        eff = batcher.efficiency
    else:
        toks, segs, pos, tgt = dp.pack_documents(docs, args.seqLen)
        eff = float((segs > 0).mean())
    print(f"input mode {args.inputMode}: padding_efficiency {eff:.3f} "
          f"(pad-to-max would be {eff_padded:.3f}) over {len(docs)} "
          f"documents, {len(toks)} rows of {args.seqLen}")
    return [toks, segs, pos], tgt


def _corpus(args):
    """(x, y) int32 0-based token windows [N, seqLen] + vocab size."""
    import numpy as np

    from bigdl_tpu.dataset import load_ptb, ptb_arrays

    if args.synthetic:
        rng = np.random.RandomState(0)
        stream = rng.randint(1, args.vocabSize + 1,
                             args.synthetic).astype(np.float32)
        vocab = args.vocabSize
        if args.inputMode != "contiguous":
            # ragged synthetic documents: mark seeded pseudo-<eos>
            # boundaries so the packed path has real length variance
            eos = args.vocabSize
            cuts = rng.randint(8, max(9, args.seqLen // 2),
                               max(1, args.synthetic // 16))
            pos = np.minimum(np.cumsum(cuts), args.synthetic - 1)
            stream[pos] = eos
            return _packed_corpus(args, stream, eos) + (vocab,)
    else:
        train_txt = args.folder if os.path.isfile(args.folder) else \
            os.path.join(args.folder, "train.txt")
        if not os.path.exists(train_txt):
            from bigdl_tpu.dataset import fetch
            try:
                train_txt = fetch.get_text_corpus(args.folder)
            except Exception as e:
                raise SystemExit(
                    f"no corpus at '{train_txt}' and auto-download "
                    f"failed ({type(e).__name__}: {e}). Pre-stage a "
                    "train.txt there, or use --synthetic N.")
        splits, d = load_ptb(train_txt, vocab_size=args.vocabSize)
        stream, vocab = splits["train"], d.vocab_size()
        if args.checkpoint:
            os.makedirs(args.checkpoint, exist_ok=True)
            d.save(os.path.join(args.checkpoint, "dictionary.json"))
        if args.inputMode != "contiguous":
            return _packed_corpus(args, stream,
                                  d.get_index("<eos>")) + (vocab,)
    bs = args.batchSize or 8
    x, y = ptb_arrays(stream, bs, args.seqLen)
    # ptb_arrays is 1-based (the torch convention); LM criterion wants
    # 0-based vocabulary ids
    return (x - 1).astype(np.int32), (y - 1).astype(np.int32), vocab


def main(argv=None):
    from bigdl_tpu.models._cli import (arrays_to_dataset, base_parser,
                                       load_model_or, wire_optimizer)

    ap = base_parser("Train the Transformer language model")
    ap.add_argument("--vocabSize", type=int, default=4000)
    ap.add_argument("--hiddenSize", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seqLen", type=int, default=128)
    ap.add_argument("--inputMode",
                    choices=("contiguous", "packed", "padded"),
                    default="contiguous",
                    help="text layout: 'contiguous' = the classic "
                    "ptb_arrays stream windows; 'packed' = documents "
                    "packed into [B, seqLen] slabs with segment masks "
                    "(datapipe.packing — no pad FLOPs); 'padded' = one "
                    "document per row padded to seqLen (the before "
                    "number for the padding-efficiency gauge)")
    ap.add_argument("--dropout", type=float, default=0.0)
    ap.add_argument("--moeExperts", type=int, default=0)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (PipelinedTransformerLM)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="pipeline microbatches (default: 2*pp)")
    ap.add_argument("--ppSchedule", choices=("gpipe", "interleaved"),
                    default="gpipe",
                    help="pipeline schedule (interleaved shrinks the "
                    "bubble by --ppRounds virtual stages)")
    ap.add_argument("--ppRounds", type=int, default=2,
                    help="virtual chunks per stage for interleaved")
    ap.add_argument("--tp", type=int, default=1,
                    help="megatron tensor-parallel degree")
    ap.add_argument("--sp", choices=("none", "ring", "ulysses"),
                    default="none", help="sequence parallelism kernel")
    ap.add_argument("--spSize", type=int, default=1,
                    help="sequence-parallel degree (mesh 'seq' axis)")
    args = ap.parse_args(argv)

    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import PipelinedTransformerLM, TransformerLM
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import Optimizer

    if args.sp != "none" and args.spSize < 2:
        args.spSize = 2
    if args.pp > 1 and args.dropout:
        raise ValueError(
            "--pp does not support dropout (per-microbatch rng through "
            "the pipeline ring would tie the objective to the stage "
            "count); use the non-pipelined TransformerLM for dropout")
    if args.inputMode != "contiguous" and (args.pp > 1
                                           or args.sp != "none"):
        raise ValueError(
            "--inputMode packed/padded needs the dense TransformerLM "
            "(segment masks are unsupported on the pipelined and "
            "sequence-parallel paths)")

    x, y, vocab = _corpus(args)
    bs = args.batchSize or 8
    if isinstance(x, list):
        # packed/padded 3-plane layout: Samples carry [tokens,
        # segment_ids, positions]; pad/boundary targets are -1 and the
        # criterion must ignore them
        from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
        samples = [Sample([plane[i] for plane in x], y[i])
                   for i in range(len(x[0]))]
        ds = DataSet.array(samples).transform(SampleToMiniBatch(bs))
        criterion = nn.SequenceCrossEntropyCriterion(ignore_index=-1)
    else:
        ds = arrays_to_dataset(x, y, bs)
        criterion = nn.SequenceCrossEntropyCriterion()

    mesh, _ = build_mesh_for(args.pp, args.tp,
                             args.spSize if args.sp != "none" else 1)
    rules = None
    if args.pp > 1:
        mb = args.microbatches or 2 * args.pp
        build = lambda: PipelinedTransformerLM(
            vocab, hidden_size=args.hiddenSize, num_layers=args.layers,
            num_heads=args.heads, max_len=args.seqLen,
            n_microbatches=mb, mesh=mesh,
            ring_axis="seq" if args.sp != "none" else None,
            sp_impl=args.sp if args.sp != "none" else "ring",
            moe_experts=args.moeExperts,
            pp_schedule=args.ppSchedule, pp_rounds=args.ppRounds)
        model = load_model_or(args, build)
        # snapshots strip the mesh (runtime placement, not identity) —
        # reattach or a resumed run would silently fall back to the
        # dense path while the CLI still promises --pp
        model.mesh = mesh
        rules = model.sharding_rules(
            model_axis="model" if args.tp > 1 else None,
            expert_axis="model" if (args.tp > 1 and args.moeExperts)
            else None)
    else:
        build = lambda: TransformerLM(
            vocab, hidden_size=args.hiddenSize, num_layers=args.layers,
            num_heads=args.heads, max_len=args.seqLen,
            dropout=args.dropout,
            ring_axis="seq" if args.sp != "none" else None,
            sp_impl=args.sp if args.sp != "none" else "ring",
            mesh=mesh, moe_experts=args.moeExperts)
        model = load_model_or(args, build)
        # snapshots strip runtime placement; SP lives in the attention
        # modules — reattach so a resumed run keeps its parallelism
        for blk in model.blocks:
            blk.attn.mesh = mesh
        if args.tp > 1:
            rules = model.sharding_rules(model_axis="model")

    optim = SGD(learning_rate=args.learningRate or 0.1,
                learning_rate_decay=args.learningRateDecay or 0.0)
    opt = Optimizer(model, ds, criterion,
                    batch_size=bs, mesh=mesh, sharding_rules=rules)
    wire_optimizer(opt, args, optim, default_epochs=1)
    opt.optimize()
    loss = opt.driver_state["Loss"]
    print(f"final loss: {loss:.4f} perplexity: {np.exp(loss):.2f}")
    return model


if __name__ == "__main__":
    from bigdl_tpu.utils.engine import enable_compile_cache

    enable_compile_cache()
    main()
