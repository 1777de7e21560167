"""On-chip convergence checks: zoo recipes on LEARNABLE synthetic tasks
(real corpora are absent offline, so these are the strongest accuracy
oracles the environment allows — far past 7-image fixture grade).

Image recipes (resnet / vgg / inception) — ten classes, each a fixed
random prototype; a sample is its class prototype under random
gain/shift/translation plus heavy pixel noise. Linearly inseparable in
pixel space (a linear probe plateaus ~60%), so high held-out accuracy
requires the conv stack to actually learn.

LM recipes (lstm / transformer) — a corpus sampled from a fixed sparse
first-order Markov chain (4 successors per state, Dirichlet weights).
The chain's conditional entropy gives a COMPUTABLE perplexity floor:
held-out per-token perplexity approaching exp(H) proves the model
learned the transition structure, not just unigram frequencies.

Each recipe runs its zoo pieces end to end on device: device-resident
data, build_train_step (the recipe's optimizer), jitted epoch scans,
held-out eval.

    python -m bigdl_tpu.tools.convergence resnet 20 20000
    python -m bigdl_tpu.tools.convergence vgg 20 20000
    python -m bigdl_tpu.tools.convergence inception 10 8192
    python -m bigdl_tpu.tools.convergence lstm 20 1000000
    python -m bigdl_tpu.tools.convergence transformer 20 1000000
"""
import json
import sys
import time

import numpy as np


# --------------------------------------------------------------- image task

# both oracle generators live in tools/synthetic (shared with perf,
# int8_sweep and the model recipes' --synthetic feeds); these aliases
# keep the historical convergence-CLI names importable
from bigdl_tpu.tools.synthetic import markov_corpus as make_markov_corpus  # noqa: E402,F401
from bigdl_tpu.tools.synthetic import prototype_image_dataset as make_dataset  # noqa: E402,F401


def run_image(name: str, build_model, optim, lr_for_epoch, epochs: int,
              n_train: int, batch: int, hw: int, pad: int,
              eval_batch: int = 256, criterion=None, eval_head=None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset.device_dataset import DeviceCachedArrayDataSet
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.random import RandomGenerator

    n_val = 2048 if hw <= 64 else 1024
    xs, ys = make_dataset(n_train, seed=0, hw=hw)
    xv, yv = make_dataset(n_val, seed=1, hw=hw)
    # large caches stage in bounded pieces, sized by the measured probe
    chunk = None
    if hw > 64:
        from bigdl_tpu.utils.transfer import probe_device_put_chunk
        chunk = probe_device_put_chunk()

    RandomGenerator.set_seed(1)
    model = build_model().training()
    model.ensure_initialized()
    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    # the recipe's own pairing: raw-logit models use CE, LogSoftMax
    # heads (inception) use ClassNLL — CE on log-probs barely
    # propagates gradient (measured: loss pinned at ln(10))
    step = build_train_step(
        model, criterion or nn.CrossEntropyCriterion(), optim)

    mean, std = (128.0,) * 3, (64.0,) * 3
    ds = DeviceCachedArrayDataSet(xs, ys, batch, crop=(hw, hw), pad=pad,
                                  flip=False, mean=mean, std=std,
                                  put_chunk_bytes=chunk)
    ev = DeviceCachedArrayDataSet(xv, yv, eval_batch, crop=(hw, hw),
                                  flip=False, mean=mean, std=std,
                                  put_chunk_bytes=chunk)

    steps_per_epoch = max(1, n_train // batch)

    # the caches ride as ARGUMENTS, never jit-closure constants: a
    # closed-over cache would be baked into the program as a
    # multi-hundred-MB constant, and arguments are the Optimizer's own
    # contract for device feeds
    def body(images, labels, carry, key):
        params, opt_state, mstate, ep, pos, lr = carry
        kb, kr = jax.random.split(key)
        x, y = ds.batch_fn_on(images, labels, kb, epoch=ep, pos=pos)
        params, opt_state, mstate, loss = step(
            params, opt_state, mstate, kr, lr, x, y)
        pos = pos + batch
        ep = ep + pos // ds.n
        pos = pos % ds.n
        return (params, opt_state, mstate, ep, pos, lr), loss

    @jax.jit
    def run_epoch(carry, keys, images, labels):
        return lax.scan(lambda c, k: body(images, labels, c, k),
                        carry, keys)

    @jax.jit
    def eval_acc(params, mstate, images, labels):
        def one(start):
            x, y = ev.eval_batch_fn_on(images, labels, start)
            out, _ = model.apply(params, mstate, x, training=False)
            if eval_head is not None:  # multi-head: score the main head
                out = eval_head(out)
            return (jnp.argmax(out, -1) + 1 == y).mean()
        starts = jnp.arange(0, ev.n, eval_batch)
        return jax.vmap(one)(starts).mean()

    root = jax.random.PRNGKey(0)
    carry = (params, opt_state, mstate, jnp.int32(0), jnp.int32(0),
             jnp.float32(lr_for_epoch(1)))
    t0 = time.time()
    history = []
    for e in range(epochs):
        carry = carry[:5] + (jnp.float32(lr_for_epoch(e + 1)),)
        keys = jax.random.split(jax.random.fold_in(root, e),
                                steps_per_epoch)
        carry, losses = run_epoch(carry, keys, ds.images, ds.labels)
        # sanctioned window boundary: the epoch is one fused scan
        # dispatch; this is the once-per-epoch sync, not per-step
        acc = float(eval_acc(carry[0], carry[2], ev.images, ev.labels))  # bigdl: disable=sync-in-loop
        history.append(round(acc, 4))
        print(f"epoch {e + 1}: loss={float(losses.mean()):.4f} "  # bigdl: disable=sync-in-loop
              f"val_acc={acc:.4f}", flush=True)
    dt = time.time() - t0
    result = {"recipe": name, "final_val_acc": history[-1],
              "best_val_acc": max(history), "epochs": epochs,
              "n_train": n_train,
              "imgs_per_sec": round(
                  epochs * steps_per_epoch * batch / dt, 1),
              "history": history}
    print(json.dumps(result))
    return result


# ------------------------------------------------------------------ LM task

def run_lm(name: str, build_model, criterion, optim, lr: float,
           epochs: int, n_tokens: int, seq: int = 32, batch: int = 256,
           one_based: bool = False, vocab: int = 256,
           aux_loss_weight: float = 0.01, report_experts: bool = False,
           gradient_clip=None):
    """Shared LM convergence loop: device-resident token windows, jitted
    epoch scans, held-out per-token perplexity vs the chain's floor."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.utils.random import RandomGenerator

    toks, floor = make_markov_corpus(n_tokens, seed=0, vocab=vocab)
    vtoks, _ = make_markov_corpus(max(65536, seq * 2048), seed=1,
                                  vocab=vocab)

    def windows(stream):
        n_win = (len(stream) - 1) // seq
        x = stream[:n_win * seq].reshape(n_win, seq)
        y = stream[1:n_win * seq + 1].reshape(n_win, seq)
        off = 1 if one_based else 0
        return (jnp.asarray(x + off, jnp.int32),
                jnp.asarray(y + off, jnp.int32))

    xw, yw = windows(toks)
    xv, yv = windows(vtoks)
    n_win = xw.shape[0]
    nv = (xv.shape[0] // batch) * batch
    xv, yv = xv[:nv], yv[:nv]

    RandomGenerator.set_seed(1)
    model = build_model().training()
    model.ensure_initialized()
    params = model.get_parameters()
    mstate = model.get_state()
    opt_state = optim.init_state(params)
    step = build_train_step(model, criterion, optim,
                            aux_loss_weight=aux_loss_weight,
                            gradient_clip=gradient_clip)

    steps_per_epoch = max(1, n_win // batch)

    def body(carry, key):
        params, opt_state, mstate = carry
        kb, kr = jax.random.split(key)
        idx = jax.random.randint(kb, (batch,), 0, n_win)
        params, opt_state, mstate, loss = step(
            params, opt_state, mstate, kr, lr,
            jnp.take(xw, idx, 0), jnp.take(yw, idx, 0))
        return (params, opt_state, mstate), loss

    @jax.jit
    def run_epoch(carry, keys):
        return lax.scan(body, carry, keys)

    @jax.jit
    def eval_nll(params, mstate):
        def one(i):
            x = lax.dynamic_slice_in_dim(xv, i * batch, batch)
            y = lax.dynamic_slice_in_dim(yv, i * batch, batch)
            out, _ = model.apply(params, mstate, x, training=False)
            logp = jax.nn.log_softmax(out, axis=-1)
            tgt = (y - 1) if one_based else y
            nll = -jnp.take_along_axis(
                logp, tgt[..., None], axis=-1, mode="clip")[..., 0]
            return nll.mean()
        return jax.vmap(one)(jnp.arange(nv // batch)).mean()

    root = jax.random.PRNGKey(0)
    carry = (params, opt_state, mstate)
    t0 = time.time()
    history = []
    for e in range(epochs):
        keys = jax.random.split(jax.random.fold_in(root, e),
                                steps_per_epoch)
        carry, losses = run_epoch(carry, keys)
        # sanctioned window boundary: one sync per scanned epoch
        ppl = float(jnp.exp(eval_nll(carry[0], carry[2])))  # bigdl: disable=sync-in-loop
        history.append(round(ppl, 3))
        print(f"epoch {e + 1}: loss={float(losses.mean()):.4f} "  # bigdl: disable=sync-in-loop
              f"val_ppl={ppl:.3f} (floor {floor:.3f})", flush=True)
    dt = time.time() - t0
    result = {"recipe": name, "final_val_ppl": history[-1],
              "best_val_ppl": min(history), "ppl_floor": round(floor, 3),
              "epochs": epochs, "n_tokens": n_tokens,
              "aux_loss_weight": aux_loss_weight,
              "tokens_per_sec": round(
                  epochs * steps_per_epoch * batch * seq / dt, 1),
              "history": history}
    if report_experts:
        # per-MoE-block top-1 routing fractions over one held-out batch
        @jax.jit
        def route(params, mstate):
            _, st = model.apply(params, mstate, xv[:batch],
                                training=False)
            return st
        st = route(carry[0], carry[2])
        fracs = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(st)
        for path, leaf in flat:
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            if key.endswith("expert_frac"):
                fracs[key.split("/")[0]] = [round(float(v), 3)
                                            for v in np.asarray(leaf)]
        result["expert_utilization"] = fracs
    print(json.dumps(result))
    return result


# ---------------------------------------------------------------- recipes

def run_recipe(recipe: str, epochs: int, n: int):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import Adam, EpochDecay, EpochStep, SGD

    if recipe == "resnet":
        from bigdl_tpu.models import ResNet
        from bigdl_tpu.models.resnet.train import cifar10_decay
        optim = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4,
                    nesterov=True, dampening=0.0,
                    learning_rate_schedule=EpochDecay(cifar10_decay))
        return run_image(
            recipe, lambda: ResNet(10, depth=20, dataset="CIFAR10"),
            optim, lambda e: 0.1 * (0.1 ** cifar10_decay(e)),
            epochs, n, batch=448, hw=32, pad=4)
    if recipe == "vgg":
        from bigdl_tpu.models import VggForCifar10
        optim = SGD(learning_rate=0.01, momentum=0.9, weight_decay=5e-4,
                    dampening=0.0,
                    learning_rate_schedule=EpochStep(25, 0.5))
        return run_image(
            recipe, lambda: VggForCifar10(10), optim,
            lambda e: 0.01 * (0.5 ** ((e - 1) // 25)),
            epochs, n, batch=256, hw=32, pad=4)
    if recipe == "inception":
        from bigdl_tpu.models import Inception_v1
        optim = SGD(learning_rate=0.05, momentum=0.9, weight_decay=2e-4,
                    dampening=0.0)

        class AuxNLL:
            """GoogLeNet's 3-head objective (main + 0.3*aux2 + 0.3*aux1
            over the channel-concat output): the aux classifiers exist
            precisely because the 22-layer no-aux net's gradient
            vanishes — measured here as a chance-level flatline."""

            def apply(self, input, target):
                c = input.shape[-1] // 3
                nll = nn.ClassNLLCriterion()
                return (nll.apply(input[:, :c], target)
                        + 0.3 * nll.apply(input[:, c:2 * c], target)
                        + 0.3 * nll.apply(input[:, 2 * c:], target))

        def eval_slice(out):
            return out[:, :out.shape[-1] // 3]

        return run_image(
            recipe, lambda: Inception_v1(10), optim,
            lambda e: 0.05, epochs, n, batch=64, hw=224, pad=8,
            eval_batch=128, criterion=AuxNLL(),
            eval_head=eval_slice)
    if recipe == "lstm":
        from bigdl_tpu.models import PTBModel
        vocab = 256
        optim = SGD(learning_rate=1.0)
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        # lr 1.0 SGD sits on the stability edge (the r4/r5 histories
        # show chaotic early epochs in EVERY code version); the classic
        # PTB recipe pairs it with global-L2 gradient clipping — the
        # reference's setGradientClippingByl2Norm, now implemented
        return run_lm(
            recipe, lambda: PTBModel(vocab, 200, vocab, num_layers=2,
                                     keep_prob=2.0),
            crit, optim, 1.0, epochs, n, seq=32, batch=128,
            one_based=True, vocab=vocab,
            gradient_clip=("l2norm", 5.0))
    if recipe == "transformer":
        from bigdl_tpu.models import TransformerLM
        vocab = 256
        optim = Adam(learning_rate=1e-3)
        crit = nn.SequenceCrossEntropyCriterion()
        return run_lm(
            recipe, lambda: TransformerLM(vocab, hidden_size=128,
                                          num_layers=4, num_heads=8,
                                          max_len=32),
            crit, optim, 1e-3, epochs, n, seq=32, batch=256,
            one_based=False, vocab=vocab)
    if recipe == "moe":
        # the dense transformer recipe's MoE twin (same corpus/oracle):
        # BIGDL_MOE_AUX_W sweeps the load-balance weight
        import os

        from bigdl_tpu.models import TransformerLM
        vocab = 256
        optim = Adam(learning_rate=1e-3)
        crit = nn.SequenceCrossEntropyCriterion()
        aux_w = float(os.environ.get("BIGDL_MOE_AUX_W", "0.01"))
        return run_lm(
            "moe", lambda: TransformerLM(vocab, hidden_size=128,
                                         num_layers=4, num_heads=8,
                                         max_len=32, moe_experts=4,
                                         moe_every=2),
            crit, optim, 1e-3, epochs, n, seq=32, batch=256,
            one_based=False, vocab=vocab, aux_loss_weight=aux_w,
            report_experts=True)
    raise ValueError(f"unknown recipe {recipe}")


def main(argv=None):
    args = list(argv if argv is not None else sys.argv[1:])
    # back-compat: a leading number means the original resnet run
    recipe = "resnet"
    if args and not args[0].isdigit():
        recipe = args.pop(0)
    epochs = int(args[0]) if args else 20
    default_n = 1_000_000 if recipe in ("lstm", "transformer") else 20000
    n = int(args[1]) if len(args) > 1 else default_n
    return run_recipe(recipe, epochs, n)


if __name__ == "__main__":
    main()
