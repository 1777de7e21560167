"""Driver ``train_window``: a model trained through
``Optimizer.optimize()`` with a K-step window, timed over whole windows.

Traffic parameters (``traffic/<name>.json``): ``batch``, ``seq``,
``steps_per_sync`` (K), ``precision``, ``optim`` (``Adam``), ``lr``,
``data_vocab`` (token ids are uniform over the first ``data_vocab`` ids,
``chip_smoke._lm_dataset``'s scheme: a unigram signal, so the loss
falls within a few steps and a step that changes nothing shows),
``epoch_windows`` (windows per pass over the seeded token set),
``warm_windows``, ``trace_windows``, ``limits``; ``controls`` and
``faults`` name the reference's stand-ins: in a lower precision, or with
a fault planted. ``--control 1`` reads them all into the log, leaf by
leaf (what limits are set from); ``--stand-in <name>`` puts one in the
program's place in ``checks``, so the run itself says ``correct: false``.

One ``Optimizer`` is built. Set-up drives it from the seed's weights
through ``optimize()`` twice: (A) one window of K steps — this compiles
the window program, and is what the reference follows; (B) the call
that holds the measured window: its first ``warm_windows`` windows
re-trace and warm up, their step time sizes the plannable iteration
trigger, and the windows after them are timed. The clock is the
benchmark's own: ``set_train_summary`` calls back after every window's
``block_until_ready``. ``optimize()`` re-initialises Adam's moments
when it is called again and exposes no optimizer state, so what (A)
yields is each step's loss and the parameters after K steps; the first
gradient's norm cannot be read through this entry (PERF.md, Open
questions).
"""
from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np


class _Clock:
    """The ``set_train_summary`` observer: per-step losses, and the
    host time at which each window's results were on the host."""

    def __init__(self, k, annotate):
        self.k, self.annotate = k, annotate
        self.reset()
        self.on_window = self._replay = None

    def reset(self):
        self.losses, self.window_done = [], []

    def add_scalar(self, tag, value, step):
        if tag != "Loss":
            return
        if len(self.losses) % self.k == 0:
            # first replayed step of a window: its outputs are ready
            self.window_done.append(time.time())
            if self.on_window is not None:
                self.on_window(len(self.window_done) - 1, int(step))
            # names the idle gap in which the host replays K steps'
            # bookkeeping; what is left unnamed is feed and dispatch
            self._replay = self.annotate("bench/optimize.replay")
            self._replay.__enter__()
        self.losses.append(float(value))
        if len(self.losses) % self.k == 0 and self._replay is not None:
            self._replay.__exit__(None, None, None)
            self._replay = None


def make_tokens(seed, rows, seq, data_vocab):
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    return rng.randint(0, data_vocab, (rows, seq + 1)).astype(np.int32)


def compare(losses, change, ref_losses, ref_grad, ref_change):
    """The numbers ``correct`` is decided on (PERF.md section 2 has the
    readings each limit was set from).

    - ``loss_gap``: the widest |program - reference| over the K losses.
    - ``dparam_gap``: by the worst leaf, the gap between the norm of
      the program's parameter change over the K steps and the
      reference's, against the reference's norm of that leaf or of the
      median leaf, whichever is larger. Leaves whose reference gradient
      at step 1 is under a thousandth of the median leaf's (a key's bias
      under soft-max) move under Adam by round-off alone and are left
      out, by that rule and not by name.
    - ``dparam_gap_rms``: the root mean square of the same gaps over
      all counted leaves. One leaf's swing moves it little, so it is
      steady from seed to seed where the worst leaf is not, and a lower
      precision of the matrix products, which widens the gaps of many
      leaves a little, shows in it where it hides under the worst
      leaf's limit (PERF.md section 2). The median leaf's gap goes into
      the log beside it.
    """
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref_losses))
    g_med = statistics.median(ref_grad.values())
    counted = [n for n in ref_change if ref_grad[n] >= 1e-3 * g_med]
    c_med = statistics.median(ref_change[n] for n in counted)
    gaps = {n: abs(change[n] - ref_change[n]) / max(ref_change[n], c_med)
            for n in counted}
    worst = max(gaps, key=gaps.get)
    return {"loss_gap": loss_gap, "dparam_gap": gaps[worst],
            "dparam_gap_rms": math.sqrt(statistics.fmean(
                g * g for g in gaps.values())),
            "dparam_gap_median": statistics.median(gaps.values()),
            "worst_leaf": worst, "counted": len(counted),
            "left_out": len(ref_change) - len(counted)}


def run(ctx, _break=None):
    """``_break`` is the tests' hook: a function applied to the built
    Optimizer before it runs, to plant a fault under the timed path."""
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import Trigger, max_iteration
    from bigdl_tpu.utils.random import RandomGenerator

    t, cfg, fam = ctx.traffic, ctx.config, ctx.family
    b, s, k = int(t["batch"]), int(t["seq"]), int(t["steps_per_sync"])
    lr = float(t["lr"])
    rows = int(t["epoch_windows"]) * k * b
    warm = int(t.get("warm_windows", 3))
    named = list(t.get("controls", [])) + [
        "fault_" + f for f in t.get("faults", [])]
    if ctx.args.stand_in and ctx.args.stand_in not in named:
        raise SystemExit(f"--stand-in: this mix names {named}")

    RandomGenerator.set_seed(ctx.seed)
    toks = make_tokens(ctx.seed, rows, s, int(t["data_vocab"]))
    ds = DataSet.array([Sample(toks[i, :-1], toks[i, 1:])
                        for i in range(rows)]) \
        .transform(SampleToMiniBatch(b))
    model = fam.build_program_model(cfg)
    model.set_parameters(fam.make_program_params(cfg, ctx.seed))
    clock = _Clock(k, ctx.annotate)
    opt = Optimizer(model, ds, nn.SequenceCrossEntropyCriterion(),
                    batch_size=b)
    opt.set_optim_method(getattr(optim, t.get("optim", "Adam"))(
        learning_rate=lr))
    opt.set_steps_per_sync(k)
    opt.set_precision(t["precision"])
    opt.set_train_summary(clock)
    if _break is not None:
        _break(opt)

    # (A) one window from the seed's weights: compiles, and is compared
    opt.set_end_when(max_iteration(k))
    opt.optimize()
    first_losses = list(clock.losses)
    change = fam.param_change_norms(cfg, ctx.seed, model.get_parameters())

    # (B) the call that holds the measured window
    clock.reset()
    limit = {"n": 10 ** 12}
    mark = {}
    traced = int(t.get("trace_windows", 4))

    def on_window(w, step):
        if w == warm - 1:
            # warm-up is over; size the window in whole K-step windows
            done = clock.window_done
            per_window = (done[-1] - done[0]) / (len(done) - 1) \
                if len(done) > 1 else ctx.seconds
            n = traced if ctx.trace_on else max(
                1, int(round(ctx.seconds / max(per_window, 1e-6))))
            limit["n"] = step + k - 2 + n * k
            mark.update(windows=n, per_window_warm=per_window,
                        compiles=ctx.compiles.count,
                        compile_s=ctx.compiles.seconds)
            ctx.trace_start()
            if ctx.trace_on:
                mark["span"] = ctx.annotate("bench/window")
                mark["span"].__enter__()
            mark["t0"] = time.time()       # first measured step starts
        elif "t0" in mark and w == warm - 1 + mark["windows"]:
            mark["t1"] = time.time()
            if "span" in mark:
                mark.pop("span").__exit__(None, None, None)
                ctx.trace_stop()

    clock.on_window = on_window
    opt.set_end_when(Trigger(
        lambda st: st.get("neval", 1) > limit["n"],
        depends_on=frozenset({"neval"})))
    with ctx.annotate("bench/optimize"):
        opt.optimize()
    window_compiles = ctx.compiles.count - mark["compiles"]
    ctx.close_window()

    steps = mark["windows"] * k
    window_s = mark["t1"] - mark["t0"]
    # where a run reads far off: one long window (a stall) or all of them
    done = clock.window_done[warm - 1:warm + mark["windows"]]
    each = [b - a for a, b in zip(done, done[1:])]
    longest = max(range(len(each)), key=each.__getitem__)
    typical = statistics.median(each)
    timed_losses = clock.losses[warm * k:]
    failed = sum(1 for v in timed_losses if not math.isfinite(v))
    ctx.log.update(
        samples=steps * b, steps=steps, window_s=window_s, batch=b, seq=s,
        required_flops=steps * b * fam.train_flops_per_sample(cfg, s),
        window_compiles=window_compiles,
        per_window_warm_s=mark["per_window_warm"],
        window_each_s={"median": typical, "max": each[longest],
                       "max_began_s": done[longest] - mark["t0"],
                       "over_1.5x_median": sum(1 for d in each
                                               if d > 1.5 * typical)},
        setup_s=mark["t0"] - ctx.t_start, setup_programs=mark["compiles"],
        setup_compile_s=mark["compile_s"],
        loss_first=first_losses[0], loss_last=timed_losses[-1])

    # free the program's state, then the plain reference
    del opt, model, ds, clock
    gc.collect()
    jax.clear_caches()
    first = toks[:k * b].reshape(k, b, s + 1)
    ref_losses, ref_grad, ref_change = fam.ref_train(cfg, ctx.seed, first, lr)
    got = compare(first_losses, change, ref_losses, ref_grad, ref_change)
    ctx.log.update(compare=got, losses=first_losses, ref_losses=ref_losses)

    def stand_in(name):
        """The reference in a control's precision (``controls``) or with
        a fault planted (``faults``), put in the program's place."""
        kw = {"mode": name} if name in t.get("controls", []) else \
            {"fault": name[len("fault_"):]}
        l2, _, c2 = fam.ref_train(cfg, ctx.seed, first, lr, **kw)
        return l2, c2, compare(l2, c2, ref_losses, ref_grad, ref_change)

    if ctx.args.control:
        # the readings limits are set from (PERF.md section 2), leaf by
        # leaf: [program, reference, reference's first gradient]
        ctx.log["leaves"] = {n: [change[n], ref_change[n], ref_grad[n]]
                             for n in ref_change}
        for name in named:
            key = name if name.startswith("fault_") else "control_" + name
            l2, c2, ctx.log[key] = stand_in(name)
            ctx.log[key].update(losses=l2, leaves=c2)
    if ctx.args.stand_in:
        ctx.log["program"], got = got, stand_in(ctx.args.stand_in)[2]
    lim = t["limits"]
    return {
        "end_to_end": {"train_samples_per_s": steps * b / window_s,
                       "setup_s": mark["t0"] - ctx.t_start},
        "attempted": steps, "failed": failed,
        "checks": [["loss_gap", got["loss_gap"], lim["loss_gap"]],
                   ["dparam_gap", got["dparam_gap"], lim["dparam_gap"]],
                   ["dparam_gap_rms", got["dparam_gap_rms"],
                    lim["dparam_gap_rms"]]],
    }
