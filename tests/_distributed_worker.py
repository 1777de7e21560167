"""Worker process for the two-process jax.distributed smoke test
(spawned by test_distributed_smoke.py; not itself a pytest file).

Brings up Engine.init_distributed (Engine.scala:100-103's executor
bring-up role), then exercises one cross-process psum and one tiny
data-parallel SGD step whose result must match the sequential update.
Prints one JSON line: {"ok": true, ...} on success, {"skip": reason}
when the runtime lacks cross-process CPU collectives.
"""
import json
import os
import sys


def _optimizer_mode(pid: int):
    """DistriOptimizer over a mesh spanning BOTH processes (4 virtual
    devices each -> 8 global): each process feeds its half of the global
    batch; prints the loss sequence, which the parent compares against a
    single-process 8-device run of the identical global batches
    (RefDistriOptimizer's oracle, lifted to real multi-host)."""
    import jax
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import (DistriOptimizer, SGD, Top1Accuracy,
                                 every_epoch, max_iteration)
    from bigdl_tpu.utils.random import RandomGenerator

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.RandomState(7)
    xs = rng.randn(64, 10).astype(np.float32)
    ys = (rng.randint(0, 3, 64) + 1).astype(np.float32)
    lo, hi = pid * 32, pid * 32 + 32
    samples = [Sample(xs[i], ys[i]) for i in range(lo, hi)]
    ds = DataSet.array(samples).transform(SampleToMiniBatch(8))

    RandomGenerator.set_seed(42)
    model = (nn.Sequential().add(nn.Linear(10, 16)).add(nn.Tanh())
             .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
    from bigdl_tpu.optim.optimizer import Optimizer
    # ZeRO-1 across REAL processes: moment buffers shard dim 0 over the
    # spanning data axis; the update must stay identical to replicated
    # state (the single-process reference the parent compares against)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(),
                    batch_size=8, mesh=mesh, zero1=True)
    opt.set_optim_method(SGD(learning_rate=0.2, momentum=0.9))
    # validation exercises the multi-host local-shard scoring path; a
    # DIFFERENT batch size than training proves the fixed-batch guard is
    # tracked per stream, not shared (it used to abort here)
    val = DataSet.array(samples[:16]).transform(SampleToMiniBatch(4))
    opt.set_validation(every_epoch(), val, [Top1Accuracy()],
                       batch_size=4)
    opt.set_end_when(max_iteration(4))  # exactly one local epoch:
    # stopping before the rollover keeps the data order deterministic
    # for the parent's single-process comparison
    opt.optimize()

    # checkpointing a cross-process ZeRO-1-sharded tree must reassemble
    # the full value on every host (serialization._host_leaf)
    import tempfile

    from bigdl_tpu.parallel import shard_opt_state_zero1
    from bigdl_tpu.utils.serialization import load_tree, save_tree

    w = np.arange(32, dtype=np.float32).reshape(8, 4)
    sharded = shard_opt_state_zero1({"momentum": {"w": w}}, mesh, "data")
    d = tempfile.mkdtemp()
    save_tree(d + "/ck", sharded)
    back = load_tree(d + "/ck")
    np.testing.assert_array_equal(np.asarray(back["momentum"]["w"]), w)

    print(json.dumps({"ok": True, "pid": pid,
                      "last_loss": opt.driver_state["Loss"],
                      "score": opt.driver_state.get("score"),
                      "neval": opt.driver_state["neval"]}))


def _imagefolder_mode(pid: int, folder: str):
    """Multi-host input parity: each process reads ITS shard of one
    image folder (process_index/process_count — the role Spark
    partitioning played for SeqFileFolder) and feeds the global
    DistriOptimizer batch from it."""
    import jax
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ImageFolderDataSet
    from bigdl_tpu.optim import DistriOptimizer, SGD, max_iteration
    from bigdl_tpu.utils.random import RandomGenerator

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    ds = ImageFolderDataSet(folder, batch_size=4, crop=12, scale=16,
                            num_threads=1, process_index=pid,
                            process_count=2)
    assert ds.size() == 16 and ds.local_size() == 8

    RandomGenerator.set_seed(42)
    model = (nn.Sequential().add(nn.Reshape((3 * 12 * 12,)))
             .add(nn.Linear(3 * 12 * 12, 2)).add(nn.LogSoftMax()))
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                          batch_size=4, mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_iteration(3))
    opt.optimize()
    ds.close()
    print(json.dumps({"ok": True, "pid": pid,
                      "last_loss": opt.driver_state["Loss"]}))


def run_parallel_case(kind: str, devices, pid=None):
    """ONE definition of the TP/PP/EP/composed equivalence cases,
    imported by both the worker (spanning mesh over ``jax.devices()``)
    and the parent test's single-process oracle (local devices) —
    hyperparameters and data cannot drift between the two sides.
    Returns driver_state.

    tp: megatron TP on a [1, 4] ("data","model") mesh — the size-1
    data axis is what the flagship recipe's mesh builder emits when TP
    consumes every device, so batches must route down the replicated
    regime, not the per-process-concat DP branch.
    pp: GPipe on a [1, 4] ("data","pipe") mesh — the ppermute
    activation ring crosses whatever transport separates the devices.
    ep: MoE TransformerLM on a [1, 2] ("data","model") mesh with the
    EXPERT axis spanning the processes — routed-expert dispatch
    collectives cross the real transport.
    composed: PipelinedTransformerLM+MoE on a [2, 2, 2]
    ("data","pipe","model") mesh — data axis SPANS the two processes
    (sharded-batch regime: each side feeds its half) while pipe/model
    run within each process: the full DP×TP×PP×EP product on one
    spanning mesh behind one optimize() call. ``pid`` (composed only):
    None = oracle feeds interleaved per-process blocks, else this
    process's half.
    """
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.parallel import make_mesh
    from bigdl_tpu.utils.random import RandomGenerator

    if kind == "tp":
        from bigdl_tpu.models import TransformerLM
        mesh = make_mesh([1, 4], ["data", "model"], devices)
        seed = 11

        def build():
            lm = TransformerLM(vocab_size=32, hidden_size=16,
                               num_layers=2, num_heads=4, max_len=8)
            return lm, lm.sharding_rules(model_axis="model")
    elif kind == "ep":
        from bigdl_tpu.models import TransformerLM
        mesh = make_mesh([1, 2], ["data", "model"], devices)
        seed = 19

        def build():
            lm = TransformerLM(vocab_size=32, hidden_size=16,
                               num_layers=2, num_heads=4, max_len=8,
                               moe_experts=2, moe_every=1)
            return lm, lm.sharding_rules(model_axis="model",
                                         expert_axis="model")
    elif kind.startswith("composed"):
        from bigdl_tpu.models import PipelinedTransformerLM
        mesh = make_mesh([2, 2, 2], ["data", "pipe", "model"], devices)
        seed = 17
        # "composed" runs the interleaved schedule (virtual-stage
        # waiting-room queue + extra ring hops across the transport);
        # "composed_gpipe" keeps the gpipe product covered too
        sched = "gpipe" if kind == "composed_gpipe" else "interleaved"

        def build():
            lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                        num_layers=4, num_heads=2,
                                        max_len=8, n_microbatches=2,
                                        mesh=mesh, moe_experts=2,
                                        pp_schedule=sched, pp_rounds=2)
            return lm, lm.sharding_rules(model_axis="model",
                                         expert_axis="model")
    else:
        from bigdl_tpu.models import PipelinedTransformerLM
        mesh = make_mesh([1, 4], ["data", "pipe"], devices)
        seed = 13

        def build():
            lm = PipelinedTransformerLM(vocab_size=32, hidden_size=16,
                                        num_layers=4, num_heads=2,
                                        max_len=8, n_microbatches=4,
                                        mesh=mesh)
            return lm, lm.sharding_rules()

    rng = np.random.RandomState(seed)
    toks = rng.randint(0, 32, (32, 9))
    all_samples = [Sample(toks[i, :-1].astype(np.int32),
                          toks[i, 1:].astype(np.int32)) for i in range(32)]
    if kind.startswith("composed"):
        # sharded-batch regime over the spanning data axis: global batch
        # i = concat(p0 batch i, p1 batch i)
        if pid is None:
            order = []
            for i in range(4):
                order += list(range(i * 4, i * 4 + 4))
                order += list(range(16 + i * 4, 16 + i * 4 + 4))
            samples, bs = [all_samples[i] for i in order], 8
        else:
            samples, bs = all_samples[pid * 16:pid * 16 + 16], 4
    else:
        # replicated-batch regime (no data axis > 1): all rows each side
        samples, bs = all_samples, 8

    RandomGenerator.set_seed(42)
    lm, rules = build()
    ds = DataSet.array(samples).transform(SampleToMiniBatch(bs))
    opt = Optimizer(lm, ds, nn.SequenceCrossEntropyCriterion(),
                    batch_size=bs, mesh=mesh, sharding_rules=rules)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(_step_marker(max_iteration(4)))
    opt.optimize()
    return opt.driver_state


def _step_marker(base_trigger):
    """Wrap an end trigger to print STEP_OK once the first training
    step completed — the harness uses it to tell a mid-run collective
    deadlock (FAIL) from a slow compile on a loaded host (skip)."""
    state_seen = {"printed": False}

    def trig(state):
        if state["neval"] > 1 and not state_seen["printed"]:
            print("STEP_OK", flush=True)
            state_seen["printed"] = True
        return base_trigger(state)
    return trig


def _tp_or_pp_mode(pid: int, kind: str):
    """TP/PP/EP/composed over a mesh spanning two OS processes (see
    run_parallel_case for the per-kind regime)."""
    import jax

    state = run_parallel_case(kind, jax.devices(),
                              pid if kind.startswith("composed") else None)
    print(json.dumps({"ok": True, "pid": pid,
                      "last_loss": state["Loss"],
                      "neval": state["neval"]}))


def run_sparse_case(pid_or_none, devices):
    """Shared sparse-feed case (SparseMiniBatch at multi-host): COO
    samples with FIXED-nnz padding feed SparseLinear over a spanning
    data mesh. Worker passes its process id (feeds its half of the
    global batch); the single-process oracle passes None (feeds all
    rows as interleaved per-process blocks). Returns driver_state."""
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import (DataSet, PaddingParam, Sample,
                                   SampleToMiniBatch, SparseFeature)
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.parallel import make_mesh
    from bigdl_tpu.utils.random import RandomGenerator

    mesh = make_mesh([len(devices)], ["data"], devices)
    rng = np.random.RandomState(17)
    dim = 32
    hots = [rng.choice(dim, size=rng.randint(1, 4), replace=False)
            for _ in range(32)]
    labels = [float(h[0] % 2 + 1) for h in hots]
    all_samples = [Sample(
        SparseFeature(h[:, None], np.ones(len(h), np.float32), (dim,)),
        labels[i]) for i, h in enumerate(hots)]
    if pid_or_none is None:
        # oracle: global batch i = concat(p0 batch i, p1 batch i)
        order = []
        for i in range(4):
            order += list(range(i * 4, i * 4 + 4))
            order += list(range(16 + i * 4, 16 + i * 4 + 4))
        samples, bs = [all_samples[i] for i in order], 8
    else:
        lo = pid_or_none * 16
        samples, bs = all_samples[lo:lo + 16], 4
    pad = PaddingParam(fixed_length=4)
    ds = DataSet.array(samples).transform(
        SampleToMiniBatch(bs, feature_padding=pad))

    RandomGenerator.set_seed(42)
    model = nn.Sequential().add(nn.SparseLinear(dim, 2)) \
        .add(nn.LogSoftMax())
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), batch_size=bs,
                    mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(_step_marker(max_iteration(4)))
    opt.optimize()
    return opt.driver_state


def _sparse_mode(pid: int):
    """SparseMiniBatch feed over a mesh spanning two OS processes:
    fixed-nnz COO batches assemble into global BCOOs whose leaves shard
    over the cross-process data axis."""
    import jax

    state = run_sparse_case(pid, jax.devices())
    print(json.dumps({"ok": True, "pid": pid,
                      "last_loss": state["Loss"],
                      "neval": state["neval"]}))


def run_predict_case(pid_or_none, devices):
    """Shared distributed-inference case: Predictor/Evaluator over a
    spanning data mesh. Worker passes its process id (feeds its HALF of
    the dataset, gets back its rows' predictions); the single-process
    oracle passes None (all rows). Returns (preds ndarray, global
    Top1Accuracy)."""
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.optim import Top1Accuracy
    from bigdl_tpu.optim.evaluator import Evaluator
    from bigdl_tpu.optim.predictor import Predictor
    from bigdl_tpu.parallel import make_mesh
    from bigdl_tpu.utils.random import RandomGenerator

    mesh = make_mesh([len(devices)], ["data"], devices)
    rng = np.random.RandomState(23)
    xs = rng.randn(32, 10).astype(np.float32)
    ys = (rng.randint(0, 3, 32) + 1).astype(np.float32)
    # oracle feeds the GLOBAL batch (8 rows over 8 devices); each
    # worker feeds its 4-row half of it
    lo, hi, bs = (0, 32, 8) if pid_or_none is None \
        else (pid_or_none * 16, pid_or_none * 16 + 16, 4)
    samples = [Sample(xs[i], ys[i]) for i in range(lo, hi)]
    ds = DataSet.array(samples)

    RandomGenerator.set_seed(42)
    model = (nn.Sequential().add(nn.Linear(10, 16)).add(nn.Tanh())
             .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
    preds = Predictor(model, mesh=mesh).predict(ds, batch_size=bs)
    res = Evaluator(model, mesh=mesh).test(ds, [Top1Accuracy()],
                                           batch_size=bs)
    score, n = res["Top1Accuracy"].result()
    return np.stack(preds), score, n


def _predict_mode(pid: int):
    """Distributed inference over a mesh spanning two OS processes:
    each process feeds ITS dataset shard and must get back exactly its
    rows' predictions; the evaluator reduces scores globally so both
    processes report the same accuracy over all 32 rows."""
    import jax

    preds, score, n = run_predict_case(pid, jax.devices())
    print(json.dumps({"ok": True, "pid": pid, "n": int(n),
                      "score": float(score),
                      "preds": preds.tolist()}))


def _rotate_mode(pid: int):
    """ShardRotator with slots sharded over a mesh SPANNING both
    processes: each process's provider returns its local shard rows,
    staging assembles global pieces, and a rotation is an argument
    rebind on the one compiled draw."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.dataset.device_dataset import ShardRotator

    mesh = Mesh(np.array(jax.devices()), ("data",))
    sh = NamedSharding(mesh, P("data"))
    local_m = 8  # global shard = 16

    def provider(i):
        # every sample carries a unique id in ALL pixels of channel 0
        # AND as its label, so any image/label row mispairing (e.g.
        # piecewise image staging vs whole-shard label layout) is
        # caught sample-exactly, not just on a per-shard aggregate
        ids = 100.0 * i + 10.0 * pid + np.arange(local_m)
        imgs = np.random.RandomState(1000 + 10 * i + pid) \
            .randint(0, 255, (local_m, 3, 8, 8), np.uint8)
        imgs[:, 0, :, :] = ids[:, None, None].astype(np.uint8)
        return imgs, ids.astype(np.float32)

    rot = ShardRotator(provider, 3, 8, crop=(6, 6),
                       shuffle_shards=False, sharding=sh,
                       chunk_bytes=2 * 3 * 8 * 8)
    assert rot.shard_size == 16, rot.shard_size
    tmpl = rot.template

    @jax.jit
    def label_mean(labels):
        return jnp.mean(labels)

    @jax.jit
    def draw(images, labels, key):
        x, y = tmpl.batch_fn_on(images, labels, key,
                                epoch=jnp.int32(0), pos=jnp.int32(0))
        # channel-0 pixel == sample id == label, crop/flip-invariant
        return jnp.max(jnp.abs(x[:, 0, 0, 0] - y)), y

    means = []
    for step in range(3):
        err, _ = draw(rot.images, rot.labels, jax.random.PRNGKey(step))
        assert float(err) == 0.0, f"image/label mispairing, err={err}"
        means.append(float(label_mean(rot.labels)))
        while not rot.staged:
            rot.pump()
        rot.rotate()
    assert draw._cache_size() == 1, "slot swap must not retrace"
    # shard k labels: {100k + 10p + r} -> global mean 100k + 8.5
    assert means == [8.5, 108.5, 208.5], means
    print(json.dumps({"ok": True, "pid": pid, "means": means}))


def main():
    port, pid = sys.argv[1], int(sys.argv[2])
    mode = sys.argv[3] if len(sys.argv) > 3 else "smoke"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + {"smoke": "1", "tp": "2", "pp": "2", "ep": "1"}.get(mode, "4"))

    import numpy as np

    try:
        import jax

        # workers are CPU processes (the chip, where there is one, is
        # the parent's); drop any backend already initialized so the
        # distributed client is wired into a fresh CPU client
        jax.config.update("jax_platforms", "cpu")
        try:
            jax.extend.backend.clear_backends()
        except Exception:
            pass

        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bigdl_tpu.utils.engine import Engine

        Engine.init_distributed(coordinator_address=f"127.0.0.1:{port}",
                                num_processes=2, process_id=pid,
                                initialization_timeout=60)
        assert jax.process_count() == 2, jax.process_count()
        assert Engine.node_number() == 2
        # the harness distinguishes "runtime lacks collectives" (no
        # marker -> skip) from "post-rendezvous deadlock" (marker then
        # timeout -> FAIL)
        print(f"RENDEZVOUS_OK {pid}", flush=True)
        if mode in ("optimizer", "imagefolder", "rotate", "tp", "pp",
                    "ep", "composed", "composed_gpipe", "sparse",
                    "predict"):
            # bring-up succeeded: failures past this point are REAL
            # regressions and must crash the worker (SystemExit bypasses
            # the skip-catch below), not print a skip
            try:
                if mode == "optimizer":
                    _optimizer_mode(pid)
                elif mode in ("tp", "pp", "ep", "composed",
                              "composed_gpipe"):
                    _tp_or_pp_mode(pid, mode)
                elif mode == "sparse":
                    _sparse_mode(pid)
                elif mode == "predict":
                    _predict_mode(pid)
                elif mode == "rotate":
                    _rotate_mode(pid)
                else:
                    _imagefolder_mode(pid, sys.argv[4])
                return
            except Exception:
                import traceback
                traceback.print_exc()
                sys.exit(3)
        mesh = Engine.mesh()
        assert mesh.devices.size == 2

        def replicated_value(arr):
            return np.asarray(
                jax.device_get(arr.addressable_shards[0].data))

        # 1. one psum: global sum of per-process contributions
        shard = NamedSharding(mesh, P("data"))
        repl = NamedSharding(mesh, P())
        local = np.array([float(pid + 1)], np.float32)
        garr = jax.make_array_from_process_local_data(shard, local, (2,))
        total = jax.jit(jnp.sum, out_shardings=repl)(garr)
        tval = float(replicated_value(total))
        assert tval == 3.0, tval

        # 2. one DP step on a global batch sharded across the processes
        xs = np.arange(1, 9, dtype=np.float32).reshape(8, 1)
        ys = 2.0 * xs
        gx = jax.make_array_from_process_local_data(
            shard, xs[pid * 4:(pid + 1) * 4], (8, 1))
        gy = jax.make_array_from_process_local_data(
            shard, ys[pid * 4:(pid + 1) * 4], (8, 1))
        w0 = jnp.zeros((1, 1), jnp.float32)

        @lambda f: jax.jit(f, out_shardings=repl)
        def step(w, x, y):
            g = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
            return w - 0.01 * g

        w1 = float(replicated_value(step(w0, gx, gy)))
        w_ref = float(-0.01 * (2.0 * (0.0 * xs - ys) * xs).mean())
        assert abs(w1 - w_ref) < 1e-6, (w1, w_ref)

        print(json.dumps({"ok": True, "psum": tval, "w1": w1}))
    except (AssertionError,):
        raise
    except Exception as e:  # runtime without cross-process CPU support
        print(json.dumps({"skip": f"{type(e).__name__}: {e}"}))


if __name__ == "__main__":
    main()
