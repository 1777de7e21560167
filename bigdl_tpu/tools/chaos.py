"""Chaos soak: recovery as a CI-checkable invariant.

    python -m bigdl_tpu.tools.chaos                   # default soak
        --model {lenet,tiny} --steps N --leg-a M      # workload size
        --ckpt-every C --batch-size B --seed S
        --schedule "point=opts;..."                   # leg-B faults
        --kill-at K                                   # + SIGKILL legs
        --workdir DIR --json

The claim under test is the reference's headline operational one —
training survives worker death via retry-from-checkpoint
(DistriOptimizer.scala:789-855; BigDL paper §4) — extended to every
layer this port has grown: checkpoint integrity, IO retry, serving
supervision. The soak *injects* a seeded schedule of faults
(:mod:`bigdl_tpu.faults`) into a seeded training run with a concurrent
serving burst, and asserts three invariants:

1. **Bit-exactness** — the disturbed run's final params are
   bit-identical to an undisturbed seeded run's. The feed is the
   epoch-exact device cache (every batch a pure function of the
   iteration number), checkpoints capture params + momentum + driver
   state, so recovery must be EXACT, not merely "converges anyway".
2. **No hangs** — every serving future AND every generation token
   stream submitted during the bursts resolves (result or *typed*
   error) within its deadline; a pending future after the run is a
   supervision bug. The generation burst drives a tiny TransformerLM
   through the KV-cache decode engine under ``serving/decode`` faults.
3. **Reconciliation** — injected faults equal observed recoveries,
   counter for counter: ``train/step`` raises == optimizer
   ``recoveries``, ``serving/dispatch`` raises == batcher
   ``failed_batches``, ``serving/take_batch`` raises == supervised
   ``worker_restarts``, ``serving/decode`` raises == generation
   decode-loop ``worker_restarts``, and (kill mode) the
   mid-checkpoint SIGKILL == one successful torn-write resume. Pure-latency rules are excluded
   (they recover nothing by design).

Phases: an undisturbed **reference** run; chaos **leg A** to
``--leg-a`` steps (in ``--kill-at`` mode this leg runs as a
subprocess, SIGKILLed mid-checkpoint-write, then relaunched to
completion — the torn tmp dir must never be selected); a **corrupt**
phase truncating the latest checkpoint's ``params.npz`` behind its
MANIFEST (bit rot); chaos **leg B** resuming — which must quarantine
the corrupt dir, walk back to the previous intact checkpoint, absorb
the scheduled step/serving faults, and still land on the reference
params. Exit 0 all invariants hold, 1 a violation, 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

DEFAULT_SCHEDULE = (
    "train/step=nth:3,raise:RuntimeError;"
    "train/step=nth:6,raise:OSError;"
    "serving/dispatch=nth:4,raise:RuntimeError;"
    "serving/take_batch=nth:6,raise:RuntimeError;"
    "serving/decode=nth:4,raise:RuntimeError;"
    "serving/dispatch=delay:2,times:2"
)

#: --fleet leg default: the 3rd request the router places on replica
#: r1 kills that replica mid-burst (the fleet/replica faultpoint in
#: Replica.submit translates an injected raise into a replica death)
DEFAULT_FLEET_SCHEDULE = \
    "fleet/replica=nth:3,raise:RuntimeError,match:replica=r1"


def _build_workload(model_kind: str, seed: int, batch_size: int,
                    sharding=None):
    """Seeded (model, dataset, criterion): the feed is the epoch-exact
    device cache with deterministic augmentation (full-size crop, no
    flip), so every batch — and therefore every optimizer state — is a
    pure function of the iteration number. That is what entitles the
    soak to demand bit-identical recovery. ``sharding`` places the
    cache over a (possibly process-spanning) mesh; the device cache's
    multi-host contract is that each process passes its LOCAL rows
    (global n = local n x process_count), so the seeded corpus is
    sliced contiguously by process rank here — the assembled GLOBAL
    array, its size, and therefore the iteration-k batch stream are
    identical whatever the world size: the invariant the host-kill
    leg's cross-world-size resume comparison rides."""
    import jax
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset.device_dataset import DeviceCachedArrayDataSet
    from bigdl_tpu.tools.synthetic import seeded_rng
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(seed)
    r = seeded_rng(seed)

    def local_rows(arr):
        """This process's contiguous slice of the seeded global
        corpus (the device cache assembles the global array from
        per-process contributions in rank order)."""
        pc = jax.process_count() if sharding is not None else 1
        if pc <= 1:
            return arr
        if len(arr) % pc:
            raise ValueError(
                f"hostkill workload rows {len(arr)} must divide the "
                f"process count {pc}")
        k = len(arr) // pc
        return arr[jax.process_index() * k:(jax.process_index() + 1) * k]

    if model_kind == "lenet":
        from bigdl_tpu.models import LeNet5
        imgs = local_rows(r.randint(0, 255, (64, 1, 28, 28))
                          .astype(np.uint8))
        lbls = local_rows((r.randint(0, 10, 64) + 1).astype(np.float32))
        ds = DeviceCachedArrayDataSet(imgs, lbls, batch_size, flip=False,
                                      mean=(127.0,), std=(64.0,),
                                      shuffle_seed=seed,
                                      sharding=sharding)
        model = LeNet5(10)
    else:
        imgs = local_rows(r.randint(0, 255, (32, 3, 8, 8))
                          .astype(np.uint8))
        lbls = local_rows((r.randint(0, 2, 32) + 1).astype(np.float32))
        ds = DeviceCachedArrayDataSet(imgs, lbls, batch_size, flip=False,
                                      mean=(127.0,) * 3, std=(64.0,) * 3,
                                      shuffle_seed=seed,
                                      sharding=sharding)
        model = (nn.Sequential().add(nn.Reshape((3 * 8 * 8,)))
                 .add(nn.Linear(3 * 8 * 8, 16)).add(nn.Tanh())
                 .add(nn.Linear(16, 2)).add(nn.LogSoftMax()))
    return model, ds, nn.ClassNLLCriterion()


def _train_leg(model_kind: str, seed: int, batch_size: int, steps: int,
               ckpt_dir: Optional[str], ckpt_every: int,
               async_ckpt: bool = False):
    """One seeded training leg: fresh model + dataset, resume from
    ``ckpt_dir`` if it holds checkpoints, train to ``steps`` total
    iterations (``async_ckpt`` uses the format-3 elastic writer).
    Returns the optimizer (final params live on its model)."""
    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.optimizer import Optimizer

    model, ds, crit = _build_workload(model_kind, seed, batch_size)
    opt = Optimizer(model, ds, crit, batch_size=batch_size)
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9))
    opt.set_end_when(max_iteration(steps))
    opt.retry_interval_s = 0.05  # keep the soak's backoff sleeps short
    if ckpt_dir is not None:
        opt.set_checkpoint(ckpt_dir, several_iteration(ckpt_every),
                           async_write=async_ckpt)
    opt.optimize()
    return opt


def _final_params(opt) -> Dict[str, "object"]:
    """name -> host ndarray of the trained model's params (the flat
    form two runs are compared bit-for-bit in)."""
    from bigdl_tpu.utils.serialization import _flatten_leaves
    return _flatten_leaves(opt.model.get_parameters())


def _params_equal(a: Dict, b: Dict) -> Tuple[bool, List[str]]:
    import numpy as np
    bad = [k for k in sorted(set(a) | set(b))
           if k not in a or k not in b
           or a[k].dtype != b[k].dtype
           or not np.array_equal(a[k], b[k])]
    return not bad, bad


# ------------------------------------------------------- serving burst

class _Burst:
    """Background serving burst against a dedicated InferenceService;
    collects EVERY submitted future so the no-hang invariant can be
    checked request by request."""

    def __init__(self, seed: int, threads: int = 2,
                 breaker_failures: int = 3):
        import numpy as np

        import bigdl_tpu.nn as nn
        from bigdl_tpu.serving import InferenceService, ServingConfig
        from bigdl_tpu.tools.synthetic import seeded_rng

        self.svc = InferenceService(config=ServingConfig(
            max_batch_size=8, max_wait_ms=1.0, buckets=(8,),
            breaker_failures=breaker_failures, breaker_cooldown_ms=50.0))
        serve_model = (nn.Sequential().add(nn.Reshape((16,)))
                       .add(nn.Linear(16, 4)))
        serve_model.ensure_initialized()
        self.svc.load("chaos", serve_model, warmup_shape=(4, 4))
        self.req = seeded_rng(seed + 1).rand(4, 4, 4).astype(np.float32)
        self.futures: List = []
        self._fut_lock = threading.Lock()
        self.shed = 0
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._run, daemon=True,
                                         name=f"chaos-burst-{i}")
                        for i in range(threads)]

    def _run(self):
        from bigdl_tpu.serving import Degraded, QueueFull
        while not self.stop.is_set():
            try:
                f = self.svc.predict_batch_async("chaos", self.req,
                                                 timeout_ms=2000)
            except Degraded:
                self.shed += 1
                time.sleep(0.005)
                continue
            except QueueFull:
                # transient backlog (e.g. during an injected worker
                # death): keep bursting — a thread that quit here
                # would let the soak pass vacuously
                time.sleep(0.005)
                continue
            except RuntimeError:
                break  # service shut down under us
            with self._fut_lock:
                # run-bounded soak collector: EVERY future must stay
                # reachable for the no-hang invariant check
                # bigdl: disable=unbounded-cache-growth
                self.futures.append(f)
            time.sleep(0.002)

    def start(self):
        for t in self.threads:
            t.start()

    def finish(self, deadline_s: float = 30.0) -> Dict[str, int]:
        """Stop the burst, drain the service, and resolve every
        future: {ok, typed_errors, hung}. ``hung`` > 0 is the
        supervision failure mode this soak exists to catch."""
        from concurrent.futures import TimeoutError as FutTimeout
        self.stop.set()
        for t in self.threads:
            t.join(timeout=10)
        self.svc.shutdown(drain=True)
        out = {"ok": 0, "typed_errors": 0, "hung": 0}
        end = time.monotonic() + deadline_s
        for f in self.futures:
            try:
                f.result(timeout=max(0.0, end - time.monotonic()))
                out["ok"] += 1
            except FutTimeout:
                out["hung"] += 1
            except Exception:
                out["typed_errors"] += 1
        return out

    def stats(self) -> Dict[str, float]:
        m = self.svc.metrics("chaos")
        m["shed_seen_by_submitters"] = self.shed
        return m


class _GenBurst:
    """Background *generation* burst against a dedicated
    GenerationService (tiny TransformerLM, 2 cache slots): token-stream
    requests submitted continuously so the ``serving/decode`` faults in
    the schedule land under real continuous-batching traffic. Collects
    EVERY stream so the no-hang invariant extends to generation — a
    decode-loop death must fail streams typed, never strand them."""

    def __init__(self, seed: int, threads: int = 2):
        import numpy as np

        from bigdl_tpu.generation import (GenerationConfig,
                                          GenerationService)
        from bigdl_tpu.models import TransformerLM
        from bigdl_tpu.tools.synthetic import seeded_rng
        from bigdl_tpu.utils.random import RandomGenerator

        RandomGenerator.set_seed(seed + 2)
        model = TransformerLM(vocab_size=32, hidden_size=16,
                              num_layers=1, num_heads=2,
                              max_len=16).evaluate()
        model.ensure_initialized()
        self.svc = GenerationService(config=GenerationConfig(
            # chaos drills pin a tiny fixed geometry — the drill is the
            # point, not throughput
            slots=2, max_len=16, length_buckets=(16,), prefill_rows=2,  # bigdl: disable=hardcoded-tuned-constant
            max_queue=8))
        self.svc.load("chaos-lm", model)
        self.prompt = seeded_rng(seed + 3).randint(
            1, 32, 3).astype(np.int32)
        self.streams: List = []
        self._lock = threading.Lock()
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._run, daemon=True,
                                         name=f"chaos-gen-burst-{i}")
                        for i in range(threads)]

    def _run(self):
        from bigdl_tpu.serving import QueueFull
        while not self.stop.is_set():
            try:
                s = self.svc.generate("chaos-lm", self.prompt,
                                      max_new_tokens=4, seed=7,
                                      timeout_ms=5000)
            except QueueFull:
                time.sleep(0.005)
                continue
            except RuntimeError:
                break  # service shut down under us
            with self._lock:
                # run-bounded soak collector (see _Burst.futures)
                # bigdl: disable=unbounded-cache-growth
                self.streams.append(s)
            time.sleep(0.002)

    def start(self):
        for t in self.threads:
            t.start()

    def finish(self, deadline_s: float = 30.0) -> Dict[str, int]:
        """Stop the burst, drain the service, and resolve every
        stream: {ok, typed_errors, hung}. The drain itself is bounded
        — a decode loop hung by the very supervision bug this
        invariant exists to catch must surface as ``hung`` streams,
        not hang the soak."""
        from concurrent.futures import TimeoutError as FutTimeout
        self.stop.set()
        for t in self.threads:
            t.join(timeout=10)
        closer = threading.Thread(
            target=lambda: self.svc.shutdown(drain=True), daemon=True,
            name="chaos-gen-burst-drain")
        closer.start()
        closer.join(timeout=deadline_s)
        out = {"ok": 0, "typed_errors": 0, "hung": 0}
        end = time.monotonic() + deadline_s
        for s in self.streams:
            try:
                s.result(timeout=max(0.0, end - time.monotonic()))
                out["ok"] += 1
            except FutTimeout:
                out["hung"] += 1
            except Exception:
                out["typed_errors"] += 1
        return out

    def stats(self) -> Dict[str, float]:
        return self.svc.metrics("chaos-lm")


def _await_deterministic_rules(sched, points, timeout_s: float) -> None:
    """Keep the burst window open until every deterministic raise rule
    on ``points`` has fired (seeded-prob rules may legitimately land on
    zero) — the training leg can finish before a background burst has
    taken enough decode steps to reach an nth trigger."""
    rules = [r for r in sched.rules
             if r.point in points and r.prob is None
             and r.action in ("raise", "sigkill")]
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if all(r.fired > 0 for r in rules):
            return
        time.sleep(0.02)


# ------------------------------------------------------------- worker

def _run_worker(args) -> int:
    """Subprocess leg for the SIGKILL phases: arm the given schedule,
    train (resuming from the shared checkpoint dir), print a JSON
    result line. Exit 0 on completion — or death by injected SIGKILL,
    which the parent observes as rc -9."""
    import jax
    # the chip is the parent's: chaos children train on the CPU
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu import faults
    if args.schedule:
        faults.arm(args.schedule)
    opt = _train_leg(args.model, args.seed, args.batch_size, args.steps,
                     args.ckpt_dir, args.ckpt_every,
                     async_ckpt=getattr(args, "async_ckpt", False))
    if args.save_params:
        import numpy as np
        np.savez(args.save_params, **_final_params(opt))
    print(json.dumps({"ok": True, "neval": opt.driver_state["neval"],
                      "loss": opt.driver_state.get("Loss")}))
    return 0


def _spawn_worker(model: str, seed: int, batch_size: int, steps: int,
                  ckpt_dir: str, ckpt_every: int, schedule: str,
                  timeout_s: float = 600.0):
    import subprocess
    env = dict(os.environ)
    # the chip is the parent's (one process per chip): the child that
    # gets killed and relaunched runs on the CPU
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, "-m", "bigdl_tpu.tools.chaos", "--worker",
           "--model", model, "--seed", str(seed),
           "--batch-size", str(batch_size), "--steps", str(steps),
           "--ckpt-dir", ckpt_dir, "--ckpt-every", str(ckpt_every)]
    if schedule:
        cmd += ["--schedule", schedule]
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout_s, env=env)


# ------------------------------------------------- host-kill chaos leg

def _run_hostkill_worker(args) -> int:
    """Gang-worker entry for the host-kill leg (spawned by
    ``tools.launch``): bring up jax.distributed from the launcher's env
    when the gang spans processes, train the seeded workload over a
    mesh of ALL devices with ASYNC elastic checkpoints + SIGTERM grace,
    and (rank 0) save the final params for the parent's comparison."""
    import jax
    import numpy as np

    if int(os.environ.get("JAX_NUM_PROCESSES", "1")) > 1:
        from bigdl_tpu.utils.engine import Engine
        Engine.init_distributed(initialization_timeout=120)
    else:
        # the chip is the parent's: a one-process gang trains on the CPU
        jax.config.update("jax_platforms", "cpu")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.optim import SGD, max_iteration, several_iteration
    from bigdl_tpu.optim.optimizer import Optimizer

    if getattr(args, "step_delay_ms", 0):
        # pure-latency pacing so the parent's monitor tick can land the
        # host kill MID-WINDOW (latency rules recover nothing and are
        # excluded from reconciliation by design)
        from bigdl_tpu import faults
        faults.arm(f"train/step=delay:{args.step_delay_ms},times:100000")
    mesh = Mesh(np.array(jax.devices()), ("data",))
    model, ds, crit = _build_workload(
        args.model, args.seed, args.batch_size,
        sharding=NamedSharding(mesh, P("data")))
    opt = Optimizer(model, ds, crit, batch_size=args.batch_size,
                    mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9))
    opt.set_end_when(max_iteration(args.steps))
    opt.retry_interval_s = 0.05
    opt.set_checkpoint(args.ckpt_dir, several_iteration(args.ckpt_every),
                       async_write=True, keep_last=4)
    opt.set_preemption_handler()
    opt.optimize()
    if args.save_params and jax.process_index() == 0:
        np.savez(args.save_params, **_final_params(opt))
    print(json.dumps({"ok": True, "neval": opt.driver_state["neval"],
                      "world": jax.process_count()}))
    return 0


def run_hostkill(model: str = "tiny", steps: int = 12,
                 ckpt_every: int = 2, batch_size: int = 8,
                 seed: int = 42, nproc: int = 2, cpu_devices: int = 2,
                 relaunch_nproc: int = 1, relaunch_cpu_devices: int = 2,
                 kill_after_commits: int = 1,
                 workdir: Optional[str] = None,
                 tol: float = 1e-5, slo_spec=None) -> Dict:
    """The multi-process host-kill leg: SIGKILL a WHOLE gang host
    mid-window and prove elastic recovery at a DIFFERENT world size.

    Phases: (1) capability probe — a runtime whose CPU backend cannot
    execute cross-process collectives reports ``skipped`` with the
    precise reason instead of crashing; (2) an uninterrupted
    single-process reference run of the identical seeded workload
    (the epoch-exact device cache makes the GLOBAL batch at iteration
    k world-size-invariant); (3) gang A (``nproc`` x ``cpu_devices``)
    through ``tools.launch.run_gang``, SIGKILLed whole-host by the
    monitor hook once ``kill_after_commits`` async checkpoints have
    COMMITTED; (4) relaunch at a different world size
    (``relaunch_nproc``) which must resume from the last committed
    elastic checkpoint and finish. Asserted: the torn in-flight write
    is never visible (the resumed run loads only committed state), the
    resumed params match the reference within ``tol`` (bit-identical
    when the relaunch topology equals the original), and the one
    injected host kill reconciles against exactly one successful
    relaunch."""
    import signal as _signal

    import numpy as np

    from bigdl_tpu.elastic.capability import multiprocess_cpu
    from bigdl_tpu.tools import launch

    report: Dict = {"model": model, "steps": steps, "seed": seed,
                    "nproc": nproc, "relaunch_nproc": relaunch_nproc,
                    "violations": []}
    if max(nproc, relaunch_nproc) > 1:
        # only a process-SPANNING gang needs cross-process collectives;
        # an nproc=1 host kill (gang + SIGKILL + elastic resume across
        # a device-count change) runs on any runtime
        ok, reason = multiprocess_cpu()
        if not ok:
            report["skipped"] = reason
            report["passed"] = True
            return report

    own_workdir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="bigdl-hostkill-")
    ckpt_dir = os.path.join(workdir, "ckpts")
    ref_ckpt = os.path.join(workdir, "ref-ckpts")
    ref_npz = os.path.join(workdir, "ref.npz")
    out_npz = os.path.join(workdir, "resumed.npz")
    # per-gang snapshot-shipping dirs: gang A's files are the
    # postmortem evidence the SIGKILL cannot destroy, gang B's feed
    # the merged-fleet SLO below
    tel_a = os.path.join(workdir, "telemetry-a")
    tel_b = os.path.join(workdir, "telemetry-b")
    script = os.path.abspath(__file__)
    # workers run this file AS A SCRIPT: the package root must be
    # importable however the parent was started
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(script)))
    extra_env = {"PYTHONPATH": pkg_root + os.pathsep
                 + os.environ.get("PYTHONPATH", "")}

    def wargs(ckpt, save, extra=()):
        return ["--hostkill-worker", "--model", model,
                "--seed", str(seed), "--batch-size", str(batch_size),
                "--steps", str(steps), "--ckpt-every", str(ckpt_every),
                "--ckpt-dir", ckpt, "--save-params", save, *extra]

    # gang A is PACED (pure-latency train/step rule) so the monitor's
    # poll tick reliably lands the SIGKILL mid-window, between commits
    paced = ["--step-delay-ms", "150"]

    try:
        # -- phase 2: uninterrupted single-process reference ----------
        ref = launch.run_gang(launch.build_args(
            script, wargs(ref_ckpt, ref_npz), nproc=1,
            cpu_devices=relaunch_cpu_devices, extra_env=extra_env))
        if not ref.ok:
            report["violations"].append(
                f"reference leg failed: {ref.reports}")
            report["passed"] = False
            return report

        # -- phase 3: gang A, whole-host SIGKILL mid-window -----------
        from bigdl_tpu.elastic import committed_checkpoints
        killed = {"done": False}

        def monitor(workers):
            if killed["done"]:
                return
            if len(committed_checkpoints(ckpt_dir)) >= kill_after_commits:
                launch.kill_gang(workers, sig=_signal.SIGKILL)
                killed["done"] = True

        gang_a = launch.run_gang(launch.build_args(
            script, wargs(ckpt_dir, out_npz, paced), nproc=nproc,
            cpu_devices=cpu_devices, extra_env=extra_env,
            ship_telemetry=tel_a),
            monitor=monitor)
        if not killed["done"]:
            report["violations"].append(
                "gang A finished before any checkpoint committed — the "
                "host kill never fired (raise steps or lower "
                "kill_after_commits)")
        kills = [r for r in gang_a.reports if r.kind == "killed"]
        report["gang_a"] = [(r.rank, r.kind, r.returncode)
                            for r in gang_a.reports]
        if killed["done"] and len(kills) != nproc:
            report["violations"].append(
                f"host kill delivered but only {len(kills)}/{nproc} "
                "workers report kind=killed")

        # -- phase 4: relaunch at a DIFFERENT world size --------------
        gang_b = launch.run_gang(launch.build_args(
            script, wargs(ckpt_dir, out_npz), nproc=relaunch_nproc,
            cpu_devices=relaunch_cpu_devices, max_restarts=1,
            extra_env=extra_env, ship_telemetry=tel_b))
        report["gang_b"] = [(r.rank, r.kind, r.returncode)
                            for r in gang_b.reports]
        if not gang_b.ok:
            report["violations"].append(
                f"relaunch at world size {relaunch_nproc} failed: "
                f"{[(r.rank, r.returncode, r.output_tail[-300:]) for r in gang_b.reports]}")

        # -- invariants -----------------------------------------------
        report["injected"] = {"hostkill": 1 if killed["done"] else 0}
        report["recovered"] = {"relaunch": 1 if gang_b.ok else 0}
        if report["injected"]["hostkill"] != report["recovered"][
                "relaunch"]:
            report["violations"].append(
                "host kills and successful relaunches do not reconcile "
                f"({report['injected']} vs {report['recovered']})")
        if gang_b.ok:
            same_topology = (relaunch_nproc == nproc
                             and relaunch_cpu_devices == cpu_devices)
            with np.load(ref_npz) as a, np.load(out_npz) as b:
                bad, worst = [], 0.0
                for k in sorted(set(a.files) | set(b.files)):
                    if k not in a.files or k not in b.files:
                        bad.append(k)
                        continue
                    err = float(np.abs(a[k] - b[k]).max())
                    worst = max(worst, err)
                    if (same_topology and err != 0.0) or err > tol:
                        bad.append(f"{k} (err {err})")
                report["params_max_err"] = worst
                report["bit_identical"] = same_topology and worst == 0.0
                if bad:
                    report["violations"].append(
                        "resumed params diverged from the "
                        f"uninterrupted reference: {bad}")

        # -- observability: postmortem snapshots, stragglers, SLO -----
        # gang A shipped step-cadence snapshots before the SIGKILL —
        # append-only files the kill cannot destroy; gang B's merged
        # fleet snapshot feeds the progress/skew SLO
        from bigdl_tpu.telemetry import agg, slo as slo_mod
        sources_a = agg.read_snapshot_dir(tel_a)
        report["postmortem_snapshots"] = len(sources_a)
        if killed["done"] and not sources_a:
            report["violations"].append(
                "SIGKILLed gang A left no shipped telemetry "
                "snapshots — the postmortem evidence trail is empty")
        sources_b = agg.read_snapshot_dir(tel_b)
        if sources_b:
            merged = agg.aggregate_snapshots(sources_b)
            for bad_line in agg.check_merge_invariant(
                    sources_b, merged):
                report["violations"].append(
                    "merge invariant: " + bad_line)
            strag = agg.detect_stragglers(sources_b)
            report["stragglers"] = {"median": strag["median"],
                                    "stragglers": strag["stragglers"]}
            skew = max((v / strag["median"]
                        for v in strag["per_source"].values()),
                       default=1.0) if strag["median"] > 0 else 1.0
            spec = slo_spec if slo_spec is not None else slo_mod.SloSpec([
                slo_mod.SloObjective(
                    "progress", "train/optimizer/steps", ">=", 1.0),
                # generous: flags pathological skew only, not CI noise
                slo_mod.SloObjective(
                    "step_skew", "step_time_skew", "<=", 100.0,
                    default=1.0),
            ])
            slo_report = slo_mod.evaluate(
                spec, merged, {"step_time_skew": skew})
            report["slo"] = slo_report.to_dict()
            report["violations"].extend(
                "SLO breach: " + v.describe()
                for v in slo_report.verdicts if not v.ok)
        elif gang_b.ok:
            report["violations"].append(
                "relaunched gang shipped no telemetry snapshots")
    finally:
        if own_workdir:
            shutil.rmtree(workdir, ignore_errors=True)
    report["passed"] = not report["violations"]
    return report


# -------------------------------------------------- fleet chaos leg

class _NoFaults:
    """Stand-in schedule for a fault-free control run."""
    rules = ()

    def fired(self):
        return {}


_NO_FAULTS = _NoFaults()


def run_fleet(replicas: int = 3, requests: int = 18, threads: int = 3,
              max_new: int = 4, seed: int = 42,
              schedule: Optional[str] = DEFAULT_FLEET_SCHEDULE,
              deadline_s: float = 120.0,
              out_dir: Optional[str] = None,
              slo_spec=None,
              ttft_budget_ms: float = 30000.0) -> Dict:
    """The ``--fleet`` leg: kill one replica mid-burst under a seeded
    schedule and prove the router's failure contract.

    Phases: (1) build a thread-hosted fleet of identical seeded
    replicas behind a :class:`~bigdl_tpu.fleet.router.FleetRouter` and
    record each prompt's greedy reference output (replica weights are
    identical, so ONE reference adjudicates every replica); (2) arm
    the schedule — an injected ``fleet/replica`` fault at a replica's
    submit path IS that replica's death — and burst seeded requests
    from several threads, holding the window open until every
    deterministic rule fired; (3) resolve every stream. Asserted:
    every in-flight stream resolves within the deadline as tokens
    (possibly re-routed) or a TYPED error — never a hang; every
    successful greedy stream is bit-identical to the reference,
    re-routed or not; and injected ``fleet/replica`` faults reconcile
    counter-for-counter against the router's
    ``fleet/replica/evictions``.

    Observability plane (this leg doubles as its end-to-end check):
    every replica serves out of its OWN registry; the per-source
    snapshots are shipped to ``out_dir`` (default: a kept temp dir,
    path under ``report["artifacts"]``), merged via
    ``telemetry.agg.aggregate_snapshots`` (merge invariant asserted),
    the burst's spans become ONE merged Perfetto timeline, and
    ``slo_spec`` (default: evictions==0 + p99 TTFT budget) is
    evaluated over the MERGED snapshot. A seeded replica death must
    surface as a typed ``SloBreach``; a clean schedule must pass."""
    import numpy as np

    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu import faults
    from bigdl_tpu.fleet import FleetRouter, build_replicas
    from bigdl_tpu.serving import Degraded, QueueFull
    from bigdl_tpu.telemetry import agg, slo as slo_mod
    from bigdl_tpu.tools.synthetic import seeded_rng

    report: Dict = {"replicas": replicas, "requests": requests,
                    "schedule": schedule, "violations": []}
    if out_dir is None:
        out_dir = tempfile.mkdtemp(prefix="bigdl-chaos-fleet-")
    os.makedirs(out_dir, exist_ok=True)
    snap_dir = os.path.join(out_dir, "snapshots")
    os.makedirs(snap_dir, exist_ok=True)
    # spans from the burst feed the merged timeline; restore the
    # caller's tracing state afterwards
    tracing_was_on = telemetry.enabled()
    telemetry.enable()
    metrics = telemetry.MetricsRegistry()
    # metrics=None: each replica's GenerationService keeps its OWN
    # registry (the cross-process shape, thread-hosted); the router's
    # instruments live in `metrics` and the observability plane must
    # merge them all back together
    reps = build_replicas(replicas, seed=seed, max_queue=8,
                          metrics=None)
    router = FleetRouter(reps, metrics=metrics)
    r = seeded_rng(seed + 1)
    prompts = [r.randint(1, 31, 3).astype(np.int32) for _ in range(4)]
    try:
        # -- phase 1: greedy references, before any chaos -------------
        refs = []
        for p in prompts:
            refs.append(list(router.submit(
                p, max_new_tokens=max_new).result(60)))

        # -- phase 2: the burst, one replica dying under it -----------
        streams: List = []
        lock = threading.Lock()
        nxt = {"i": 0}

        def pump():
            while True:
                with lock:
                    i = nxt["i"]
                    if i >= requests:
                        return
                    nxt["i"] += 1
                while True:
                    try:
                        s = router.submit(prompts[i % len(prompts)],
                                          session=f"sess-{i % 6}",
                                          max_new_tokens=max_new)
                    except (QueueFull, Degraded):
                        time.sleep(0.005)
                        continue
                    with lock:
                        streams.append((i % len(prompts), s))
                    break

        # pre-pin the burst's sessions round-robin so every replica —
        # including the schedule's target — deterministically receives
        # submits (stickiness then keeps them there until the kill)
        names = [rep.name for rep in router.replicas()]
        for i in range(6):
            router._sessions[f"sess-{i}"] = names[i % len(names)]
        # schedule=None runs the same burst fault-free — the control
        # leg that proves a clean fleet does NOT breach the SLO
        sched = faults.arm(schedule) if schedule else _NO_FAULTS
        try:
            workers = [threading.Thread(target=pump, daemon=True,
                                        name=f"chaos-fleet-{i}")
                       for i in range(threads)]
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=deadline_s)
            if schedule:
                _await_deterministic_rules(sched, ("fleet/replica",),
                                           timeout_s=15.0)
        finally:
            if schedule:
                faults.disarm()

        # -- phase 3: every stream resolves, typed or tokens ----------
        from concurrent.futures import TimeoutError as FutTimeout
        resolved = {"ok": 0, "typed_errors": 0, "hung": 0}
        end = time.monotonic() + deadline_s
        mismatched = []
        for pi, s in streams:
            try:
                out = list(s.result(
                    timeout=max(0.0, end - time.monotonic())))
                resolved["ok"] += 1
                if out != refs[pi]:
                    mismatched.append((pi, out, refs[pi]))
            except FutTimeout:
                resolved["hung"] += 1
            except Exception:
                resolved["typed_errors"] += 1
        report["burst"] = resolved
        if resolved["hung"]:
            report["violations"].append(
                f"{resolved['hung']} fleet streams never resolved")
        if mismatched:
            report["violations"].append(
                "greedy outputs diverged from the pre-chaos reference "
                f"(first: {mismatched[0]})")
        report["bit_identical"] = not mismatched

        # -- invariants: injected == evictions, rules fired -----------
        injected = sched.fired().get("fleet/replica", 0)
        evictions = int(metrics.counter(
            "fleet/replica/evictions").total())
        reroutes = int(metrics.counter("fleet/router/reroutes").total())
        report["injected"] = {"fleet/replica": injected}
        report["recovered"] = {"evictions": evictions,
                               "reroutes": reroutes}
        if injected != evictions:
            report["violations"].append(
                f"injected {injected} replica kills but the router "
                f"evicted {evictions}")
        for rule in sched.rules:
            if rule.prob is None and rule.action == "raise" \
                    and rule.fired == 0:
                report["violations"].append(
                    f"scheduled fault never fired: {rule!r}")
        report["states"] = router.metrics()["states"]

        # -- observability plane: ship, merge, SLO --------------------
        # ship every per-replica registry (dead ones included — that
        # is the postmortem) plus the router's through the real JSONL
        # wire format, then read the directory back like a collector
        for rep in reps:
            telemetry.JsonlExporter(
                rep.service.metrics_registry,
                os.path.join(snap_dir, f"snap-{rep.name}.jsonl"),
                identity=telemetry.process_identity(replica=rep.name),
                include_samples=True).export()
        telemetry.JsonlExporter(
            metrics, os.path.join(snap_dir, "snap-router.jsonl"),
            identity=telemetry.process_identity(replica="router"),
            include_samples=True).export()
        sources = agg.read_snapshot_dir(snap_dir)
        merged = agg.aggregate_snapshots(sources)
        for bad in agg.check_merge_invariant(sources, merged):
            report["violations"].append(f"merge invariant: {bad}")
        trace_path = os.path.join(out_dir, "fleet-trace.json")
        agg.write_merged_trace(
            trace_path,
            [("fleet", telemetry.tracer().chrome_trace_events())])
        if slo_spec is None:
            slo_spec = slo_mod.SloSpec([
                slo_mod.SloObjective(
                    "evictions", "fleet/replica/evictions", "<=", 0.0,
                    default=0.0),
                slo_mod.SloObjective(
                    "p99_ttft", "serving/generation/ttft_ms.p99",
                    "<=", ttft_budget_ms, default=0.0),
            ])
        slo_report = slo_mod.evaluate(slo_spec, merged)
        report["slo"] = slo_report.to_dict()
        slo_path = os.path.join(out_dir, "slo.json")
        with open(slo_path, "w") as f:
            json.dump(report["slo"], f, indent=2, default=str)
        report["artifacts"] = {"dir": out_dir, "snapshots": snap_dir,
                               "trace": trace_path, "slo": slo_path}
        breach = None
        try:
            slo_report.check()
        except slo_mod.SloBreach as e:
            breach = e
        report["slo_breach_detected"] = breach is not None
        # the contract this leg certifies: a seeded replica death
        # under load IS an SLO breach (typed), a clean run is not
        if injected > 0 and breach is None:
            report["violations"].append(
                "seeded replica death did not surface as a typed "
                "SLO breach")
        if injected == 0 and breach is not None:
            report["violations"].append(
                f"clean fleet run breached SLO: "
                f"{breach.report.breached}")
    finally:
        router.shutdown(drain=True)
        if not tracing_was_on:
            telemetry.disable()
    report["passed"] = not report["violations"]
    return report


# ------------------------------------------------ control-plane leg

def run_control(max_replicas: int = 3, wave_size: int = 8,
                max_new: int = 3, seed: int = 42,
                inject: bool = True, deadline_s: float = 600.0,
                ttft_budget_ms: float = 30000.0) -> Dict:
    """The ``--control`` leg: a load-ramp soak of the SLO-driven
    control plane (``fleet.control`` + ``fleet.admission`` +
    ``fleet.deploy``) with actuator faults injected at every new
    faultpoint.

    One incumbent replica starts; a two-tenant synthetic burst ramps
    (gold: weight 3, priority 1, unmetered; bronze: weight 1,
    priority 0, metered budget) and the
    :class:`~bigdl_tpu.fleet.control.Autoscaler` is ticked between
    waves. Proven, in order:

    1. **scale 1→N**: replicas reach ``max_replicas`` under the ramp
       with the FIRST spawn actuation aborted by an injected
       ``fleet/spawn`` fault (retried next tick — reconciled against
       ``fleet/control/spawn_aborted``); every spawn is
       warm-before-join; scale-up reaction time is measured;
    2. **mid-ramp kill absorbed**: an injected ``fleet/replica``
       fault kills one autoscaled replica under traffic — the router
       evicts and re-routes, nothing hangs;
    3. **N→1**: traffic stops and the scaler drains back to one
       replica, the FIRST drain actuation aborted by an injected
       ``fleet/drain`` fault (reconciled against
       ``fleet/control/drain_aborted``);
    4. **poisoned canary auto-rollback**: a full
       :class:`~bigdl_tpu.fleet.deploy.DeployPipeline` runs with a
       fault killing the canary replica inside its own probe window —
       the deploy lands ``rolled_back`` with the incumbent fleet
       untouched and still serving.

    Throughout: overload is only ever a TYPED shed attributable per
    tenant (host-side typed counts must equal the
    ``fleet/admission/shed`` counter), zero streams hang, and every
    injected fault reconciles counter-for-counter against its
    recovery counter. ``inject=False`` runs the same ramp fault-free
    (the clean control)."""
    import numpy as np

    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu import faults
    from bigdl_tpu.fleet import (AdmissionController, Autoscaler,
                                 BudgetExhausted, DeployPipeline,
                                 FleetRouter, ScalePolicy,
                                 build_replicas)
    from bigdl_tpu.precision.gate import AccuracyGate
    from bigdl_tpu.serving import Degraded, QueueFull
    from bigdl_tpu.telemetry import slo as slo_mod
    from bigdl_tpu.tools.deploy import build_model, replica_factory
    from bigdl_tpu.tools.synthetic import seeded_rng
    from bigdl_tpu.utils.profiling import percentile_summary

    report: Dict = {"max_replicas": max_replicas,
                    "wave_size": wave_size, "inject": inject,
                    "violations": []}
    metrics = telemetry.MetricsRegistry()
    router = FleetRouter(build_replicas(1, seed=seed, max_queue=4,
                                        metrics=metrics),
                         metrics=metrics)
    r = seeded_rng(seed + 1)
    prompts = [r.randint(1, 31, 3).astype(np.int32) for _ in range(4)]
    policy = ScalePolicy(min_replicas=1, max_replicas=max_replicas,
                         up_load=2.0, down_load=0.5,
                         up_cooldown_s=0.05, down_cooldown_s=0.05,
                         warm_prompts=[prompts[0]])
    scaler = Autoscaler(
        router, lambda name: replica_factory(
            name, build_model(seed), metrics=metrics),
        policy=policy, metrics=metrics)
    adm = AdmissionController(router, metrics=metrics,
                              saturation_load=2.0, fairness_slack=8.0)
    adm.register("gold", weight=3.0, priority=1)
    adm.register("bronze", weight=1.0, priority=0, rate=2.0, burst=6.0)

    c_ups = metrics.counter("fleet/control/scale_ups")
    c_evict = metrics.counter("fleet/replica/evictions")
    injected = {"fleet/spawn": 0, "fleet/drain": 0, "fleet/replica": 0}
    sheds: Dict[str, Dict[str, int]] = \
        {"gold": {}, "bronze": {}}
    requests = {"gold": 0, "bronze": 0}
    resolved = {"ok": 0, "typed_errors": 0, "hung": 0}
    ttfts: List[float] = []
    tokens_out = 0
    replicas_path: List[int] = [1]
    reaction_ms = None
    t_total = time.monotonic()

    def serving() -> int:
        return sum(1 for rep in router.replicas()
                   if rep.state == "serving")

    def ramp_to(target: int, timeout_s: float = 120.0) -> None:
        """Sustained two-tenant burst (pump threads, soak idiom) while
        the main thread ticks the scaler, until ``target`` replicas
        serve or the deadline passes. Sheds stay typed per tenant;
        every accepted stream is resolved afterwards — zero hangs."""
        nonlocal tokens_out, reaction_ms
        stop = threading.Event()
        streams: List = []
        lock = threading.Lock()

        def pump(tenant: str, k: int) -> None:
            i = k
            while not stop.is_set():
                i += 1
                with lock:
                    requests[tenant] += 1
                try:
                    s = adm.submit(prompts[i % len(prompts)],
                                   tenant=tenant,
                                   max_new_tokens=max_new)
                    with lock:
                        streams.append(s)
                except (BudgetExhausted, QueueFull, Degraded) as e:
                    kind = type(e).__name__
                    with lock:
                        sheds[tenant][kind] = \
                            sheds[tenant].get(kind, 0) + 1
                    time.sleep(0.002)  # shed fast, retry soon
                except Exception as e:  # untyped shed = violation
                    with lock:
                        report["violations"].append(
                            f"UNTYPED shed for tenant {tenant!r}: "
                            f"{type(e).__name__}: {e}")

        workers = [threading.Thread(
            target=pump, args=(t, k), daemon=True,
            name=f"chaos-control-{t}-{k}")
            for t in ("gold", "bronze") for k in range(2)]
        for w in workers:
            w.start()
        try:
            end = time.monotonic() + timeout_s
            while serving() < target and time.monotonic() < end:
                scaler.step()
                if reaction_ms is None and c_ups.total() > 0:
                    reaction_ms = \
                        (time.monotonic() - t_ramp) * 1000.0
                time.sleep(0.02)
        finally:
            stop.set()
            for w in workers:
                w.join(timeout=30.0)
        end = time.monotonic() + 120.0
        for s in streams:
            try:
                out = s.result(timeout=max(0.0,
                                           end - time.monotonic()))
                resolved["ok"] += 1
                tokens_out += len(out)
                if s.ttft_ms is not None:
                    ttfts.append(s.ttft_ms)
            except FutTimeout:
                resolved["hung"] += 1
            except Exception:
                resolved["typed_errors"] += 1
        replicas_path.append(serving())
        if serving() < target:
            report["violations"].append(
                f"ramp stalled at {serving()} replicas "
                f"(target {target})")

    from concurrent.futures import TimeoutError as FutTimeout
    try:
        # -- phase 1: ramp up, first spawn actuation sabotaged --------
        t_ramp = time.monotonic()
        sched = faults.arm("fleet/spawn=nth:1,raise:RuntimeError") \
            if inject else _NO_FAULTS
        try:
            ramp_to(2)
        finally:
            injected["fleet/spawn"] += sched.fired().get(
                "fleet/spawn", 0)
            if inject:
                faults.disarm()

        # -- phase 2: mid-ramp kill of an autoscaled replica ----------
        victims = [rep.name for rep in router.replicas()
                   if rep.name.startswith("auto-")
                   and rep.state == "serving"]
        if victims and inject:
            victim = victims[0]
            sched = faults.arm(
                f"fleet/replica=nth:1,raise:RuntimeError,"
                f"match:replica={victim}")
            try:
                router._sessions["kill-sess"] = victim
                streams = []
                for i in range(wave_size):
                    try:
                        streams.append(router.submit(
                            prompts[i % len(prompts)],
                            session="kill-sess",
                            max_new_tokens=max_new))
                    except (QueueFull, Degraded):
                        pass
                _await_deterministic_rules(sched, ("fleet/replica",),
                                           timeout_s=15.0)
                end = time.monotonic() + 120.0
                for s in streams:
                    try:
                        out = s.result(timeout=max(
                            0.0, end - time.monotonic()))
                        resolved["ok"] += 1
                        tokens_out += len(out)
                    except FutTimeout:
                        resolved["hung"] += 1
                    except Exception:
                        resolved["typed_errors"] += 1
            finally:
                injected["fleet/replica"] += sched.fired().get(
                    "fleet/replica", 0)
                faults.disarm()
            report["killed_replica"] = victim
            replicas_path.append(serving())
        elif inject:
            report["violations"].append(
                "ramp produced no autoscaled replica to kill")

        # -- phase 3: keep ramping to max_replicas (fault-free) -------
        if serving() < max_replicas:
            ramp_to(max_replicas)
        if max(replicas_path) < max_replicas:
            report["violations"].append(
                f"fleet never reached max_replicas={max_replicas} "
                f"under the ramp (path: {replicas_path})")
        if reaction_ms is None:
            report["violations"].append(
                "the autoscaler never scaled up under the ramp")

        # -- phase 4: traffic stops; drain back down to 1, first
        #    drain actuation sabotaged ------------------------------
        sched = faults.arm("fleet/drain=nth:1,raise:RuntimeError") \
            if inject else _NO_FAULTS
        try:
            end = time.monotonic() + 60.0
            while serving() > 1 and time.monotonic() < end:
                scaler.step()
                time.sleep(0.06)
        finally:
            injected["fleet/drain"] += sched.fired().get(
                "fleet/drain", 0)
            if inject:
                faults.disarm()
        replicas_path.append(serving())
        if serving() != 1:
            report["violations"].append(
                f"fleet did not scale back down to 1 "
                f"(still {serving()} serving)")

        # -- phase 5: poisoned canary deploy must auto-rollback -------
        rng = np.random.default_rng(seed)
        pipe = DeployPipeline(
            router, train_fn=lambda: build_model(seed),
            replica_factory=lambda n, m: replica_factory(
                n, m, metrics=metrics),
            gate=AccuracyGate(rng.integers(1, 16, size=(8, 4)).astype(
                np.int32)),
            canary_fraction=0.5, canary_requests=6, seed=seed,
            metrics=metrics)
        sched = faults.arm(
            f"fleet/replica=nth:1,raise:RuntimeError,"
            f"match:replica=canary-{seed}") if inject else _NO_FAULTS
        try:
            deploy_report = pipe.run()
        finally:
            injected["fleet/replica"] += sched.fired().get(
                "fleet/replica", 0)
            if inject:
                faults.disarm()
        report["deploy"] = {"state": deploy_report["state"],
                            "reason": deploy_report.get("reason")}
        if inject and deploy_report["state"] != "rolled_back":
            report["violations"].append(
                f"poisoned canary deploy landed "
                f"{deploy_report['state']!r}, expected rolled_back")
        if not inject and deploy_report["state"] != "done":
            report["violations"].append(
                f"clean deploy landed {deploy_report['state']!r}, "
                f"expected done")
        # the incumbent must still be serving after the rollback
        try:
            router.submit(prompts[0], max_new_tokens=2).result(60)
        except Exception as e:
            report["violations"].append(
                f"incumbent not serving after canary rollback: "
                f"{type(e).__name__}: {e}")

        # -- invariants: typed-only sheds, zero hangs, reconciliation
        if resolved["hung"]:
            report["violations"].append(
                f"{resolved['hung']} streams never resolved")
        recovered = {
            "spawn_aborted": int(metrics.counter(
                "fleet/control/spawn_aborted").total()),
            "drain_aborted": int(metrics.counter(
                "fleet/control/drain_aborted").total()),
            "evictions": int(c_evict.total()),
        }
        report["injected"] = dict(injected)
        report["recovered"] = recovered
        if injected["fleet/spawn"] != recovered["spawn_aborted"]:
            report["violations"].append(
                f"injected {injected['fleet/spawn']} spawn faults but "
                f"counted {recovered['spawn_aborted']} spawn_aborted")
        if injected["fleet/drain"] != recovered["drain_aborted"]:
            report["violations"].append(
                f"injected {injected['fleet/drain']} drain faults but "
                f"counted {recovered['drain_aborted']} drain_aborted")
        if injected["fleet/replica"] != recovered["evictions"]:
            report["violations"].append(
                f"injected {injected['fleet/replica']} replica kills "
                f"but the router evicted {recovered['evictions']}")
        shed_host = sum(sum(d.values()) for d in sheds.values())
        shed_counted = int(metrics.counter(
            "fleet/admission/shed").total())
        if shed_host != shed_counted:
            report["violations"].append(
                f"{shed_host} typed sheds seen by callers but "
                f"{shed_counted} counted — sheds must be attributable")
        report["tenants"] = {
            name: {"requests": requests[name],
                   "sheds": dict(sheds[name]),
                   "shed_fraction": round(
                       sum(sheds[name].values())
                       / max(1, requests[name]), 3)}
            for name in sheds}
        report["burst"] = resolved
        report["replicas_path"] = replicas_path
        report["scaleup_reaction_ms"] = \
            None if reaction_ms is None else round(reaction_ms, 1)
        wall = time.monotonic() - t_total
        report["goodput_tokens_per_sec"] = round(
            tokens_out / max(wall, 1e-9), 3)
        obs = {"control_goodput_tokens_per_sec":
               report["goodput_tokens_per_sec"]}
        obs.update({f"ramp_ttft_ms_{k}": round(v, 3)
                    for k, v in percentile_summary(
                        ttfts, (50, 99)).items()})
        report["latency"] = {k: v for k, v in obs.items()
                            if k.startswith("ramp_")}
        spec = slo_mod.SloSpec.parse(
            f"p99_ttft: ramp_ttft_ms_p99 <= {ttft_budget_ms:g} "
            f"default 0")
        slo_report = slo_mod.evaluate(spec, None, obs)
        report["slo"] = slo_report.to_dict()
        report["violations"].extend(
            "SLO breach: " + v.describe()
            for v in slo_report.verdicts if not v.ok)
    finally:
        scaler.stop()
        router.shutdown(drain=True)
    report["passed"] = not report["violations"]
    return report


# ----------------------------------------------------------- the soak

def _corrupt_latest(ckpt_dir: str) -> str:
    """Truncate the latest checkpoint's params.npz BEHIND its MANIFEST
    — the classic bit-rot artifact: the completeness certificate says
    done, the bytes say otherwise. Only integrity verification can
    catch it."""
    from bigdl_tpu.utils.serialization import find_latest_checkpoint
    latest = find_latest_checkpoint(ckpt_dir)
    if latest is None:
        raise RuntimeError(f"no checkpoint to corrupt under {ckpt_dir}")
    npz = os.path.join(latest, "params.npz")
    with open(npz, "r+b") as f:
        f.truncate(max(0, os.path.getsize(npz) // 2))
    return latest


def run_soak(model: str = "lenet", steps: int = 16, leg_a: int = 8,
             ckpt_every: int = 2, batch_size: int = 8, seed: int = 42,
             schedule: str = DEFAULT_SCHEDULE,
             kill_at: Optional[int] = None,
             workdir: Optional[str] = None) -> Dict:
    """Run the full soak (module docstring has the phases); returns the
    report dict (key ``"passed"`` is the verdict)."""
    import bigdl_tpu.telemetry as telemetry
    from bigdl_tpu import faults

    own_workdir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="bigdl-chaos-")
    ckpt_dir = os.path.join(workdir, "ckpts")
    report: Dict = {"model": model, "steps": steps, "leg_a": leg_a,
                    "seed": seed, "schedule": schedule,
                    "kill_at": kill_at, "violations": []}
    try:
        # -- phase 1: undisturbed reference ---------------------------
        ref_opt = _train_leg(model, seed, batch_size, steps, None, 0)
        p_ref = _final_params(ref_opt)

        # -- phase 2: chaos leg A to leg_a steps ----------------------
        if kill_at is not None:
            # subprocess leg: SIGKILL mid-checkpoint-write (after the
            # tree files, before the MANIFEST) at neval kill_at...
            r = _spawn_worker(
                model, seed, batch_size, leg_a, ckpt_dir, ckpt_every,
                f"ckpt/write_manifest=match:neval={kill_at},sigkill")
            if r.returncode != -9:
                report["violations"].append(
                    f"kill leg exited rc={r.returncode} (want -9); "
                    f"stderr tail: {r.stderr[-300:]}")
            # ...and the relaunched gang must resume past the torn tmp
            # dir and finish the leg
            r2 = _spawn_worker(model, seed, batch_size, leg_a, ckpt_dir,
                               ckpt_every, "")
            if r2.returncode != 0:
                report["violations"].append(
                    f"resume leg failed rc={r2.returncode}; stderr "
                    f"tail: {r2.stderr[-300:]}")
            report["kill"] = {"injected_sigkills": 1,
                              "resumes": 1 if r2.returncode == 0 else 0}
        else:
            _train_leg(model, seed, batch_size, leg_a, ckpt_dir,
                       ckpt_every)

        # -- phase 3: corrupt the latest checkpoint -------------------
        corrupted = _corrupt_latest(ckpt_dir)
        report["corrupted"] = corrupted

        # -- phase 4: chaos leg B — resume under the fault schedule
        # with a concurrent serving burst ----------------------------
        rec_counter = telemetry.counter("train/optimizer/recoveries")
        io_counter = telemetry.counter("io/retry/retries")
        rec0, io0 = rec_counter.value(), io_counter.value()
        burst = _Burst(seed)
        gen_burst = _GenBurst(seed)
        sched = faults.arm(schedule)
        try:
            burst.start()
            gen_burst.start()
            leg_b = _train_leg(model, seed, batch_size, steps, ckpt_dir,
                               ckpt_every)
            # the background bursts may need a little longer than the
            # training leg to reach their scheduled nth triggers
            _await_deterministic_rules(
                sched, ("serving/dispatch", "serving/take_batch",
                        "serving/decode"), timeout_s=15.0)
        finally:
            faults.disarm()
            futures = burst.finish()
            gen_streams = gen_burst.finish()
        p_chaos = _final_params(leg_b)

        # -- invariant 1: bit-exactness -------------------------------
        same, bad = _params_equal(p_ref, p_chaos)
        report["bit_identical"] = same
        if not same:
            report["violations"].append(
                f"final params differ from the undisturbed run: {bad}")

        # -- invariant 2: quarantine + fallback actually happened -----
        quarantined = [n for n in os.listdir(ckpt_dir)
                       if ".corrupt-" in n]
        report["quarantined"] = quarantined
        if not quarantined:
            report["violations"].append(
                "corrupt checkpoint was not quarantined")

        # -- invariant 3: no serving future hangs ---------------------
        report["burst"] = futures
        report["burst_stats"] = {
            k: v for k, v in burst.stats().items()
            if k in ("request_count", "errors", "shed", "timed_out",
                     "worker_restarts", "shed_seen_by_submitters")}
        if futures["hung"]:
            report["violations"].append(
                f"{futures['hung']} serving futures never resolved")
        report["gen_burst"] = gen_streams
        gen_metrics = gen_burst.stats()
        report["gen_burst_stats"] = {
            k: gen_metrics[k] for k in ("request_count", "tokens",
                                        "finished", "worker_restarts",
                                        "timed_out")}
        if gen_streams["hung"]:
            report["violations"].append(
                f"{gen_streams['hung']} generation token streams never "
                "resolved")

        # -- invariant 4: injected == recovered, counter for counter --
        fired = {}
        for rule in sched.rules:
            if rule.action not in ("raise", "sigkill"):
                continue
            fired[rule.point] = fired.get(rule.point, 0) + rule.fired
            if rule.fired == 0 and rule.prob is None:
                # a deterministic rule that never fired means the soak
                # exercised nothing at that point — reconciling 0 == 0
                # would pass vacuously (seeded-prob rules MAY land on
                # zero; that is their contract)
                report["violations"].append(
                    f"scheduled fault never fired: {rule!r}")
        svc_metrics = burst.svc.metrics("chaos")
        observed = {
            "train/step": rec_counter.value() - rec0,
            "serving/dispatch": svc_metrics["failed_batches"],
            "serving/take_batch": svc_metrics["worker_restarts"],
            "serving/decode": gen_metrics["worker_restarts"],
            "fetch/download": io_counter.value() - io0,
        }
        report["injected"] = fired
        report["recovered"] = {k: int(v) for k, v in observed.items()}
        for point, n in fired.items():
            got = int(observed.get(point, 0))
            if got != n:
                report["violations"].append(
                    f"{point}: injected {n} faults but observed {got} "
                    "recoveries")
    finally:
        if own_workdir:
            shutil.rmtree(workdir, ignore_errors=True)
    report["passed"] = not report["violations"]
    return report


# ------------------------------------------------------------------ CLI

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m bigdl_tpu.tools.chaos", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model", choices=("lenet", "tiny"), default="lenet")
    ap.add_argument("--steps", type=int, default=16,
                    help="total training iterations of each run")
    ap.add_argument("--leg-a", type=int, default=8,
                    help="iterations of the pre-corruption chaos leg")
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--schedule", default=DEFAULT_SCHEDULE,
                    help="leg-B fault schedule (faults.parse_schedule "
                         "syntax)")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="run leg A as a subprocess SIGKILLed "
                         "mid-checkpoint-write at this neval")
    ap.add_argument("--workdir", default=None,
                    help="keep work files here instead of a temp dir")
    ap.add_argument("--json", action="store_true")
    # fleet leg: kill one generation replica mid-burst, assert typed
    # resolution / re-route, eviction reconciliation, bit-identity
    ap.add_argument("--fleet", action="store_true",
                    help="run the replica-fleet chaos leg instead of "
                         "the training soak (bigdl_tpu.fleet router)")
    ap.add_argument("--fleet-replicas", type=int, default=3)
    ap.add_argument("--fleet-requests", type=int, default=18)
    ap.add_argument("--fleet-schedule", default=DEFAULT_FLEET_SCHEDULE,
                    help="fleet-leg fault schedule (the fleet/replica "
                         "point kills the matched replica); 'none' "
                         "runs the fault-free control leg")
    ap.add_argument("--fleet-out", default=None,
                    help="fleet-leg artifact directory (per-replica "
                         "snapshots, merged Perfetto trace, SLO "
                         "report); default: a temp dir, printed")
    ap.add_argument("--slo", default=None,
                    help="override the fleet leg's SloSpec, e.g. "
                         "'evictions: fleet/replica/evictions <= 0 "
                         "default 0; p99: serving/generation/"
                         "ttft_ms.p99 <= 5000 default 0'")
    # control-plane leg: load-ramp autoscale 1->N->1 with actuator
    # faults, mid-ramp replica kill, poisoned-canary auto-rollback
    ap.add_argument("--control", action="store_true",
                    help="run the control-plane chaos leg (autoscaler "
                         "ramp + admission sheds + canary rollback)")
    ap.add_argument("--control-max-replicas", type=int, default=3)
    ap.add_argument("--control-wave-size", type=int, default=8,
                    help="burst size of the mid-ramp kill wave")
    ap.add_argument("--control-no-inject", action="store_true",
                    help="run the same ramp fault-free (the clean "
                         "control: expects a done deploy, no aborts)")
    # host-kill leg: SIGKILL a whole tools/launch gang host mid-window,
    # relaunch at a different world size, assert elastic recovery
    ap.add_argument("--hostkill", action="store_true",
                    help="run the multi-process host-kill leg instead "
                         "of the in-process soak (capability-probed; "
                         "skips on runtimes without multiprocess CPU "
                         "collectives)")
    ap.add_argument("--hk-nproc", type=int, default=2,
                    help="gang A processes (the host that dies)")
    ap.add_argument("--hk-devices", type=int, default=2,
                    help="virtual CPU devices per gang-A process")
    ap.add_argument("--hk-relaunch-nproc", type=int, default=1,
                    help="relaunch world size (different from "
                         "--hk-nproc = the elastic resume under test)")
    ap.add_argument("--hk-relaunch-devices", type=int, default=2,
                    help="virtual CPU devices per relaunch process")
    ap.add_argument("--kill-after-commits", type=int, default=1,
                    help="SIGKILL the gang once this many async "
                         "checkpoints have COMMITTED")
    # internal: subprocess leg entries
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--hostkill-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--save-params", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--step-delay-ms", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--async-ckpt", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.fleet:
        from bigdl_tpu.telemetry import slo as slo_mod
        spec = slo_mod.SloSpec.parse(args.slo) if args.slo else None
        schedule = None if args.fleet_schedule in ("none", "") \
            else args.fleet_schedule
        report = run_fleet(replicas=args.fleet_replicas,
                           requests=args.fleet_requests,
                           seed=args.seed, schedule=schedule,
                           out_dir=args.fleet_out, slo_spec=spec)
        if args.json:
            print(json.dumps(report, indent=2, default=str))
        else:
            print("== chaos fleet leg ==")
            print(f"replicas={report['replicas']} "
                  f"requests={report['requests']}")
            print(f"burst:     {report.get('burst')}")
            print(f"injected:  {report.get('injected')} "
                  f"recovered: {report.get('recovered')}")
            print(f"states:    {report.get('states')}")
            print(f"bit-identical greedy outputs: "
                  f"{report.get('bit_identical')}")
            slo = report.get("slo") or {}
            print(f"slo:       breached={slo.get('breached')} "
                  f"breach_detected="
                  f"{report.get('slo_breach_detected')}")
            art = report.get("artifacts") or {}
            print(f"artifacts: merged trace {art.get('trace')}  "
                  f"slo {art.get('slo')}")
            for v in report["violations"]:
                print(f"VIOLATION: {v}")
            print("PASS" if report["passed"] else "FAIL")
        return 0 if report["passed"] else 1
    if args.control:
        report = run_control(max_replicas=args.control_max_replicas,
                             wave_size=args.control_wave_size,
                             seed=args.seed,
                             inject=not args.control_no_inject)
        if args.json:
            print(json.dumps(report, indent=2, default=str))
        else:
            print("== chaos control-plane leg ==")
            print(f"replicas path: {report['replicas_path']}  "
                  f"(max {report['max_replicas']})")
            print(f"scale-up reaction: "
                  f"{report.get('scaleup_reaction_ms')} ms  "
                  f"goodput: {report.get('goodput_tokens_per_sec')} "
                  f"tok/s")
            print(f"burst:     {report.get('burst')}  "
                  f"latency: {report.get('latency')}")
            print(f"injected:  {report.get('injected')} "
                  f"recovered: {report.get('recovered')}")
            print(f"tenants:   {report.get('tenants')}")
            print(f"kill:      {report.get('killed_replica')}  "
                  f"deploy: {report.get('deploy')}")
            for v in report["violations"]:
                print(f"VIOLATION: {v}")
            print("PASS" if report["passed"] else "FAIL")
        return 0 if report["passed"] else 1
    if args.hostkill_worker:
        if not args.ckpt_dir:
            print("--hostkill-worker needs --ckpt-dir", file=sys.stderr)
            return 2
        return _run_hostkill_worker(args)
    if args.hostkill:
        report = run_hostkill(
            model=args.model, steps=args.steps,
            ckpt_every=args.ckpt_every, batch_size=args.batch_size,
            seed=args.seed, nproc=args.hk_nproc,
            cpu_devices=args.hk_devices,
            relaunch_nproc=args.hk_relaunch_nproc,
            relaunch_cpu_devices=args.hk_relaunch_devices,
            kill_after_commits=args.kill_after_commits,
            workdir=args.workdir)
        if args.json:
            print(json.dumps(report, indent=2, default=str))
        elif report.get("skipped"):
            print(f"SKIPPED: {report['skipped']}")
        else:
            print("== chaos host-kill leg ==")
            print(f"gang A: {report.get('gang_a')}")
            print(f"relaunch: {report.get('gang_b')}")
            print(f"injected={report.get('injected')} "
                  f"recovered={report.get('recovered')}")
            print(f"params_max_err={report.get('params_max_err')} "
                  f"bit_identical={report.get('bit_identical')}")
            print(f"postmortem snapshots: "
                  f"{report.get('postmortem_snapshots')}  "
                  f"stragglers: {report.get('stragglers')}")
            slo = report.get("slo") or {}
            print(f"slo: breached={slo.get('breached')}")
            for v in report["violations"]:
                print(f"VIOLATION: {v}")
            print("PASS" if report["passed"] else "FAIL")
        return 0 if report["passed"] else 1
    if args.worker:
        if not args.ckpt_dir:
            print("--worker needs --ckpt-dir", file=sys.stderr)
            return 2
        return _run_worker(args)
    if args.leg_a >= args.steps:
        print("--leg-a must be < --steps", file=sys.stderr)
        return 2
    if args.kill_at is not None \
            and (not 0 < args.kill_at <= args.leg_a
                 or args.kill_at % args.ckpt_every):
        print("--kill-at must fall inside leg A on a checkpoint step "
              "(a multiple of --ckpt-every): the SIGKILL fires "
              "mid-checkpoint-write, so a non-checkpoint neval never "
              "kills", file=sys.stderr)
        return 2

    report = run_soak(model=args.model, steps=args.steps,
                      leg_a=args.leg_a, ckpt_every=args.ckpt_every,
                      batch_size=args.batch_size, seed=args.seed,
                      schedule=args.schedule, kill_at=args.kill_at,
                      workdir=args.workdir)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print("== chaos soak ==")
        print(f"model={report['model']} steps={report['steps']} "
              f"seed={report['seed']} kill_at={report['kill_at']}")
        print(f"injected:  {report.get('injected')}")
        print(f"recovered: {report.get('recovered')}")
        print(f"burst:     {report.get('burst')} "
              f"{report.get('burst_stats')}")
        print(f"gen burst: {report.get('gen_burst')} "
              f"{report.get('gen_burst_stats')}")
        print(f"bit-identical final params: "
              f"{report.get('bit_identical')}")
        print(f"quarantined: {report.get('quarantined')}")
        for v in report["violations"]:
            print(f"VIOLATION: {v}")
        print("PASS" if report["passed"] else "FAIL")
    return 0 if report["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
