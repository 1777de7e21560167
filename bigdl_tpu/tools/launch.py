"""Process launcher — the spark-submit/torchrun role for multi-process
training (reference: Engine.scala:93-137 derived topology from the
Spark conf that spark-submit provided; here a small launcher provides
the same contract through JAX's standard env vars).

Single host, N processes (testing / CPU pods):

    python -m bigdl_tpu.tools.launch --nproc 2 train.py --epochs 5

Multi-host (run once per host):

    python -m bigdl_tpu.tools.launch --nproc 1 \
        --coordinator host0:12345 --nnodes 4 --node-rank 2 train.py

Each worker gets JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID, so ``Engine.init_distributed()`` (no arguments) brings
the mesh up. The launcher streams worker output with a ``[rank]``
prefix and exits non-zero if any worker fails.

Fault tolerance, two classified layers (both feed the typed per-process
exit reports ``run_gang`` returns — a :class:`GangResult` of
:class:`ProcExit`, never a bare join):

- **startup failures** (``--start-retries``, default 3): a worker that
  dies during the ``--startup-grace`` window with rendezvous-shaped
  output (bind conflict, ``jax.distributed`` initialize timeout /
  UNAVAILABLE) poisons only the bring-up — the whole gang is killed
  and restarted through ``faults.retry.retry_call`` (classified,
  exponential backoff + jitter) on a FRESH coordinator port, because
  the dead coordinator's socket may linger in TIME_WAIT. A user-pinned
  ``--coordinator`` is kept (every host must agree on it); the backoff
  still spaces the retries out.
- **runtime failures** (``--max-restarts``): a dead worker poisons the
  whole gang — its peers hang or fail in the next collective, and a
  JAX distributed client cannot re-join a live job — so recovery is
  GANG restart (the torchrun/elastic model, and the multi-process form
  of the reference's retry-from-checkpoint loop,
  DistriOptimizer.scala:789-855): kill the survivors, pick a fresh
  port, relaunch everyone, and let each worker's ``Optimizer`` resume
  from its latest checkpoint — with elastic (format-3) checkpoints,
  even at a DIFFERENT world size (``bigdl_tpu.elastic``).

Workers see ``BIGDL_RESTART_ATTEMPT`` so tests can script failures on
the first incarnation only (the reference's ExceptionTest pattern,
test/.../utils/TestUtils.scala:103-131). ``tools.chaos --hostkill``
drives :func:`run_gang` programmatically with a ``monitor`` hook to
SIGKILL a whole gang mid-window and assert elastic recovery.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import re
import signal as _signal
import socket
import subprocess
import sys
import threading
import time
from typing import Callable, List, Optional


class GangStartupError(RuntimeError):
    """The gang died during bring-up with rendezvous-shaped output
    (bind / ``jax.distributed`` initialize failure). Classified
    TRANSIENT (``RuntimeError``) so ``faults.retry.retry_call`` retries
    the start with a fresh coordinator port + backoff."""


#: worker-output shapes that mark a bring-up death as a rendezvous /
#: coordinator failure rather than an application bug (a fast app
#: crash stays a RUNTIME failure — retrying its port fixes nothing)
_STARTUP_RE = re.compile(
    r"UNAVAILABLE|DEADLINE_EXCEEDED|Address already in use|"
    r"coordinat|distributed\.initialize|barrier timed out|"
    r"Failed to connect", re.IGNORECASE)


@dataclasses.dataclass
class ProcExit:
    """One worker's typed exit report.

    ``kind`` — ``"ok"`` (rc 0), ``"startup"`` (died in the grace
    window with rendezvous-shaped output), ``"killed"`` (died by
    signal — SIGKILL'd hosts land here), ``"runtime"`` (any other
    nonzero exit). ``signal`` names the killing signal when rc < 0.
    ``output_tail`` keeps the last worker output for diagnostics."""

    rank: int
    returncode: Optional[int]
    kind: str
    signal: Optional[str] = None
    attempt: int = 0
    output_tail: str = ""


@dataclasses.dataclass
class GangResult:
    """What a whole ``run_gang`` run did: the final gang's per-process
    reports, restarts consumed at both layers, the coordinator the
    last attempt used, and — on failure — the ``culprit``: the worker
    whose death triggered the gang teardown (the survivors the
    launcher itself then put down report kind=killed, which must not
    be blamed)."""

    reports: List[ProcExit]
    ok: bool
    restarts: int = 0
    start_retries: int = 0
    coordinator: str = ""
    culprit: Optional[ProcExit] = None

    def failed(self) -> List[ProcExit]:
        """The non-ok reports of the final gang."""
        return [r for r in self.reports if r.kind != "ok"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _signame(rc: int) -> Optional[str]:
    if rc is None or rc >= 0:
        return None
    try:
        return _signal.Signals(-rc).name
    except ValueError:
        return f"signal {-rc}"


class _Worker:
    """One spawned worker + its output-streaming thread (which also
    keeps a bounded tail for exit classification/reports)."""

    def __init__(self, rank: int, proc: subprocess.Popen):
        self.rank = rank
        self.proc = proc
        self.tail: collections.deque = collections.deque(maxlen=80)
        self.thread = threading.Thread(target=self._stream, daemon=True)
        self.thread.start()

    def _stream(self):
        for line in iter(self.proc.stdout.readline, ""):
            self.tail.append(line)
            sys.stdout.write(f"[{self.rank}] {line}")
            sys.stdout.flush()

    def tail_text(self) -> str:
        return "".join(self.tail)[-4000:]


def _launch_gang(args, coord: str, attempt: int) -> List[_Worker]:
    total = args.nproc * args.nnodes
    workers = []
    for local in range(args.nproc):
        rank = args.node_rank * args.nproc + local
        env = dict(os.environ)
        env.update(getattr(args, "extra_env", None) or {})
        env["JAX_COORDINATOR_ADDRESS"] = coord
        env["JAX_NUM_PROCESSES"] = str(total)
        env["JAX_PROCESS_ID"] = str(rank)
        env["BIGDL_RESTART_ATTEMPT"] = str(attempt)
        if getattr(args, "ship_telemetry", None):
            # every worker ships identity-stamped snapshots into one
            # directory; telemetry.agg merges them fleet-wide
            env["BIGDL_TELEMETRY_SHIP_DIR"] = args.ship_telemetry
            env["BIGDL_FLIGHT_DIR"] = os.path.join(
                args.ship_telemetry, "flight")
        if args.cpu_devices:
            # a chip belongs to ONE process: N workers on one host
            # cannot each open it, so a local gang runs on virtual CPU
            # devices (on a pod, one worker per host owns that host's
            # chips and this flag stays off)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count="
                  f"{args.cpu_devices}").strip()
        p = subprocess.Popen(
            [sys.executable, args.script] + args.script_args,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        workers.append(_Worker(rank, p))
    return workers


def kill_gang(workers: List[_Worker], sig: Optional[int] = None) -> None:
    """Put a gang down: SIGTERM + bounded wait + SIGKILL (the default),
    or deliver ``sig`` (e.g. ``signal.SIGKILL`` for the chaos host-kill
    leg) to every live worker immediately."""
    if sig is not None:
        for w in workers:
            if w.proc.poll() is None:
                try:
                    os.kill(w.proc.pid, sig)
                except OSError:
                    pass
        for w in workers:
            try:
                w.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        return
    for w in workers:
        if w.proc.poll() is None:
            w.proc.terminate()
    deadline = time.time() + 10
    for w in workers:
        while w.proc.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if w.proc.poll() is None:
            w.proc.kill()
            w.proc.wait()


def _join_threads(workers: List[_Worker]) -> None:
    for w in workers:
        w.thread.join(timeout=5)


def _reports(workers: List[_Worker], attempt: int,
             kind_for: Callable[[_Worker, int], str]) -> List[ProcExit]:
    out = []
    for w in workers:
        rc = w.proc.poll()
        out.append(ProcExit(rank=w.rank, returncode=rc,
                            kind=kind_for(w, rc), signal=_signame(rc),
                            attempt=attempt, output_tail=w.tail_text()))
    return out


def _start_gang(args, attempt: int, counters: dict,
                monitor=None) -> tuple:
    """One bring-up attempt: launch, then watch the ``--startup-grace``
    window. A worker dying nonzero inside it with rendezvous-shaped
    output kills the gang and raises :class:`GangStartupError` (the
    transient ``retry_call`` retries on a fresh port); an app-shaped
    fast death falls through to the runtime path. ``monitor`` runs on
    every poll tick here too — a fast gang must not be invisible to
    the chaos host-kill hook just because it finished inside the
    grace window."""
    coord = args.coordinator or f"127.0.0.1:{_free_port()}"
    counters["coordinator"] = coord
    workers = _launch_gang(args, coord, attempt)
    deadline = time.time() + args.startup_grace
    while time.time() < deadline:
        if monitor is not None:
            monitor(workers)
        rcs = [w.proc.poll() for w in workers]
        bad = [(w, rc) for w, rc in zip(workers, rcs)
               if rc is not None and rc != 0]
        if bad:
            w, rc = bad[0]
            time.sleep(0.3)  # let the tail drain before classifying
            if _STARTUP_RE.search(w.tail_text() or ""):
                culprit_rank = w.rank
                kill_gang(workers)
                _join_threads(workers)
                counters["start_retries"] += 1

                def startup_kind(wk, wrc):
                    # only the worker whose rendezvous-shaped death
                    # triggered the teardown is a startup failure; the
                    # survivors the launcher just put down are "killed"
                    if wrc == 0:
                        return "ok"
                    if wk.rank == culprit_rank:
                        return "startup"
                    return "killed" if wrc is not None and wrc < 0 \
                        else "runtime"

                counters["last_reports"] = _reports(workers, attempt,
                                                    startup_kind)
                raise GangStartupError(
                    f"worker {w.rank} died rc={rc} during the "
                    f"{args.startup_grace:.0f}s startup grace with "
                    "rendezvous-shaped output; retrying the gang on a "
                    "fresh coordinator port")
            return coord, workers  # app failure: runtime path owns it
        if all(rc == 0 for rc in rcs):
            break  # the whole gang finished inside the grace window
        time.sleep(0.1)
    return coord, workers


def run_gang(args, monitor: Optional[Callable[[List[_Worker]], None]]
             = None) -> GangResult:
    """Run the gang to completion with both recovery layers; returns
    the typed :class:`GangResult` (never raises on worker failure —
    callers read the reports). ``monitor(workers)`` is called every
    poll tick of the wait loop: the chaos host-kill leg uses it to
    SIGKILL the whole gang mid-window."""
    from bigdl_tpu.faults.retry import retry_call
    counters = {"start_retries": 0, "coordinator": ""}
    attempt = 0
    while True:
        # startup failures retry HERE (classified, backoff + jitter,
        # fresh port); counted separately from runtime gang restarts.
        # retry_call counts each performed retry into io/retry/retries.
        try:
            coord, workers = retry_call(
                _start_gang, args, attempt, counters, monitor,
                attempts=args.start_retries + 1, base_delay_s=0.5,
                max_delay_s=10.0, describe="gang start")
        except GangStartupError:
            # start retries exhausted: report typed "startup" exits
            # instead of raising past the caller
            reports = counters.get("last_reports", [])
            return GangResult(
                reports=reports, ok=False,
                restarts=attempt,
                start_retries=counters["start_retries"],
                coordinator=counters["coordinator"],
                culprit=next((r for r in reports
                              if r.kind == "startup"), None))
        failed = None
        while failed is None and any(w.proc.poll() is None
                                     for w in workers):
            if monitor is not None:
                monitor(workers)
            for w in workers:
                rc = w.proc.poll()
                if rc is not None and rc != 0:
                    failed = (w.rank, rc)
                    break
            else:
                time.sleep(0.2)
        if failed is None:
            rcs = [w.proc.wait() for w in workers]
            bad = [(w.rank, rc) for w, rc in zip(workers, rcs)
                   if rc != 0]
            if not bad:
                _join_threads(workers)
                return GangResult(
                    reports=_reports(workers, attempt,
                                     lambda w, rc: "ok"),
                    ok=True, restarts=attempt,
                    start_retries=counters["start_retries"],
                    coordinator=coord)
            failed = bad[0]
        # one death poisons the gang's collectives: put the survivors
        # down before relaunching
        kill_gang(workers)
        _join_threads(workers)

        def kind_for(w, rc):
            if rc == 0:
                return "ok"
            if rc is not None and rc < 0:
                return "killed"
            return "runtime"

        reports = _reports(workers, attempt, kind_for)
        if attempt >= args.max_restarts:
            return GangResult(reports=reports, ok=False,
                              restarts=attempt,
                              start_retries=counters["start_retries"],
                              coordinator=coord,
                              culprit=next(
                                  (r for r in reports
                                   if r.rank == failed[0]), None))
        attempt += 1
        print(f"[launcher] worker {failed[0]} died rc={failed[1]}; "
              f"gang restart {attempt}/{args.max_restarts}",
              flush=True)


def build_args(script: str, script_args=(), *, nproc: int = 1,
               nnodes: int = 1, node_rank: int = 0,
               coordinator: Optional[str] = None, cpu_devices: int = 0,
               max_restarts: int = 0, startup_grace: float = 20.0,
               start_retries: int = 3,
               extra_env: Optional[dict] = None,
               ship_telemetry: Optional[str] = None) -> argparse.Namespace:
    """The programmatic form of the CLI arguments (what
    ``tools.chaos --hostkill`` passes to :func:`run_gang`).
    ``extra_env`` overlays the inherited environment per worker;
    ``ship_telemetry`` arms every worker's snapshot shipper and flight
    recorder into that directory (``diagnose --fleet`` reads it)."""
    return argparse.Namespace(
        nproc=nproc, nnodes=nnodes, node_rank=node_rank,
        coordinator=coordinator, cpu_devices=cpu_devices,
        max_restarts=max_restarts, startup_grace=startup_grace,
        start_retries=start_retries, script=script,
        script_args=list(script_args), extra_env=dict(extra_env or {}),
        ship_telemetry=ship_telemetry)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Launch multi-process training workers")
    ap.add_argument("--nproc", type=int, default=1,
                    help="processes to spawn on THIS host")
    ap.add_argument("--nnodes", type=int, default=1,
                    help="total hosts participating")
    ap.add_argument("--node-rank", type=int, default=0,
                    help="this host's rank in [0, nnodes)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (default: a free local "
                         "port — single-host mode)")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force N virtual CPU devices per process "
                         "(testing without accelerators)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="gang-restart the workers up to N times after "
                         "a runtime failure (workers resume from their "
                         "latest checkpoint)")
    ap.add_argument("--startup-grace", type=float, default=20.0,
                    help="seconds after launch during which a worker "
                         "death with rendezvous-shaped output counts "
                         "as a startup failure")
    ap.add_argument("--start-retries", type=int, default=3,
                    help="retry a failed gang START this many times on "
                         "a fresh coordinator port (classified backoff "
                         "via faults.retry)")
    ap.add_argument("--ship-telemetry", dest="ship_telemetry",
                    default=None, metavar="DIR",
                    help="arm every worker's snapshot shipper + flight "
                         "recorder into DIR (merge with "
                         "`python -m bigdl_tpu.tools.diagnose "
                         "--fleet DIR`)")
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    result = run_gang(args)
    for r in result.reports:
        sig = f" ({r.signal})" if r.signal else ""
        print(f"[launcher] rank {r.rank}: rc={r.returncode}{sig} "
              f"kind={r.kind} attempt={r.attempt}", flush=True)
    if result.ok:
        return 0
    bad = result.culprit or result.failed()[0]
    budget = (f"start-retries={args.start_retries}"
              if bad.kind == "startup"
              else f"max-restarts={args.max_restarts}")
    raise SystemExit(
        f"worker {bad.rank} failed rc={bad.returncode} "
        f"kind={bad.kind} and {budget} exhausted")


if __name__ == "__main__":
    main()
