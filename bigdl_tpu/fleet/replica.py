"""One generation-serving replica: an engine + decode loop behind a
health breaker.

A **replica** is the fleet's unit of failure and of capacity: its own
:class:`~bigdl_tpu.generation.service.GenerationService` (own compile
cache, own KV cache, own decode loop — an independent failure domain),
plus a :class:`~bigdl_tpu.serving.breaker.CircuitBreaker` fed by its
stream outcomes so the router can shed a failing replica in
microseconds instead of queueing into it. Tier-1 replicas are
**thread-hosted** (everything in-process, ``JAX_PLATFORMS=cpu`` works
end to end); :class:`ProcessReplica` hosts the identical serving loop
in a subprocess — one process per replica is the data-parallel serving
shape real fleets run, and the slow tests drive it through the same
router.

Lifecycle: ``serving`` → (``drain()``) → ``draining`` → (``shutdown``)
→ ``dead``. A *draining* replica finishes the streams it holds but
takes no new sessions (the hot-swap rebalance); a *dead* one is
evicted by the router and its in-flight streams fail typed (the chaos
``--fleet`` leg asserts they re-route or resolve ``WorkerDied``,
never hang). The ``fleet/replica`` faultpoint at the submit path is
the seeded kill site the chaos schedule drives.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from typing import Dict, Optional

import numpy as np

from bigdl_tpu import faults
from bigdl_tpu.generation.service import (GenerationConfig,
                                          GenerationService)
from bigdl_tpu.generation.stream import TokenStream
from bigdl_tpu.serving.batcher import QueueFull, WorkerDied
from bigdl_tpu.serving.breaker import CircuitBreaker


class Replica:
    """Thread-hosted replica (module docstring has the contract).

    ``name`` doubles as the served model name, so every replica's
    generation telemetry lands under its own ``model=<name>`` label
    series in a shared registry."""

    def __init__(self, name: str, model, *,
                 config: Optional[GenerationConfig] = None,
                 breaker_failures: int = 3,
                 breaker_cooldown_ms: float = 250.0, metrics=None):
        self.name = name
        self.state = "serving"
        self.breaker = CircuitBreaker(failures=breaker_failures,
                                      cooldown_ms=breaker_cooldown_ms)
        self._lock = threading.Lock()
        self._svc = GenerationService(config=config,
                                      metrics_registry=metrics)
        self._svc.load(name, model)

    @property
    def service(self) -> GenerationService:
        """The replica's own GenerationService (hot-swap a new model
        version through it — the router keeps routing throughout)."""
        return self._svc

    # -------------------------------------------------------- routing
    def accepting(self) -> bool:
        """Whether the router may place a NEW session here right now:
        serving (not draining/dead) and the breaker admits (closed, or
        one half-open probe)."""
        return self.state == "serving" and self.breaker.allow()

    def load(self) -> int:
        """Current occupancy (live slots + queued requests) — the
        router's least-loaded placement key."""
        with self._svc._lock:
            loop = self._svc._loops.get(self.name)
        if loop is None:
            return 0
        return loop.live_slots() + loop.queue_depth()

    def submit(self, prompt, **kw) -> TokenStream:
        """Submit one generation to this replica. The ``fleet/replica``
        faultpoint fires first: an injected fault here IS a replica
        death (the chaos leg's seeded kill switch) — the replica fails
        its in-flight streams typed, reports ``WorkerDied``, and the
        router evicts + re-routes."""
        try:
            faults.point("fleet/replica", replica=self.name)
        except BaseException as e:
            self.kill()
            err = WorkerDied(f"replica {self.name!r} killed by injected "
                             f"fault: {type(e).__name__}: {e}")
            err.__cause__ = e
            raise err from e
        try:
            return self._svc.generate(self.name, prompt, **kw)
        except QueueFull:
            raise
        except RuntimeError as e:
            if self.state == "dead":
                # a concurrent kill shut the service down under this
                # submit: keep the router's typed-error contract
                raise WorkerDied(
                    f"replica {self.name!r} is dead") from e
            raise

    # ------------------------------------------------------ lifecycle
    def drain(self) -> None:
        """Hot-swap rebalance: stop taking new sessions; streams this
        replica holds run to completion."""
        with self._lock:
            if self.state == "serving":
                self.state = "draining"

    def resume(self) -> None:
        """Return a draining replica to service."""
        with self._lock:
            if self.state == "draining":
                self.state = "serving"

    def kill(self) -> None:
        """Replica death (chaos): in-flight and queued streams fail
        promptly and typed; the replica never serves again."""
        with self._lock:
            if self.state == "dead":
                return
            self.state = "dead"
        self._svc.shutdown(drain=False)

    def shutdown(self, drain: bool = True) -> None:
        """Clean stop: with ``drain`` finish held streams first."""
        with self._lock:
            already = self.state == "dead"
            self.state = "dead"
        if not already:
            self._svc.shutdown(drain=drain)

    def metrics(self) -> Dict[str, float]:
        """The replica's own generation metrics snapshot."""
        return self._svc.metrics(self.name)

    def __repr__(self) -> str:
        return f"Replica({self.name!r} {self.state} load={self.load()})"


# --------------------------------------------------------------------
# process-hosted replica: the same serving loop, one process per
# replica — the data-parallel serving shape (slow tests only; jax
# imports per process make it far too heavy for tier-1)

class ProcessReplica:
    """A replica hosted in a subprocess, driven over a line-JSON pipe.

    The worker (``python -m bigdl_tpu.fleet.replica --worker``) builds
    the same seeded model the parent describes in ``model_spec`` and
    serves generations through its own GenerationService; tokens
    stream back as ``{"id", "token"}`` lines, terminal lines are
    ``{"id", "done"}`` / ``{"id", "error"}``. The parent-side object
    duck-types :class:`Replica`, so the router treats both hosts
    identically."""

    def __init__(self, name: str, model_spec: Dict, *,
                 slots: int = 2, max_len: int = 32,
                 breaker_failures: int = 3,
                 breaker_cooldown_ms: float = 250.0,
                 startup_timeout_s: float = 120.0,
                 telemetry_dir: Optional[str] = None):
        self.name = name
        self.state = "serving"
        self.telemetry_dir = telemetry_dir
        self.breaker = CircuitBreaker(failures=breaker_failures,
                                      cooldown_ms=breaker_cooldown_ms)
        self._lock = threading.Lock()
        self._seq = 0
        self._streams: Dict[int, TokenStream] = {}
        env = dict(os.environ)
        # the chip is the parent's (a process that has touched jax
        # holds it, and a child that wanted it would fail or hang): a
        # subprocess replica serves from the CPU. Replicas that need
        # chips are in-process ``Replica``s, one per device.
        env.setdefault("JAX_PLATFORMS", "cpu")
        if telemetry_dir:
            # the worker arms its flight recorder and ships identity-
            # stamped snapshot JSONL into the (router-owned) directory
            # at import, so a SIGKILLed replica still leaves a
            # postmortem bundle the parent can read
            env["BIGDL_TELEMETRY_SHIP_DIR"] = telemetry_dir
            env["BIGDL_TELEMETRY_SHIP_EVERY_S"] = "0.2"
            env["BIGDL_FLIGHT_DIR"] = os.path.join(
                telemetry_dir, "flight")
            env["BIGDL_REPLICA_ID"] = name
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep \
            + env.get("PYTHONPATH", "")
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "bigdl_tpu.fleet.replica", "--worker",
             "--model-spec", json.dumps(model_spec),
             "--slots", str(slots), "--max-len", str(max_len)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, env=env)
        ready = self._proc.stdout.readline()
        if not ready.strip().startswith("{"):
            raise RuntimeError(
                f"process replica {name!r} failed to start: {ready!r}")
        self._reader = threading.Thread(target=self._read_loop,
                                        name=f"fleet-proc-{name}",
                                        daemon=True)
        self._reader.start()

    def accepting(self) -> bool:
        """Router placement gate (see :meth:`Replica.accepting`)."""
        return self.state == "serving" and self.breaker.allow()

    def load(self) -> int:
        """In-flight requests held by the subprocess."""
        with self._lock:
            return len(self._streams)

    def submit(self, prompt, *, max_new_tokens=None, temperature=0.0,
               top_k=None, seed=0, timeout_ms=None) -> TokenStream:
        """Submit one generation over the pipe; same faultpoint-driven
        kill semantics as :meth:`Replica.submit`."""
        try:
            faults.point("fleet/replica", replica=self.name)
        except BaseException as e:
            self.kill()
            err = WorkerDied(f"replica {self.name!r} killed by injected "
                             f"fault: {type(e).__name__}: {e}")
            raise err from e
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        stream = TokenStream(int(prompt.shape[0]),
                             max_new_tokens or 16)
        with self._lock:
            if self.state != "serving" or self._proc.poll() is not None:
                raise WorkerDied(f"replica {self.name!r} is {self.state}")
            self._seq += 1
            rid = self._seq
            self._streams[rid] = stream
            req = {"id": rid, "prompt": prompt.tolist(),
                   "max_new": int(max_new_tokens or 16),
                   "temperature": float(temperature),
                   "top_k": top_k, "seed": int(seed)}
            try:
                self._proc.stdin.write(json.dumps(req) + "\n")
                self._proc.stdin.flush()
            except (BrokenPipeError, OSError) as e:
                self._streams.pop(rid, None)
                raise WorkerDied(
                    f"replica {self.name!r} pipe closed") from e
        return stream

    def _read_loop(self) -> None:
        for line in self._proc.stdout:
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            with self._lock:
                stream = self._streams.get(msg.get("id"))
            if stream is None:
                continue
            if "token" in msg:
                stream._push(int(msg["token"]))
            elif "done" in msg:
                with self._lock:
                    self._streams.pop(msg["id"], None)
                stream._finish(msg["done"])
            elif "error" in msg:
                with self._lock:
                    self._streams.pop(msg["id"], None)
                stream._fail(WorkerDied(
                    f"replica {self.name!r}: {msg['error']}"))
        # pipe closed: the worker died — fail everything typed
        self._fail_all(WorkerDied(f"replica {self.name!r} process died"))

    def _fail_all(self, err: BaseException) -> None:
        with self._lock:
            doomed = list(self._streams.values())
            self._streams.clear()
            if self.state != "dead":
                self.state = "dead"
        for s in doomed:
            try:
                s._fail(err)
            except Exception:
                pass  # racing a resolution

    def drain(self) -> None:
        """Stop placing new sessions here (held streams finish)."""
        with self._lock:
            if self.state == "serving":
                self.state = "draining"

    def resume(self) -> None:
        """Return a draining replica to service."""
        with self._lock:
            if self.state == "draining":
                self.state = "serving"

    def kill(self) -> None:
        """SIGKILL the hosting process; streams fail typed via the
        reader's pipe-closed path."""
        with self._lock:
            if self.state == "dead":
                return
            self.state = "dead"
        self._proc.kill()

    def shutdown(self, drain: bool = True) -> None:
        """Stop the subprocess (``drain`` waits for held streams)."""
        if drain:
            import time as _time
            end = _time.monotonic() + 30.0
            while self.load() and _time.monotonic() < end:
                _time.sleep(0.01)
        with self._lock:
            self.state = "dead"
        try:
            self._proc.stdin.close()
        except OSError:
            pass
        try:
            self._proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self._proc.kill()

    def metrics(self) -> Dict[str, float]:
        """Minimal parent-side view (the full registry lives in the
        subprocess)."""
        return {"in_flight": self.load(), "state": self.state}

    def __repr__(self) -> str:
        return f"ProcessReplica({self.name!r} {self.state})"


# ----------------------------------------------------------- worker

def _worker(argv) -> int:
    """Subprocess entry: serve generations over stdin/stdout."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--model-spec", required=True)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=32)
    args = ap.parse_args(argv)

    import jax
    # the chip is the parent's — see ProcessReplica.__init__
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu import telemetry
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random import RandomGenerator

    spec = json.loads(args.model_spec)
    RandomGenerator.set_seed(int(spec.get("seed", 42)))
    model = TransformerLM(
        vocab_size=int(spec["vocab_size"]),
        hidden_size=int(spec["hidden_size"]),
        num_layers=int(spec["num_layers"]),
        num_heads=int(spec["num_heads"]),
        max_len=int(spec.get("max_len", args.max_len))).evaluate()
    model.ensure_initialized()
    # when the parent armed the shipper (BIGDL_TELEMETRY_SHIP_DIR via
    # ProcessReplica telemetry_dir), serve out of the process registry
    # so the shipped snapshots carry the serving instruments
    shipping = telemetry.agg.shipping()
    svc = GenerationService(config=GenerationConfig(
        slots=args.slots, max_len=args.max_len,
        length_buckets=(args.max_len,),
        prefill_rows=min(2, args.slots)),
        metrics_registry=telemetry.registry() if shipping else None)
    svc.load("lm", model)
    out_lock = threading.Lock()

    def emit(obj):
        with out_lock:
            print(json.dumps(obj), flush=True)

    emit({"ready": True})
    telemetry.agg.maybe_ship(force=True)

    def pump(rid, stream):
        try:
            for tok in stream:
                emit({"id": rid, "token": int(tok)})
            emit({"id": rid, "done": stream.finish_reason or "done"})
            telemetry.flight.note("request_done", id=rid)
        except Exception as e:
            emit({"id": rid, "error": f"{type(e).__name__}: {e}"})
            telemetry.flight.note("request_error", id=rid,
                                  error=f"{type(e).__name__}: {e}")
        telemetry.agg.maybe_ship()

    for line in sys.stdin:
        try:
            req = json.loads(line)
        except ValueError:
            continue
        telemetry.flight.note("request", id=req.get("id"))
        try:
            stream = svc.generate(
                "lm", np.asarray(req["prompt"], np.int32),
                max_new_tokens=req.get("max_new"),
                temperature=req.get("temperature", 0.0),
                top_k=req.get("top_k"), seed=req.get("seed", 0))
        except Exception as e:
            emit({"id": req.get("id"), "error":
                  f"{type(e).__name__}: {e}"})
            continue
        threading.Thread(target=pump, args=(req["id"], stream),
                         daemon=True).start()
    svc.shutdown(drain=True)
    telemetry.agg.stop_shipping()
    return 0


if __name__ == "__main__":
    raise SystemExit(_worker(sys.argv[1:]))
