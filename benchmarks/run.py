#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs ONE cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
with ``--trace 1`` ``breakdown``), then ``checks`` last.

The harness holds no table of cells, configurations, drivers or
metrics. It finds each by the name ``BENCHMARK.json`` gives it:

    configs/<config>.json    sizes as run; "family" names models/<family>.py
    traffic/<traffic>.json   the mix's parameters; "driver" names
                             drivers/<driver>.py
    metrics/<metric>.json    "reader" names readers/<reader>.py

so a later PR adds a cell, a configuration, a driver or a per-layer
metric as new files plus entries, editing nothing that is here.

Without a TPU (or with fewer chips than the cell asks for) it exits 2
and prints no result. ``--allow-cpu`` is the rehearsal switch of the
tests: it runs the same path on the CPU at a tiny size, reports no
device metric, and marks its line ``"rehearsal": true``.
"""
from __future__ import annotations

import time

_T_START = time.time()          # process start, as near as Python gets

import argparse
import importlib
import json
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def find_config(bench, name):
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"run.py: no config {name!r} in BENCHMARK.json")


def metrics_for(bench, kind, cell, end_to_end_reported):
    """The metrics of ``kind`` this cell reports: those that list it
    under ``workloads``, or list nothing and (per-layer) move an
    end-to-end metric the cell reports."""
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is not None:
            if cell in cells:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in end_to_end_reported:
            out.append(m)
    return out


def peaks_for(device_kind):
    table = load_json(HERE, "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(known: {sorted(table['devices'])}); add it with its source")
    return table["devices"][device_kind]


class Compiles:
    """Programs XLA was asked for, and the seconds that took, through
    the public ``jax.monitoring`` event that fires for each, compiled
    or fetched from the persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax.monitoring

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1
            self.seconds += duration


class Heartbeat(threading.Thread):
    """A thread that sleeps 20 ms at a time through the measured window
    and keeps the times it overslept by 100 ms or more. A run that reads
    far off shows here whether the whole process (or its machine) stood
    still, or only the program's path did."""

    def __init__(self):
        super().__init__(daemon=True, name="bench-heartbeat")
        self.t0, self.late, self._halt = time.monotonic(), [], threading.Event()

    def run(self):
        last = time.monotonic()
        while not self._halt.wait(0.02):
            now = time.monotonic()
            if now - last >= 0.12:
                self.late.append([round(last - self.t0, 3),
                                  round(now - last - 0.02, 3)])
            last = now

    def stop(self):
        """[[seconds into the window, seconds overslept], ...], longest
        first, at most five."""
        self._halt.set()
        self.join()
        return sorted(self.late, key=lambda x: -x[1])[:5]


class Context:
    """What a driver and a reader are handed."""

    def __init__(self, args, bench, cell, config, traffic, family,
                 devices, rehearsal):
        self.args, self.bench, self.cell = args, bench, cell
        self.config, self.traffic, self.family = config, traffic, family
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace_on = bool(args.trace)
        self.devices, self.rehearsal = devices, rehearsal
        self.t_start = _T_START
        self.compiles = Compiles()
        self.trace_dir = os.path.join(ROOT, ".bench_trace")
        self._tracing = False
        self._heartbeat = None
        self.memory_peak_bytes = None
        self.peaks = None if rehearsal else peaks_for(devices[0].device_kind)
        # filled by the driver
        self.log = {}
        self.spans = {}             # {program span name: [seconds, ...]}
        self.reduced = None

    # -- the traced part of the window --------------------------------
    def trace_start(self):
        """Called by the driver as the measured window opens."""
        self._heartbeat = Heartbeat()
        self._heartbeat.start()
        if not self.trace_on:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        # host spans (TraceAnnotation) on, the per-call Python tracer
        # off: it slows a host-bound loop and swells the file
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True

    def trace_stop(self):
        if not self._tracing:
            return
        import jax

        jax.profiler.stop_trace()
        self._tracing = False

    def annotate(self, name, **kw):
        """A host span in the profiler's own trace (a no-op cost when
        no trace is on)."""
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)

    def close_window(self):
        """Called by the driver when the measured window has closed and
        BEFORE it frees the program's state or runs the reference."""
        self.trace_stop()
        if self._heartbeat is not None:
            self.log["host_stalls_s"] = self._heartbeat.stop()
            self._heartbeat = None
        used = [d.memory_stats() or {} for d in self.devices]
        peaks = [s.get("peak_bytes_in_use") for s in used
                 if s.get("peak_bytes_in_use") is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def reduce(self):
        from benchmarks import trace_reduce

        path = trace_reduce.find_xplane(self.trace_dir)
        self.reduced = trace_reduce.reduce_trace(
            path, allow_host_ops=self.rehearsal)
        keep = self.args.keep_trace
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, f"{self.cell['name']}.trace.txt"),
                      "w") as f:
                f.write(trace_reduce.describe(path))
            shutil.copy(path, os.path.join(
                keep, f"{self.cell['name']}.xplane.pb"))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return self.reduced


def main(argv=None, _break=None) -> int:
    """``_break`` is the tests' hook: handed to the driver, which plants
    it under the timed path (see the drivers)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal on the CPU: no device metric")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read every control and planted fault of the "
                         "mix into the log, leaf by leaf (slower; for "
                         "setting limits, not for the driver)")
    ap.add_argument("--stand-in", default=None, metavar="NAME",
                    help="put one of the mix's controls (or fault_<name>) "
                         "in the program's place in the checks: the line "
                         "has to say correct false")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the trace and a by-hand description there")
    ap.add_argument("--bench-file", default=os.path.join(ROOT,
                                                         "BENCHMARK.json"),
                    help="tests point this at a tiny benchmark")
    args = ap.parse_args(argv)

    bench = load_json(args.bench_file)
    base = os.path.dirname(os.path.abspath(args.bench_file))
    cell = find_cell(bench, args.workload)
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cfg_entry = find_config(bench, cell["config"])
    config = load_json(base, cfg_entry["file"])
    # traffic/ and metrics/ sit beside configs/
    data_dir = os.path.dirname(os.path.dirname(
        os.path.join(base, cfg_entry["file"])))
    traffic = load_json(data_dir, "traffic", cell["traffic"] + ".json")

    import jax                      # first: which machine is this?

    devices = jax.devices()
    rehearsal = False
    if devices[0].platform != "tpu":
        if not args.allow_cpu:
            print(f"run.py: JAX found platform {devices[0].platform!r}, "
                  "not a TPU - nothing was run", file=sys.stderr)
            return 2
        rehearsal = True
    if len(devices) < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} chip(s), "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]

    # the persistent compile cache: where JAX_COMPILATION_CACHE_DIR says,
    # else <checkout>/.jax_cache (the program's own helper decides), and
    # every program kept, however quick its compile
    from bigdl_tpu.utils.engine import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # and none evicted: under a size cap (the chip tool's machine sets
    # 192 MiB) the train cell's window program, about 100 MiB, is the
    # first to go once a few reference programs are written after it,
    # and the next run compiles it again for 95 s (PERF.md, PR 25)
    jax.config.update("jax_compilation_cache_max_size", -1)

    family = importlib.import_module(f"benchmarks.models.{config['family']}")
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    ctx = Context(args, bench, cell, config, traffic, family, devices,
                  rehearsal)

    # the driver warms up, measures, closes the window, frees the
    # program's state, runs the plain reference and returns
    #   {"end_to_end": {name: value}, "attempted", "failed",
    #    "checks": [[name, value, limit], ...]}
    result = driver.run(ctx, _break=_break)
    if ctx.log.get("window_compiles", 0) != 0:
        print(f"run.py: {ctx.log['window_compiles']} program(s) were "
              "compiled inside the measured window", file=sys.stderr)
        return 3

    e2e = metrics_for(bench, "end_to_end", cell["name"], None)
    missing = [m["name"] for m in e2e if m["name"] not in result["end_to_end"]]
    if missing and not args.trace:
        print(f"run.py: the driver reported no {missing}", file=sys.stderr)
        return 3
    checks = result["checks"]
    correct = bool(checks) and all(
        v is not None and v == v and v <= lim for _, v, lim in checks)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    if rehearsal:
        line["rehearsal"] = True
    if args.stand_in:
        line["stand_in"] = args.stand_in
    if not args.trace:
        line["metrics"] = {
            m["name"]: {"value": result["end_to_end"][m["name"]],
                        "unit": m["unit"]} for m in e2e}
    else:
        red = ctx.reduce()
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        ctx.end_to_end = result["end_to_end"]
        out = {}
        for m in metrics_for(bench, "per_layer", cell["name"],
                             set(result["end_to_end"])):
            spec = load_json(data_dir, "metrics", m["name"] + ".json")
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(ctx, **spec.get("args", {}))
            if value is None:
                print(f"run.py: {m['name']}: its reader found nothing to "
                      "read in this run; left out", file=sys.stderr)
                continue
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = {} if rehearsal else out
        if rehearsal:
            line["rehearsal_counts"] = sorted(out)
        line["breakdown"] = {"device_ops": red["top_ops"],
                             "idle_gaps": red["gaps"]}
    line["device"] = device
    line["log"] = {k: v for k, v in ctx.log.items()
                   if isinstance(v, (int, float, str, dict, list))}
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}

    for n, v, lim in checks:
        ok = v is not None and v == v and v <= lim
        print(f"check {n}: {v} (limit {lim}) {'ok' if ok else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
