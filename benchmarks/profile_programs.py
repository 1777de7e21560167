#!/usr/bin/env python3
"""By hand, on a chip: a serve cell's prefill and decode programs ALONE
under the profiler, every device operation by self time (a run's
``breakdown`` keeps ten, and mixes the two programs).

    python3 benchmarks/profile_programs.py --workload <cell> [--out FILE]
        [--bench-file BENCHMARK.json]

Builds the cell's model from its configuration and traffic files as
``drivers/serve_closed.py`` does (weights from seed 5), fills every slot
at a random length of the mix, and traces ``--prefills`` prefills of one
prompt of the mix's mean length and ``--decodes`` decode steps over all
slots. ``PERF.md`` section 5 quotes its output; no metric reads it."""
from __future__ import annotations

import argparse
import importlib
import os
import shutil
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                               # noqa: E402
import numpy as np                                       # noqa: E402

from benchmarks import run as harness, trace_reduce      # noqa: E402


def traced(name, fn, n):
    """``n`` calls of ``fn`` under the profiler: the device operations
    by kind, self time a call, longest first."""
    where = os.path.join(ROOT, ".bench_trace", name)
    shutil.rmtree(where, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    began = time.time()
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(n):
            fn()
    wall = time.time() - began
    jax.profiler.stop_trace()
    reduced = trace_reduce.reduce_trace(
        trace_reduce.find_xplane(where),
        allow_host_ops=jax.default_backend() != "tpu")
    by_kind = defaultdict(float)
    for op, seconds in reduced["ops"].items():
        by_kind[trace_reduce.kind_name(op)] += seconds
    lines = [f"== {name}: {n} calls, wall {1e3 * wall / n:.2f} ms a call, "
             f"device busy {1e3 * reduced['busy_s'] / n:.2f} ms a call"]
    lines += [f"  {1e3 * seconds / n:8.3f} ms  {op}" for op, seconds in
              sorted(by_kind.items(), key=lambda kv: -kv[1])[:45]]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prefills", type=int, default=6)
    ap.add_argument("--decodes", type=int, default=30)
    ap.add_argument("--out", default=None)
    ap.add_argument("--bench-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="as run.py's: a tiny benchmark, for a rehearsal")
    args = ap.parse_args(argv)
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.generation.kv_cache import KVCache
    from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache
    from bigdl_tpu.serving.registry import ModelRegistry

    bench = harness.load_json(args.bench_file)
    base = os.path.dirname(os.path.abspath(args.bench_file))
    cell = harness.find_cell(bench, args.workload)
    file = harness.find_config(bench, cell["config"])["file"]
    cfg = harness.load_json(base, file)
    t = harness.load_json(os.path.dirname(os.path.dirname(os.path.join(
        base, file))), "traffic", cell["traffic"] + ".json")
    fam = importlib.import_module(f"benchmarks.models.{cfg['family']}")
    model = fam.build_program_model(cfg).evaluate()
    model.set_parameters(fam.make_program_params(
        cfg, 5, t.get("weights_dtype", "float32")))
    slots, max_len = int(t["slots"]), int(t["max_len"])
    eng = DecodeEngine(CompileCache(), BucketLadder(
        max_len, t.get("length_buckets") or [max_len]), slots, 4)
    sv = ModelRegistry().load("m", model)
    kv = KVCache.for_model(model, slots, max_len)
    rng = np.random.RandomState(0)
    vocab = int(cfg["vocab_size"])
    p, o = t["prompt_len"], t["new_tokens"]
    prompt = rng.randint(0, vocab, (p["lo"] + p["hi"]) // 2).astype(np.int32)
    tokens = rng.randint(0, vocab, slots).astype(np.int32)
    positions = rng.randint(p["lo"], p["hi"] + o["hi"],
                            slots).astype(np.int32)
    active = np.ones(slots, bool)
    prefill = lambda: eng.prefill(sv, kv, [prompt], [0])
    decode = lambda: eng.decode(sv, kv, tokens, positions, active,
                                ids_only=True)
    for _ in range(2):                                  # compile, warm
        prefill()
        decode()
    text = "\n".join([traced("prefill", prefill, args.prefills),
                      traced("decode", decode, args.decodes)])
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
