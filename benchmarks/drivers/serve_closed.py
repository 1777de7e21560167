"""Driver ``serve_closed``: a model served by ``GenerationService``
under a closed loop — ``clients`` callers, each sending its next
request when its last one completes.

Traffic parameters (``traffic/<name>.json``): ``clients``, ``slots``,
``max_len``, ``length_buckets`` (null = the service's default ladder),
``prompt_len`` / ``new_tokens`` ({"dist": "log_uniform" | "uniform",
"lo", "hi"}; ``lo`` = ``hi`` is a fixed length), ``shared_prefix`` (the
first so many ids of every prompt are the same, drawn from the seed),
``pool`` and ``shape_seed`` (the fixed list of request
sizes every seed draws: the seed shuffles it inside blocks of
``clients`` requests and fills it with other token ids, so that no seed
changes the amount of work a window holds), ``warm_seconds``,
``trace_seconds``, ``check_requests``, ``limits``.

A closed loop started all at once runs in lock-step for generations:
every client's first request would end within the same few steps. So
each client's FIRST request asks for a fraction of its output length
(drawn from ``shape_seed``, the same for every seed), as if the run had
begun with every request part-way through; those requests are warm-up
and every later one is the mix as written.

One thread drives the load. Token times are taken where the token
reaches the caller: a done-callback on ``TokenStream.token_future(i)``
reads the clock, and the completion callback posts the client's number
to a queue the driving thread waits on. The program receives only the
requests. All greedy.
"""
from __future__ import annotations

import gc
import itertools
import math
import queue
import time

import numpy as np


def draw(spec, rng, n):
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "log_uniform":
        v = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), n))
        return np.clip(v.astype(np.int64), lo, hi)
    if spec["dist"] == "uniform":
        return rng.randint(lo, hi + 1, n)
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def make_requests(traffic, seed, vocab):
    """The mix's requests for one run: a fixed pool of sizes (from
    ``shape_seed``), permuted and filled with token ids from ``seed``."""
    shapes = np.random.RandomState(int(traffic["shape_seed"]))
    n = int(traffic["pool"])
    plens = draw(traffic["prompt_len"], shapes, n)
    olens = draw(traffic["new_tokens"], shapes, n)
    first = shapes.uniform(0.0, 1.0, int(traffic["clients"]))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    block = int(traffic["clients"])
    order = np.concatenate([i + rng.permutation(min(block, n - i))
                            for i in range(0, n, block)])
    out = [(rng.randint(0, vocab, int(plens[i])).astype(np.int32),
            int(olens[i])) for i in order]
    prefix = rng.randint(0, vocab, int(traffic.get("shared_prefix", 0)))
    for p, _ in out:
        p[:len(prefix)] = prefix[:len(p)]
    for c, u in enumerate(first):
        out[c] = (out[c][0], max(1, int(math.ceil(out[c][1] * u))))
    return out


class _Req:
    __slots__ = ("client", "prompt", "max_new", "t_submit", "times",
                 "t_done", "tokens", "error", "stream")

    def __init__(self, client, prompt, max_new):
        self.client, self.prompt, self.max_new = client, prompt, max_new
        self.t_submit = self.t_done = None
        self.times, self.tokens, self.error, self.stream = [], None, None, None


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))]


def summarise(reqs, t0, t1):
    """The end-to-end numbers from the request log. A request sent in
    the window that failed or never produced a token counts as the
    worst TTFT seen; none is dropped."""
    sent = [r for r in reqs if t0 <= r.t_submit < t1]
    done = [r for r in reqs if r.error is None and r.t_done is not None
            and t0 <= r.t_done <= t1]
    ttft = [(r.times[0] - r.t_submit) * 1e3 for r in sent if r.times]
    worst = max(ttft) if ttft else float("inf")
    ttft += [worst] * (len(sent) - len(ttft))
    gaps = [(b - a) * 1e3 for r in reqs
            for a, b in zip(r.times, r.times[1:]) if t0 <= b <= t1]
    failed = [r for r in sent if r.error is not None or r.t_done is None
              or len(r.tokens) != r.max_new]
    return {"sent": len(sent), "completed": len(done), "failed": len(failed),
            "tokens_completed": sum(len(r.tokens) for r in done),
            "tokens_delivered": sum(1 for r in reqs for t in r.times
                                    if t0 <= t <= t1),
            "ttft_ms": ttft, "gaps_ms": gaps}


def work_in(reqs, t0, t1, cfg, fam, kv_itemsize):
    """Required FLOPs and cache bytes of what was processed in
    ``[t0, t1]``, from the token log alone: a request whose first token
    came in the window had its prompt processed there; output token j
    (j >= 1) that came in the window was decoded there at context
    ``prompt + j``."""
    flops = kv_bytes = 0.0
    prompt_tokens = decoded = 0
    for r in reqs:
        p = len(r.prompt)
        for j, t in enumerate(r.times):
            if not (t0 <= t <= t1):
                continue
            if j == 0:
                flops += fam.serve_flops_span(cfg, 0, p)
                prompt_tokens += p
            else:
                flops += fam.serve_flops_per_token(cfg, p + j)
                kv_bytes += fam.kv_read_bytes(cfg, p + j, kv_itemsize)
                decoded += 1
    return {"required_flops": flops, "decode_kv_bytes": kv_bytes,
            "prompt_tokens": prompt_tokens, "decoded_tokens": decoded}


def live_tokens(reqs, instants):
    """Cached tokens in the slots at each instant, from the token log: a
    request holds its prompt from its first token on, one more with
    every later token, and nothing once it is done."""
    out = []
    for at in instants:
        n = 0
        for r in reqs:
            if r.times and r.times[0] <= at and (r.t_done is None
                                                 or at < r.t_done):
                n += len(r.prompt) + sum(1 for x in r.times if x <= at)
        out.append(n)
    return out


def pick_checked(reqs, seed, n, t0, t1):
    """The requests finished in the window whose served tokens the
    reference judges: the longest, and a sample drawn from the seed."""
    done = [r for r in reqs if r.error is None and r.tokens is not None
            and len(r.tokens) > 0 and t0 <= r.t_done <= t1]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.RandomState((int(seed) + 1) % (2 ** 32))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def judge(gaps_per_row):
    flat = np.concatenate(gaps_per_row)
    return {"gap_max": float(flat.max()), "gap_mean": float(flat.mean()),
            "tokens": int(flat.size),
            "off_best": int((flat > 0).sum())}


def run(ctx, _break=None):
    """``_break`` is the tests' hook: applied to the loaded service, to
    plant a fault under the timed path."""
    import jax

    from bigdl_tpu import telemetry
    from bigdl_tpu.generation import GenerationConfig, GenerationService
    from bigdl_tpu.utils.random import RandomGenerator

    t, cfg, fam = ctx.traffic, ctx.config, ctx.family
    clients = int(t["clients"])
    named = t.get("controls", [])
    if ctx.args.stand_in and ctx.args.stand_in not in named:
        raise SystemExit(f"--stand-in: this mix names {named}")
    seconds = float(t["trace_seconds"]) if ctx.trace_on else ctx.seconds
    RandomGenerator.set_seed(ctx.seed)
    requests = make_requests(t, ctx.seed, int(cfg["vocab_size"]))
    model = fam.build_program_model(cfg).evaluate()
    model.set_parameters(fam.make_program_params(
        cfg, ctx.seed, t.get("weights_dtype", "float32")))
    svc = GenerationService(config=GenerationConfig(
        slots=int(t["slots"]), max_len=int(t["max_len"]),
        length_buckets=t.get("length_buckets"),
        max_new_tokens=int(t["new_tokens"]["hi"]),
        max_queue=4 * clients))
    svc.load("lm", model)               # warms every ladder rung
    if _break is not None:
        _break(svc)

    events = queue.SimpleQueue()
    log, nxt = [], itertools.cycle(requests)
    clock = time.monotonic

    def send(client):
        prompt, max_new = next(nxt)
        r = _Req(client, prompt, max_new)
        stamp = lambda _f, r=r: r.times.append(clock())

        def finished(f, r=r):
            r.t_done = clock()
            try:
                r.tokens = np.asarray(f.result())
            except BaseException as e:      # typed stream errors
                r.error = e
            events.put(r.client)
        with ctx.annotate("bench/submit"):
            r.t_submit = clock()
            r.stream = svc.generate("lm", prompt, max_new_tokens=max_new)
            for i in range(max_new):
                r.stream.token_future(i).add_done_callback(stamp)
            r.stream.completion.add_done_callback(finished)
        log.append(r)

    def pump(until, resend):
        """Wait on completions until ``until``; a finished client sends
        its next request while ``resend``, else return once nothing is
        in flight."""
        while True:
            left = until - clock()
            if left <= 0 or (not resend
                             and all(r.t_done is not None for r in log)):
                return
            try:
                with ctx.annotate("bench/client_wait"):
                    client = events.get(timeout=min(left, 0.25))
            except queue.Empty:
                continue
            if resend:
                send(client)

    try:
        for c in range(clients):
            send(c)
        pump(clock() + float(t.get("warm_seconds", 3.0)), True)
        compiles0, compile_s = ctx.compiles.count, ctx.compiles.seconds
        ctx.trace_start()
        if ctx.trace_on:            # the program's own spans, traced run only
            telemetry.enable().clear()
        span = ctx.annotate("bench/window")
        span.__enter__()
        t0 = clock()
        setup_s = time.time() - ctx.t_start
        pump(t0 + seconds, True)
        t1 = clock()
        span.__exit__(None, None, None)
        telemetry.disable()
        ctx.trace_stop()
        window_compiles = ctx.compiles.count - compiles0
        # answers due in the window: wait for each, a minute past the close
        pump(t1 + 60.0 + seconds, False)
    finally:
        svc.shutdown(drain=False)
    ctx.close_window()
    if ctx.trace_on:
        for rec in telemetry.tracer().spans():
            if t0 <= rec.ts and rec.ts + rec.dur <= t1:
                ctx.spans.setdefault(rec.name, []).append(rec.dur)

    s = summarise(log, t0, t1)
    kv_itemsize = np.dtype(t.get("kv_dtype", "float32")).itemsize
    ctx.log.update(work_in(log, t0, t1, cfg, fam, kv_itemsize),
                   window_s=t1 - t0, window_compiles=window_compiles,
                   setup_s=setup_s, setup_programs=compiles0,
                   setup_compile_s=compile_s,
                   sent=s["sent"], completed=s["completed"],
                   tokens_completed=s["tokens_completed"],
                   tokens_delivered=s["tokens_delivered"],
                   gaps=len(s["gaps_ms"]))
    # how full the reserved cache is: the floor on a cell's size is met
    # on tokens that are there, not on rows the traffic never fills
    live = live_tokens(log, np.linspace(t0, t1, 12)[1:-1])
    per_token = fam.kv_read_bytes(cfg, 1, kv_itemsize)
    ctx.log["kv"] = {"live_tokens_mean": float(np.mean(live)),
                     "live_tokens_max": int(max(live)),
                     "live_bytes_mean": float(np.mean(live)) * per_token,
                     "reserved_bytes": int(t["slots"]) * int(t["max_len"])
                     * per_token}
    if s["ttft_ms"]:
        ctx.log["ttft_ms_at"] = {q: percentile(s["ttft_ms"], q)
                                 for q in (50, 75, 90, 95, 99, 100)}
    # the rate is over ALL output tokens that reached a caller inside the
    # window, those of requests still in flight at its close too: counting
    # only completed requests moves by a request's length (0.6%) with
    # every request that ends just past the close (PERF.md section 2)
    end_to_end = {"setup_s": setup_s}
    if s["tokens_delivered"]:
        end_to_end["serve_tokens_per_s"] = s["tokens_delivered"] / (t1 - t0)
    if s["ttft_ms"]:
        end_to_end["ttft_ms_p95"] = percentile(s["ttft_ms"], 95)
    if s["gaps_ms"]:
        end_to_end["itl_ms_p95"] = percentile(s["gaps_ms"], 95)
        ctx.log["itl_ms_at"] = {q: percentile(s["gaps_ms"], q)
                                for q in (50, 95, 99, 100)}
        # the longest gap, and when it began: beside the heartbeat's
        # record it tells a stall of the machine from one of the program
        ctx.log["itl_longest_began_s"] = max(
            ((b - a, a - t0) for r in log
             for a, b in zip(r.times, r.times[1:]) if t0 <= b <= t1))[1]

    checked = pick_checked(log, ctx.seed, int(t["check_requests"]), t0, t1)
    rows = [(r.prompt, r.tokens) for r in checked]
    del svc, model
    for r in log:
        r.stream = None
    gc.collect()
    jax.clear_caches()
    lim = t["limits"]
    if rows:
        served, _ = fam.ref_token_gaps(cfg, ctx.seed, rows)
        got = judge(served)
        # the gaps of the tokens that a lower-precision forward of the
        # same prompts and tokens puts first: read into the log
        # (--control, what limits are set from), or put in the
        # program's place (--stand-in)
        for mode in named:
            if ctx.args.control or mode == ctx.args.stand_in:
                _, low = fam.ref_token_gaps(cfg, ctx.seed, rows, mode)
                ctx.log["control_" + mode] = judge(low)
        ctx.log["compare"] = got
        if ctx.args.stand_in:
            ctx.log["program"] = got
            got = ctx.log["control_" + ctx.args.stand_in]
    else:
        got = {"gap_max": float("nan")}
    return {"end_to_end": end_to_end, "attempted": s["sent"],
            "failed": s["failed"],
            "checks": [["gap_max", got["gap_max"], lim["gap_max"]],
                       ["unanswered", float(s["failed"]), 0.0]]}
