"""Pipeline parallelism (GPipe-style) over a ``pipe`` mesh axis — net-new
vs the reference (SURVEY.md §2.3: PP absent). TPU-first design: each
device owns a contiguous stage of stacked homogeneous blocks; microbatches
stream through the ring via ``ppermute`` inside a ``lax.scan`` (the
classic SPMD pipeline pattern), so XLA overlaps the per-stage compute
with the ICI transfer of activations.

Use inside ``shard_map``: params sharded [n_stages, layers/stage, ...]
over ``pipe`` dim 0, inputs microbatched [M, mb, ...] (replicated), output
replicated [M, mb, ...].

Composition: ``pipeline_forward`` maps ONLY the pipe axis (plus any
``extra_axes`` — e.g. a sequence-parallel axis whose ring-attention
collectives must run manually inside the stage) — every other mesh axis
stays auto (GSPMD), so data/tensor/expert parallelism compose freely.
``with_aux=True`` threads a per-block scalar side output (MoE
load-balance loss) through the pipeline: garbage fill/drain steps are
masked out, so the result equals the dense model's
mean-over-microbatches, sum-over-layers aux exactly.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu.telemetry as telemetry


def spmd_pipeline(block_fn: Callable, stage_params, x, *,
                  axis_name: str = "pipe", n_stages: int,
                  with_aux: bool = False):
    """Run microbatches through the pipeline. Call under shard_map.

    block_fn(layer_params, x) -> x : one block's forward
        (with_aux: -> (x, aux_scalar)).
    stage_params: pytree with leading dim [layers_per_stage] — THIS
        stage's shard.
    x: [M, mb, ...] microbatched input (replicated across stages).
    Returns [M, mb, ...] outputs (replicated); with_aux additionally a
    scalar: mean over microbatches of the sum of per-layer aux values
    (fill/drain steps that run on garbage buffers are masked out).
    """
    stage = jax.lax.axis_index(axis_name)
    m = x.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def apply_stage(xx):
        if with_aux:
            def body(carry, layer_params):
                h, aux = carry
                h, a = block_fn(layer_params, h)
                return (h, aux + a.astype(jnp.float32)), None
            (out, aux), _ = jax.lax.scan(
                body, (xx, jnp.zeros((), jnp.float32)), stage_params)
            return out, aux

        def body(h, layer_params):
            return block_fn(layer_params, h), None
        out, _ = jax.lax.scan(body, xx, stage_params)
        return out, jnp.zeros((), jnp.float32)

    buf0 = jnp.zeros(x.shape[1:], x.dtype)
    out0 = jnp.zeros_like(x)
    buf0, out0 = jax.lax.pcast((buf0, out0), (axis_name,), to="varying")

    def step(carry, t):
        buf, out, aux = carry
        # stage 0 ingests microbatch t (clamped; tail steps flush)
        inject = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        buf = jnp.where(stage == 0, inject, buf)
        y, a = apply_stage(buf)
        # stage s processes microbatch (t - s): real only inside the
        # window, fill/drain iterations compute on garbage and must not
        # pollute the aux accumulation
        valid = jnp.logical_and(t >= stage, t - stage < m)
        aux = aux + jnp.where(valid, a, 0.0)
        # last stage writes microbatch (t - (n_stages-1))
        widx = t - (n_stages - 1)
        should = jnp.logical_and(stage == n_stages - 1, widx >= 0)
        upd = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(widx, 0, m - 1), 0)
        out = jnp.where(should, upd, out)
        # rotate activations one stage down the ring
        y = jax.lax.ppermute(y, axis_name, perm)
        return (y, out, aux), None

    aux0 = jnp.zeros((), jnp.float32)
    aux0 = jax.lax.pcast(aux0, (axis_name,), to="varying")
    (_, out, aux), _ = jax.lax.scan(step, (buf0, out0, aux0),
                                    jnp.arange(m + n_stages - 1))
    # replicate the last stage's outputs to every shard
    out = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
    out = jax.lax.psum(out, axis_name)
    if with_aux:
        # per-stage masked sums -> global sum over (layer, microbatch),
        # then mean over microbatches (matches the dense twin)
        return out, jax.lax.psum(aux, axis_name) / m
    return out


def spmd_pipeline_interleaved(block_fn: Callable, stage_params, x, *,
                              axis_name: str = "pipe", n_stages: int,
                              n_rounds: int, with_aux: bool = False):
    """Interleaved (virtual-stage / Megatron-style) schedule: each stage
    owns ``n_rounds`` NON-contiguous layer chunks and every microbatch
    circles the ring ``n_rounds`` times, so the fill/drain bubble
    shrinks from (S-1)/(M+S-1) to (S-1)/(V·M+S-1) — each fill tick is
    1/V of a GPipe stage's work. Autodiff mirrors the schedule for the
    backward pass. Call under shard_map.

    stage_params: pytree [1, V, layers_per_chunk, ...] — THIS stage's
        shard; chunk v of stage s holds global layer block (v·S + s).
    x: [M, mb, ...] microbatched input (replicated); M must be >= S
        (a round-v activation re-enters stage 0 at tick v·M+m, which
        precedes its arrival when M < S-1+1).
    Returns [M, mb, ...] (+ aux scalar when with_aux), identical math
    to the sequential layer scan.
    """
    stage = jax.lax.axis_index(axis_name)
    m = x.shape[0]
    assert m >= n_stages, (
        f"interleaved schedule needs microbatches >= stages "
        f"({m} < {n_stages})")
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    ticks = n_rounds * m + n_stages - 1

    def apply_chunk(v_idx, xx):
        chunk = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a[0], v_idx, 0,
                                                   keepdims=False),
            stage_params)
        if with_aux:
            def body(carry, layer_params):
                h, aux = carry
                h, a = block_fn(layer_params, h)
                return (h, aux + a.astype(jnp.float32)), None
            (out, aux), _ = jax.lax.scan(
                body, (xx, jnp.zeros((), jnp.float32)), chunk)
            return out, aux

        def body(h, layer_params):
            return block_fn(layer_params, h), None
        out, _ = jax.lax.scan(body, xx, chunk)
        return out, jnp.zeros((), jnp.float32)

    buf0 = jnp.zeros(x.shape[1:], x.dtype)
    out0 = jnp.zeros_like(x)
    queue0 = jnp.zeros_like(x)  # stage-0 re-entry waiting room
    aux0 = jnp.zeros((), jnp.float32)
    buf0, out0, queue0, aux0 = jax.lax.pcast(
        (buf0, out0, queue0, aux0), (axis_name,), to="varying")

    def step(carry, t):
        buf, queue, out, aux = carry
        # a round-(v) microbatch m finished stage S-1 at tick v·M+m+S-1
        # and its rotation lands here NOW (tick t = v·M+m+S): park it in
        # slot m until its round-(v+1) start tick (v+1)·M+m
        arr_idx = t - n_stages
        park = jax.lax.dynamic_update_index_in_dim(
            queue, buf, jnp.maximum(arr_idx, 0) % m, 0)
        queue = jnp.where(arr_idx >= 0, park, queue)
        # stage 0 input: round 0 injects externally, later rounds read
        # the waiting room; other stages read the ring buffer
        inject = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        waiting = jax.lax.dynamic_index_in_dim(
            queue, jnp.clip(t, 0, ticks) % m, axis=0, keepdims=False)
        s0_in = jnp.where(t < m, inject, waiting)
        xx = jnp.where(stage == 0, s0_in, buf)
        # chunk index: stage s at tick t works round v = (t-s)//M
        v_idx = jnp.clip((t - stage) // m, 0, n_rounds - 1)
        y, a = apply_chunk(v_idx, xx)
        valid = jnp.logical_and(t >= stage,
                                t - stage < n_rounds * m)
        aux = aux + jnp.where(valid, a, 0.0)
        # last stage, final round: this microbatch is DONE
        widx = t - stage
        done = jnp.logical_and(stage == n_stages - 1,
                               jnp.logical_and(valid,
                                               widx >= (n_rounds - 1) * m))
        upd = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(widx - (n_rounds - 1) * m, 0, m - 1), 0)
        out = jnp.where(done, upd, out)
        y = jax.lax.ppermute(y, axis_name, perm)
        return (y, queue, out, aux), None

    (_, _, out, aux), _ = jax.lax.scan(
        step, (buf0, queue0, out0, aux0), jnp.arange(ticks))
    out = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
    out = jax.lax.psum(out, axis_name)
    if with_aux:
        return out, jax.lax.psum(aux, axis_name) / m
    return out


# bounded: entries key on bound methods, pinning the model instance and
# its compiled executable — unbounded growth across repeated model
# construction (tests, sweeps) would leak host memory
@functools.lru_cache(maxsize=32)
def _pipeline_callable(block_fn: Callable, mesh: Mesh, axis_name: str,
                       n_stages: int, x_spec, extra_axes: frozenset,
                       with_aux: bool, schedule: str = "gpipe",
                       n_rounds: int = 1):
    """Cached jitted partial-manual pipeline over ``axis_name`` (+ any
    ``extra_axes`` the stage body runs manual collectives over, e.g. a
    ring-attention seq axis).

    in_specs uses pytree-PREFIX specs, so one cache entry serves any
    stacked-params structure; cache key includes block_fn — pass a
    STABLE callable (a stored bound method, not a fresh lambda) or every
    call recompiles. jit is load-bearing: partial-manual shard_map
    cannot run eagerly; under an outer jit it inlines.
    """
    if schedule == "interleaved":
        fn = functools.partial(spmd_pipeline_interleaved, block_fn,
                               axis_name=axis_name, n_stages=n_stages,
                               n_rounds=n_rounds, with_aux=with_aux)
    else:
        fn = functools.partial(spmd_pipeline, block_fn,
                               axis_name=axis_name, n_stages=n_stages,
                               with_aux=with_aux)
    xs = x_spec if x_spec is not None else P()
    out_specs = (xs, P()) if with_aux else xs
    jitted = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis_name), xs),
        out_specs=out_specs,
        axis_names=frozenset({axis_name}) | extra_axes,
        check_vma=False))
    # program-profile hook (one flag check when profiling is off):
    # eagerly-dispatched pipeline programs register their cost/memory
    # analysis; under an outer jit the wrapper is tracer-transparent
    from bigdl_tpu.telemetry import programs
    return programs.maybe_wrap_jitted(
        f"train/pipeline/{schedule}x{n_stages}", "train", jitted)


def pipeline_forward(block_fn: Callable, stacked_params, x, mesh: Mesh, *,
                     axis_name: str = "pipe", n_microbatches: int,
                     x_spec=None, extra_axes=(), with_aux: bool = False,
                     schedule: str = "gpipe", n_rounds: int = 2):
    """Full-array convenience wrapper — composes with DP/TP/SP/EP.

    stacked_params: pytree with leading dim [n_layers] (n_layers divisible
    by the pipe axis size); x: [batch, ...] (batch divisible by
    n_microbatches). Returns [batch, ...] (with_aux: plus a scalar).

    Only ``axis_name`` (and ``extra_axes``) are mapped manually; every
    OTHER mesh axis stays an auto (GSPMD) axis, so a
    (data × pipe × model) mesh runs the microbatch dim data-parallel and
    the within-block matmuls tensor-parallel with XLA-inserted
    collectives, while activations ride the pipe ring via ppermute —
    DP×TP×PP in one jitted step. A sequence-parallel axis goes in
    ``extra_axes`` with ``x_spec`` sharding the microbatched activations'
    sequence dim (e.g. ``P(None, None, 'seq', None)`` for [M, mb, S, E])
    so the stage body's ring attention runs its own collectives.

    ``schedule="interleaved"`` (with ``n_rounds`` virtual chunks per
    stage) trades the GPipe bubble (stages−1)/(M+stages−1) for
    (stages−1)/(n_rounds·M+stages−1); the stacked params are re-laid
    out [S, V, layers/(S·V), ...] inside the jitted step, so with
    pipe-sharded rules GSPMD inserts one layer-permutation collective
    per step — measure before choosing it for small models.
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    assert b % n_microbatches == 0, (b, n_microbatches)
    # telemetry marks the host-side entry into the pipeline collective
    # (eager calls only: under an enclosing jit the python here runs
    # once at trace time, where a span would record a lie)
    pspan = telemetry.NOOP_SPAN if isinstance(x, jax.core.Tracer) \
        else telemetry.span("parallel/pipeline_forward",
                            schedule=schedule, stages=n_stages,
                            microbatches=n_microbatches)
    with pspan:
        return _pipeline_forward_impl(block_fn, stacked_params, x, mesh,
                                      axis_name, n_microbatches, x_spec,
                                      extra_axes, with_aux, schedule,
                                      n_rounds, n_stages)


def _pipeline_forward_impl(block_fn, stacked_params, x, mesh, axis_name,
                           n_microbatches, x_spec, extra_axes, with_aux,
                           schedule, n_rounds, n_stages):
    b = x.shape[0]
    mb = b // n_microbatches
    xm = x.reshape((n_microbatches, mb) + x.shape[1:])
    if schedule == "interleaved":
        leading = jax.tree.leaves(stacked_params)[0].shape[0]
        chunk = n_stages * n_rounds
        assert leading % chunk == 0, (leading, n_stages, n_rounds)
        lps = leading // chunk

        def relayout(a):
            a = a.reshape((n_rounds, n_stages, lps) + a.shape[1:])
            return jnp.moveaxis(a, 1, 0)  # [S, V, lps, ...]
        stacked_params = jax.tree.map(relayout, stacked_params)
    else:
        n_rounds = 1
    res = _pipeline_callable(block_fn, mesh, axis_name, n_stages,
                             x_spec, frozenset(extra_axes),
                             with_aux, schedule,
                             n_rounds)(stacked_params, xm)
    if with_aux:
        out, aux = res
        return out.reshape((b,) + out.shape[2:]), aux
    return res.reshape((b,) + res.shape[2:])
