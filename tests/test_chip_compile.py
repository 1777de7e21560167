"""Ask the TPU compiler, without a TPU.

libtpu is installed in the sandbox and compiles for a chip that is
*described*, not attached (``jax.experimental.topologies``). These tests
compile — never run — the kernels of the main path at the shapes
``chip_smoke.py`` uses, plus the jitted decode step and the LM train
steps, for a ``v5e:2x2``. What interpret mode cannot see shows here:
block shapes Mosaic refuses, unaligned dynamic slices, VMEM a kernel may
not have, collectives the partitioner did not put in.

Rules of this file (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture that skips when it cannot
be, never at import; shardings and shapes are built in fixtures or
tests; every compile runs in the test's own process; the persistent
compilation cache is off around them; and all of it is ONE file, because
only one process at a time may hold the TPU library.

Code that asks ``jax.default_backend()`` sees the CPU here, so the tests
steer it with ``kernels.use(KernelConfig(..., interpret=False))``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# the smoke's widths (GPT-2 small) — depth is cut for the whole-step
# compiles, widths are not
SLOTS, HEADS, HEAD_DIM, MAX_LEN = 16, 12, 64, 1024
LM = dict(vocab=50257, hidden=768, layers=2, heads=12, ffn=3072,
          positions=1024, seq=1024, batch=8)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo, no_persistent_cache):
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


@pytest.fixture
def one_chip_host(monkeypatch):
    """A one-chip machine's process sees one device, and there the
    flash dispatch takes its kernel under a plain ``jit``
    (``dispatch._partitioned``); this one sees tier-1's eight CPU
    devices, so say one, as the tests say ``interpret=False``."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def _on(sharding, *shape_dtypes):
    return [jax.ShapeDtypeStruct(s, np.dtype(d), sharding=sharding)
            for s, d in shape_dtypes]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _decode_operands(sharding, slots, hq, hkv, d, t, dtype):
    """``ragged_decode_attention``'s operands: q, one layer's K and V,
    lengths, write_at and the step's new K and V column."""
    cache, new = ((slots, hkv, d, t), dtype), ((slots, hkv, d), dtype)
    return _on(sharding, ((slots, hq, d), dtype), cache, cache,
               ((slots,), "int32"), ((slots,), "int32"), new, new)


def _compile_decode(args, **kwargs):
    """The kernel alone, the cache donated as the engine donates it."""
    from bigdl_tpu.kernels.ragged_decode import ragged_decode_attention

    return jax.jit(
        lambda *a: ragged_decode_attention(*a, **kwargs),
        donate_argnums=(1, 2)).lower(*args).compile()


def _grad_of(attn):
    def loss(q, k, v, *seg):
        return attn(q, k, v, *seg).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))


# ------------------------------------------------------------ kernels

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [1, 2, 4, 8, 16, 32, 64, 128, 256, 512,
                               1024])
def test_ragged_decode_compiles_at_ladder_rungs(one_chip, t, dtype):
    """The default TPU decode path at every rung of the powers-of-two
    ladder up to ``max_len`` 1024 — below one lane tile, one tile,
    several tiles — each reading the whole ``[slots, H, D, max_len]``
    cache through a block of that rung's width."""
    args = _decode_operands(one_chip, SLOTS, HEADS, HEADS, HEAD_DIM,
                            MAX_LEN, dtype)
    assert _has_kernel(_compile_decode(args, attend_len=t))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [8, 64, 200])
def test_ragged_decode_compiles_at_short_caches(one_chip, t, dtype):
    """A cache shorter than a lane tile, or not a whole number of them
    (a service with a small ``max_len``): the block is all of ``T``."""
    args = _decode_operands(one_chip, SLOTS, HEADS, HEADS, HEAD_DIM, t,
                            dtype)
    assert _has_kernel(_compile_decode(args))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ragged_decode_compiles_at_head_dim_128(one_chip, dtype):
    args = _decode_operands(one_chip, 16, 8, 8, 128, 512, dtype)
    assert _has_kernel(_compile_decode(args))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("segmented", [False, True])
def test_flash_attention_compiles_fwd_and_grad(one_chip, segmented,
                                               dtype):
    """The full-row flash kernel at the LM train shape of the smoke."""
    from bigdl_tpu.kernels.flash_attention import flash_attention

    shape = (8, 12, 1024, 64)
    args = _on(one_chip, (shape, dtype), (shape, dtype), (shape, dtype))
    if segmented:
        args += _on(one_chip, ((8, 1024), "int32"))

    def attn(q, k, v, *seg):
        return flash_attention(q, k, v, *seg, causal=True)

    assert _has_kernel(_compile(attn, *args))
    assert _has_kernel(_compile(_grad_of(attn), *args))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(8, 12, 1024, 64),
                                   (1, 8, 8192, 128)])
def test_blockwise_flash_compiles_fwd_and_grad(one_chip, shape, dtype):
    from bigdl_tpu.kernels.flash_attention import (
        blockwise_flash_attention)

    args = _on(one_chip, (shape, dtype), (shape, dtype), (shape, dtype),
               ((shape[0], shape[2]), "int32"))

    def attn(q, k, v, seg):
        return blockwise_flash_attention(q, k, v, seg, causal=True)

    assert _has_kernel(_compile(attn, *args))
    assert _has_kernel(_compile(_grad_of(attn), *args))


def test_full_row_flash_is_refused_past_vmem_and_dispatch_knows(
        one_chip, one_chip_host):
    """At ``[1, 8, 8192, 128]`` float32 the full-row kernel, at the
    chunk the dispatch would give it, asks for more VMEM than the
    32 MiB it may have — the compiler says so —
    and the dispatch layer's estimate routes that shape to the
    blockwise kernel instead, which compiles, forward and grad."""
    from bigdl_tpu import kernels
    from bigdl_tpu.kernels import KernelConfig
    from bigdl_tpu.kernels.flash_attention import flash_attention

    shape = (1, 8, 8192, 128)
    args = _on(one_chip, (shape, "float32"), (shape, "float32"),
               (shape, "float32"))
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(_grad_of(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=512)), *args)

    def attn(q, k, v):
        out = kernels.attention(q, k, v, causal=True)
        assert out is not None, "dispatch declined an eligible shape"
        return out

    with kernels.use(KernelConfig(flash_attention=True,
                                  interpret=False)):
        assert _has_kernel(_compile(attn, *args))
        assert _has_kernel(_compile(_grad_of(attn), *args))


def test_flash_vmem_budget_is_inside_what_the_compiler_takes(one_chip):
    """The largest shapes the default estimate (the 32 MiB the kernels
    ask the compiler for, less 4) still hands to the full-row kernel
    compile, backward included, at the chunk the dispatch gives them -
    512, the 256 and 384 of lengths no 512 divides, with and without a
    segment plane. The estimate errs high: the first length the
    compiler refuses is priced at 37 MiB or more (PERF.md section 6,
    PR 35). From S = 2048 on it is the forward's ``[S, chunk]`` score
    strip that binds."""
    from bigdl_tpu.kernels import KernelConfig, dispatch
    from bigdl_tpu.kernels.flash_attention import flash_attention

    budget = KernelConfig().resolve_vmem_budget()
    assert budget == 28 << 20
    for shape, dtype, chunk, segmented in (
            ((1, 2, 10752, 64), "bfloat16", 512, False),
            ((1, 2, 8704, 64), "float32", 512, True),
            ((1, 2, 7680, 128), "bfloat16", 512, True),
            ((1, 2, 4608, 128), "float32", 512, False),
            ((1, 2, 18176, 64), "bfloat16", 256, True),
            ((1, 2, 8960, 128), "bfloat16", 256, False),
            ((1, 2, 1152, 64), "bfloat16", 384, False)):
        itemsize = np.dtype(dtype).itemsize
        assert dispatch.flash_route(
            shape, itemsize, segmented=segmented, interpret=False,
            vmem_budget=budget) == ("full", chunk), (shape, dtype)
        # ... and from 2048 on, the next length of that chunk is over
        assert shape[2] < 2048 or dispatch._flash_vmem_bytes(
            shape[2] + 512, shape[3], itemsize, chunk) > budget, shape

        def attn(q, k, v, *seg):
            return flash_attention(q, k, v, *seg, causal=True,
                                   block_q=chunk)

        args = _on(one_chip, (shape, dtype), (shape, dtype),
                   (shape, dtype))
        if segmented:
            args += _on(one_chip, ((shape[0], shape[2]), "int32"))
        assert _has_kernel(_compile(_grad_of(attn), *args))


CELL = (4, 16, 1024, 64)    # gpt2m_train_b4s1024: one layer's q, k, v


def test_dispatched_attention_compiles_at_the_train_cells_shape(
        one_chip, one_chip_host):
    """``kernels.attention`` as a TPU's default policy has it (flash
    on, compiled) at the train cell's ``[4, 16, 1024, 64]`` bfloat16:
    the dispatch takes the kernel, forward and gradient."""
    from bigdl_tpu import kernels
    from bigdl_tpu.kernels import KernelConfig

    def attn(q, k, v):
        out = kernels.attention(q, k, v, causal=True)
        assert out is not None, "dispatch declined the train cell's shape"
        return out

    args = _on(one_chip, *[(CELL, "bfloat16")] * 3)
    with kernels.use(KernelConfig(flash_attention=True, interpret=False)):
        assert "bigdl_flash_fwd" in _compile(attn, *args).as_text()
        text = _compile(_grad_of(attn), *args).as_text()
    assert "bigdl_flash_fwd" in text and "bigdl_flash_bwd" in text


def test_a_transformer_block_keeps_no_score_matrix(one_chip,
                                                   one_chip_host):
    """One ``TransformerBlock``'s ``value_and_grad`` at the train
    cell's shape (batch 4 x 1024 tokens, 1024 wide, 16 heads, bfloat16
    activations): the compiled program holds the flash kernels and no
    ``[4, 16, 1024, 1024]`` array of any dtype - with flash off it
    holds several."""
    from bigdl_tpu import kernels
    from bigdl_tpu.kernels import KernelConfig
    from bigdl_tpu.models.transformer import TransformerBlock

    b, heads, s, d = CELL
    block = TransformerBlock(heads * d, heads, 4 * heads * d).training()
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0))
    state = block.initial_state()

    def loss(params, x):
        half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        out, _ = block.apply(half, state, x, training=True)
        return out.astype(jnp.float32).sum()

    args = (jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), params),
            *_on(one_chip, ((b, s, heads * d), "bfloat16")))
    scores = f"[{b},{heads},{s},{s}]"

    def compiled_under(config):
        # a new function a policy: the policy is read when a function
        # is traced, and jax keeps the trace of one it has seen
        with kernels.use(config):
            return _compile(jax.value_and_grad(
                lambda p, x: loss(p, x)), *args).as_text()

    text = compiled_under(KernelConfig(flash_attention=True,
                                       interpret=False))
    assert "bigdl_flash_fwd" in text and "bigdl_flash_bwd" in text
    assert scores not in text
    assert scores in compiled_under(KernelConfig.off())


def test_int8_gemm_compiles(one_chip):
    from bigdl_tpu.kernels.int8_gemm import pallas_quantized_matmul

    args = _on(one_chip, ((256, 512), "int8"), ((1024, 512), "int8"),
               ((256, 1), "float32"), ((1024,), "float32"))
    assert _has_kernel(_compile(
        lambda x, w, xs, ws: pallas_quantized_matmul(x, w, xs, ws),
        *args))


def test_bundled_flash_escape_hatch_compiles(one_chip):
    """``nn.attention`` routes HBM-busting score matrices to jax's
    bundled flash kernel on a TPU; ``_flash_eligible`` decides the
    shapes, nothing catches what the kernel raises."""
    from bigdl_tpu.nn.attention import (_flash_attention_tpu,
                                        _flash_eligible)

    shape = (1, 8, 16384, 128)
    q, k, v = _on(one_chip, (shape, "bfloat16"), (shape, "bfloat16"),
                  (shape, "bfloat16"))
    assert _flash_eligible(q, None, 0.0, False)
    assert _has_kernel(_compile(
        lambda q, k, v: _flash_attention_tpu(q, k, v, True), q, k, v))


# ------------------------------------------------- whole jitted steps

@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(0)
    model = TransformerLM(LM["vocab"], hidden_size=LM["hidden"],
                          num_layers=LM["layers"], num_heads=LM["heads"],
                          ffn_size=LM["ffn"], max_len=LM["positions"],
                          tie_embeddings=True)
    model.ensure_initialized()
    return model


def _compile_program(lm, one_chip, slots, kind="decode"):
    """The engine's own ``<kind>/1024`` program at ``slots`` slots,
    compiled at the policy a TPU gets by default (decode + int8 on, no
    interpreter); returns ``(compiled, donated cache specs, pallas
    dispatches taken by the trace)``."""
    from bigdl_tpu import kernels
    from bigdl_tpu.analysis.programs import abstract_tree
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.kernels import KernelConfig
    from bigdl_tpu.serving.compile_cache import (BucketLadder,
                                                 CompileCache)

    engine = DecodeEngine(CompileCache(), BucketLadder(MAX_LEN), slots, 4)
    lm.evaluate()
    programs = engine.abstract_programs(
        lm, abstract_tree(lm.get_parameters()),
        abstract_tree(lm.get_state()))
    (program,) = [p for p in programs if p[0] == f"{kind}/{MAX_LEN}"]
    _, jitted, args = program
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), args)
    with kernels.use(KernelConfig(decode_attention=True,
                                  int8_matmul=True, interpret=False)):
        before = kernels.dispatch.taken_in_thread()
        compiled = jitted.lower(*args).compile()
        taken = kernels.dispatch.taken_in_thread() - before
    return compiled, jax.tree.leaves(args[2]), taken


def test_decode_step_holds_the_kernel(one_chip, lm):
    """The engine's own decode program for the top rung, at the policy a
    TPU gets by default: its compiled text must contain the Mosaic
    kernel."""
    compiled, _, taken = _compile_program(lm, one_chip, SLOTS)
    assert taken == LM["layers"]
    assert _has_kernel(compiled)


def _root_opcode(module, op):
    """``op``'s opcode, or its root's where it is a fusion."""
    fused = module.computations.get(op.called.get("calls", ""))
    if op.opcode != "fusion" or fused is None:
        return op.opcode
    return next(o.opcode for o in fused.ops if o.is_root)


def _cache_writes_outside_the_kernel(module, shapes):
    """Operations of a parsed decode step that write a layer's cache
    entry any other way than the decode kernel does: a
    ``dynamic-update-slice`` (alone or as a fusion's root) whose result
    has one of the entries' ``shapes``, and a ``while`` that carries
    one (the per-slot loop XLA made of ``_write_columns``; the expert
    layer's dispatch plan keeps a loop over small int32 vectors)."""
    dims = {"[" + ",".join(map(str, shape)) + "]" for shape in shapes}
    return [(op.name, _root_opcode(module, op), op.result_type)
            for _, op in module.find_ops()
            if _root_opcode(module, op) in ("while", "dynamic-update-slice")
            and any(d in op.result_type for d in dims)]


def test_decode_step_holds_no_copy_of_the_cache(one_chip, lm):
    """The serve cell's decode program (64 slots x 1024, GPT-2-small
    widths, 2 layers): the cache is stored as the kernel reads it and
    the kernel writes the step's new columns itself, so the compiled
    step aliases every donated cache leaf to its output, keeps less
    than one layer's K in temporaries, and holds no ``copy`` and no
    ``slice`` (alone or as a fusion's root) of a whole layer's K or V,
    no ``dynamic-update-slice`` whose result is a layer's entry and no
    ``while`` at all: nothing but the kernel touches an entry. (The
    stacked ``[layers, slots, H, T, D]`` cache read 1.41 GB of
    temporaries here: two transposing copies, a slice and a stack
    rewrite per layer and K/V; ``_write_columns``' per-slot loop then
    took 1,536 read-modify-write turns a step.)"""
    from bigdl_tpu.analysis.hlo import parse_hlo

    slots = 64
    compiled, cache_leaves, _ = _compile_program(lm, one_chip, slots)
    assert _has_kernel(compiled)
    layer_elems = slots * HEADS * MAX_LEN * HEAD_DIM
    layer_bytes = layer_elems * 4
    assert len(cache_leaves) == 2 * LM["layers"]
    assert all(int(np.prod(a.shape)) == layer_elems
               for a in cache_leaves)

    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= len(cache_leaves) * layer_bytes
    assert mem.temp_size_in_bytes < layer_bytes, mem.temp_size_in_bytes

    module = parse_hlo(compiled.as_text())
    moved = [(op.name, _root_opcode(module, op), op.result_type)
             for _, op in module.find_ops()
             if op.result_elements() >= layer_elems
             and _root_opcode(module, op) in ("copy", "slice", "transpose")]
    assert not moved, moved
    written = _cache_writes_outside_the_kernel(
        module, {a.shape for a in cache_leaves})
    assert not written, written
    assert not list(module.find_ops("while"))


def _train_step_args(lm, optim, policy, replicated, batch_sharding,
                     opt_state_shardings=None):
    """Abstract arguments of the LM train step: everything on
    ``replicated`` but the batch and, where given, the optimizer state
    (``opt_state_shardings`` maps its tree to a tree of shardings)."""
    from bigdl_tpu.analysis.programs import _key_struct, _train_abstract

    lm.training()
    params, opt_state, mstate = _train_abstract(lm, optim, policy)

    def place(tree, shardings=None):
        if shardings is None:
            shardings = jax.tree.map(lambda _: replicated, tree)
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            tree, shardings)

    tokens = jax.ShapeDtypeStruct((LM["batch"], LM["seq"]), np.int32,
                                  sharding=batch_sharding)
    return (place(params),
            place(opt_state, opt_state_shardings
                  and opt_state_shardings(opt_state)),
            place(mstate), place(_key_struct()),
            jax.ShapeDtypeStruct((), np.float32, sharding=replicated),
            tokens, tokens)


def test_lm_train_step_compiles_and_fits(one_chip, lm):
    """The smoke's LM train step (bf16 mixed, Adam) for one chip."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.precision import PrecisionPolicy

    policy, optim = PrecisionPolicy.named("bf16_mixed"), Adam(3e-4)
    args = _train_step_args(lm, optim, policy, one_chip, one_chip)
    step = build_train_step(lm, nn.SequenceCrossEntropyCriterion(),
                            optim, precision=policy)
    mem = step.lower(*args).compile().memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 << 30)


def test_zero2_step_on_four_chips_has_its_collectives(four_chips, lm):
    """``chip_smoke.py --chips 4``'s step, compiled for a four-chip
    ``data`` mesh: the gradient reduce-scatter (the TPU compiler's fused
    ``all-reduce-scatter``) and the parameter all-gather are in it."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.analysis.hlo import collective_counts
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.parallel import ZeroConfig
    from bigdl_tpu.parallel.zero import tree_zero_specs
    from bigdl_tpu.precision import PrecisionPolicy

    mesh, cfg = four_chips, ZeroConfig(stage=2)
    policy, optim = PrecisionPolicy.named("bf16_mixed"), Adam(3e-4)

    def zero2(opt_state):
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                            tree_zero_specs(opt_state, mesh, cfg))

    args = _train_step_args(lm, optim, policy, NamedSharding(mesh, P()),
                            NamedSharding(mesh, P("data")), zero2)
    step = build_train_step(lm, nn.SequenceCrossEntropyCriterion(),
                            optim, zero=cfg, mesh=mesh, precision=policy)
    counts = collective_counts(step.lower(*args).compile())
    assert counts["reduce-scatter"]["total"] > 0, counts
    assert counts["all-gather"]["total"] > 0, counts


def test_a_partitioned_step_keeps_the_einsum_form(four_chips, lm):
    """The same step at the policy a TPU gets by default (flash on,
    compiled). The step is a plain ``jit`` that the partitioner splits
    over the data axis, and it cannot split a Mosaic kernel - the
    full-row kernel, called past the dispatch with its batch sharded,
    is refused at lowering - so the dispatch declines every layer's
    attention (``reason=mesh``) and the step compiles in the einsum
    form, as before the default changed."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import kernels
    from bigdl_tpu.kernels import KernelConfig
    from bigdl_tpu.kernels.flash_attention import flash_attention
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.optim.optimizer import build_train_step
    from bigdl_tpu.precision import PrecisionPolicy

    mesh = four_chips
    shape = (LM["batch"], LM["heads"], LM["seq"], 64)
    qkv = _on(NamedSharding(mesh, P("data")), *[(shape, "bfloat16")] * 3)
    with pytest.raises(NotImplementedError, match="partitioned"):
        jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=512)).lower(*qkv)

    policy, optim = PrecisionPolicy.named("bf16_mixed"), Adam(3e-4)
    args = _train_step_args(lm, optim, policy, NamedSharding(mesh, P()),
                            NamedSharding(mesh, P("data")))
    with kernels.use(KernelConfig(flash_attention=True, interpret=False)):
        step = build_train_step(lm, nn.SequenceCrossEntropyCriterion(),
                                optim, mesh=mesh, precision=policy)
        asked = (kernels.dispatch.taken_in_thread("flash"),
                 kernels.dispatch.declined_in_thread("flash"))
        text = step.lower(*args).compile().as_text()
    assert kernels.dispatch.taken_in_thread("flash") == asked[0]
    assert (kernels.dispatch.declined_in_thread("flash")
            == asked[1] + LM["layers"])
    assert "bigdl_flash" not in text


def test_inside_a_manual_mesh_the_kernel_is_taken(four_chips):
    """What a layer that owns the mesh can do about it: inside a
    ``shard_map`` over every axis the program is one device's again,
    the dispatch sees that in the trace and takes the kernel, and the
    compiler lowers it - each chip its two rows of the batch."""
    from bigdl_tpu import kernels
    from bigdl_tpu.kernels import KernelConfig

    def attn(q, k, v):
        out = kernels.attention(q, k, v, causal=True)
        assert out is not None, "dispatch declined inside a manual mesh"
        return out

    rows = P("data")
    sharded = jax.shard_map(attn, mesh=four_chips, in_specs=rows,
                            out_specs=rows, check_vma=False)
    args = _on(NamedSharding(four_chips, rows),
               *[((8, 12, 1024, 64), "bfloat16")] * 3)
    with kernels.use(KernelConfig(flash_attention=True, interpret=False)):
        assert "bigdl_flash_fwd" in _compile(sharded, *args).as_text()
        text = _compile(_grad_of(sharded), *args).as_text()
    assert "bigdl_flash_fwd" in text and "bigdl_flash_bwd" in text


# ------------------------- a decoder of unlike layers (ISSUE 28's cell)

# trinity-large-ep8's published widths; depth cut to one window layer
# and one global one, both with the expert FFN
PD = dict(vocab=25024, hidden=3072, heads=48, kv_heads=8, head_dim=128,
          ffn=12288, expert=3072, router=256, held=32, top_k=4,
          window=4096, slots=48, max_len=6144, rungs=(4096, 6144))


@pytest.mark.parametrize("rows,tile_m", [(704, 16), (8192, 128)])
def test_grouped_product_compiles(one_chip, rows, tile_m):
    """The expert layer's kernel at the cell's two shapes: a decode
    step's 48 x 4 pairs on tiles of 16 rows, a prefill chunk's on tiles
    of 128; 32 experts of 3072 x 3072 in bfloat16."""
    from bigdl_tpu.kernels.moe_gmm import grouped_matmul_pallas

    e, k = PD["held"], PD["hidden"]
    x, w, te, nt = _on(one_chip, ((rows, k), "bfloat16"),
                       ((e, k, PD["expert"]), "bfloat16"),
                       ((rows // tile_m,), "int32"), ((1,), "int32"))
    compiled = _compile(
        lambda x, w, te, nt: grouped_matmul_pallas(x, w, te, nt,
                                                   tile_m=tile_m),
        x, w, te, nt)
    assert "bigdl_moe_gmm" in compiled.as_text()


@pytest.mark.parametrize("t", PD["rungs"])
def test_ragged_decode_compiles_with_grouped_heads(one_chip, t):
    """48 query heads over 8 K/V heads of 128: the query block is the
    ``[6, 128]`` group, the cache block ``[128, rung]``."""
    args = _decode_operands(one_chip, PD["slots"], PD["heads"],
                            PD["kv_heads"], PD["head_dim"], PD["max_len"],
                            "bfloat16")
    assert _has_kernel(_compile_decode(args, attend_len=t))


@pytest.mark.parametrize("slots,hq,hkv,d,t,rung,dtype", [
    (64, 12, 12, 64, 1024, 1024, "float32"),     # gpt2s_serve_closed64
    (48, 48, 8, 128, 4096, 4096, "bfloat16"),    # trinity: a ring
    (48, 48, 8, 128, 6144, 4096, "bfloat16"),    # its global entry,
    (48, 48, 8, 128, 6144, 6144, "bfloat16"),    # both rungs
    (48, 48, 8, 128, 64, 64, "bfloat16"),        # a short cache
])
def test_ragged_decode_compiles_at_the_serve_cells(one_chip, slots, hq,
                                                   hkv, d, t, rung,
                                                   dtype):
    """Both serve cells' decode kernels at their real shapes, the K/V
    tile on the grid at the width the kernel sizes for the shape (one
    tile a GPT-2 row, 2048 columns of Trinity's), running max, sum and
    accumulator in VMEM scratch across a slot-head's tiles - and the
    step's new column written by the same kernel: a ``[D, 128]`` lane
    tile a slot-head cut out of the fetched K/V tile at a dynamic,
    lane-aligned offset (all 64 columns of the short cache), K and V
    aliased to their outputs with nothing held beside them."""
    from bigdl_tpu.kernels.ragged_decode import block_columns, kv_tile

    tile = kv_tile(block_columns(t, rung), d, hq // hkv,
                   np.dtype(dtype).itemsize)
    assert tile == {1024: 1024, 64: 64}.get(t, 2048)
    compiled = _compile_decode(
        _decode_operands(one_chip, slots, hq, hkv, d, t, dtype),
        attend_len=rung)
    assert "bigdl_ragged_decode" in compiled.as_text()
    cache_bytes = 2 * slots * hkv * d * t * np.dtype(dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 100


@pytest.fixture(scope="module")
def pattern_programs(one_chip):
    """The engine's own prefill (rung 4096) and decode (rung 6144)
    programs for a window layer and a global one at the published
    widths, bfloat16, compiled at the policy a TPU gets by default."""
    from bigdl_tpu import kernels
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.generation.kv_cache import KVCache
    from bigdl_tpu.kernels import KernelConfig
    from bigdl_tpu.models import PatternDecoderLM
    from bigdl_tpu.serving.compile_cache import (BucketLadder,
                                                 CompileCache)

    model = PatternDecoderLM(
        PD["vocab"], PD["hidden"],
        [("window", "experts"), ("global", "experts")], PD["heads"],
        PD["kv_heads"], PD["head_dim"], PD["ffn"], window=PD["window"],
        max_len=PD["max_len"], expert_size=PD["expert"],
        shared_size=PD["expert"], router_experts=PD["router"],
        local_experts=(0, PD["held"]), top_k=PD["top_k"],
        route_scale=2.448, embed_scale=PD["hidden"] ** 0.5).evaluate()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    model.set_parameters(params)         # the cache's dtype follows it
    state = jax.eval_shape(model.initial_state)
    engine = DecodeEngine(CompileCache(),
                          BucketLadder(PD["max_len"], PD["rungs"]),
                          PD["slots"], 4)
    spec = KVCache.spec_for_model(model, PD["slots"], PD["max_len"])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape,
                                                    np.dtype(dtype))
    out = {"pieces": engine.prefill_shape(model, 4096),   # interpreting
           "cache": tuple(e[n] for n in "kv" for e in spec)}
    with kernels.use(KernelConfig(decode_attention=True, int8_matmul=True,
                                  grouped_matmul=True, interpret=False)):
        rows, chunk = out["shape"] = engine.prefill_shape(model, 4096)
        calls = {
            "prefill": (engine._prefill_jit(model, 4096, lambda: None,
                                            chunk == 4096),
                        (params, state, spec,
                         sds((rows, chunk), "int32"),
                         sds((rows,), "int32"), sds((rows,), "int32"),
                         sds((rows,), "int32"))),
            "decode": (engine._decode_jit(model, 6144, lambda: None),
                       (params, state, spec,
                        sds((PD["slots"],), "int32"),
                        sds((PD["slots"],), "int32"),
                        sds((PD["slots"],), "bool")))}
        for name, (jitted, args) in calls.items():
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            out[name] = jitted.lower(*args).compile()
    return out


def test_pattern_decode_step_aliases_both_kinds_of_cache(pattern_programs):
    """A ring of 4096 columns and a whole context of 6144 side by side,
    8 K/V heads each: the compiled decode step aliases every leaf of
    both kinds to its output, keeps temporaries under a hundredth of
    the cache (under one layer's K), and holds the decode kernel (2)
    and the grouped product (2 layers x gate, up, down); the kernel
    writes both kinds' new columns, so no ``dynamic-update-slice``
    results in an entry and no ``while`` carries one."""
    compiled, leaves = (pattern_programs["decode"],
                        pattern_programs["cache"])
    assert [a.shape[3] for a in leaves] == [4096, 6144, 4096, 6144]
    assert all(a.dtype == jnp.bfloat16 and a.shape[1] == PD["kv_heads"]
               for a in leaves)
    cache_bytes = sum(int(np.prod(a.shape)) * 2 for a in leaves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 100
    text = compiled.as_text()
    assert text.count("bigdl_ragged_decode") >= 2
    assert text.count("bigdl_moe_gmm") >= 6
    from bigdl_tpu.analysis.hlo import parse_hlo

    written = _cache_writes_outside_the_kernel(
        parse_hlo(text), {a.shape for a in leaves})
    assert not written, written


@pytest.fixture(scope="module")
def gpt2_prefill(one_chip, lm):
    """The GPT-2 serve cell's prefill program (4 rows x rung 1024, 2
    layers of GPT-2-small's widths) at the policy a TPU gets."""
    compiled, leaves, taken = _compile_program(lm, one_chip, 64, "prefill")
    return {"prefill": compiled, "taken": taken, "cache": leaves}


@pytest.mark.parametrize("family,rows,vocab,hidden", [
    ("pattern", 1, PD["vocab"], PD["hidden"]),
    ("gpt2", 4, LM["vocab"], LM["hidden"])])
def test_pattern_prefill_holds_no_scores_and_one_row_of_logits(
        family, rows, vocab, hidden, request):
    """Every served decoder's prefill returns ``[rows, V]`` and holds
    no logits but one position a row (the engine hands each model
    ``logits_at``; no ``[rows, Sq, V]`` array is sliced afterwards).

    *pattern*, rung 4096 at 48 heads: the einsum form in one shot would
    hold ``[4, 48, 4096, 4096]`` float32 scores (12.9 GB) and ``[4,
    4096, 25024]`` logits. The engine decides by itself: where the
    model's attention is scoreless at the rung (compiled kernels, 4096
    <= the window) ONE row in one shot through the bundled flash
    kernel, else (interpreting, as the CPU does) ``[1, 512]`` pieces.
    The compiled one-shot program holds no result as large as one row's
    ``heads x 4096 x 4096`` scores, the flash kernel twice and the
    grouped product six times. *gpt2*, 4 rows of rung 1024 at a head of
    64: not scoreless, so the einsum form and NO kernel
    (``decode_attn_roofline`` counts every ``tpu_custom_call`` of a
    trace as the decode kernel)."""
    from bigdl_tpu.analysis.hlo import parse_hlo

    programs = request.getfixturevalue(
        {"pattern": "pattern_programs", "gpt2": "gpt2_prefill"}[family])
    compiled = programs["prefill"]
    module = parse_hlo(compiled.as_text())
    ops = [op for _, op in module.find_ops()
           if op.opcode not in ("tuple", "parameter")]
    wide = [(op.name, op.result_type) for op in ops
            if f",{vocab}]" in op.result_type.split("{")[0]
            and op.result_elements() > hidden * vocab]
    assert not wide, wide
    logits = jax.tree.leaves(compiled.out_info)[0]
    assert logits.shape == (rows, vocab)
    if family == "gpt2":
        assert programs["taken"] == 0
        assert "tpu_custom_call" not in compiled.as_text()
        return
    assert programs["pieces"] == (1, 512)
    assert programs["shape"] == (1, 4096)
    assert compiled.as_text().count("tpu_custom_call") >= 8
    one_shot = PD["heads"] * 4096 * 4096
    big = [(op.name, op.result_type) for op in ops
           if op.result_elements() >= one_shot]
    assert not big, big
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 << 30


# ------------------------------------- the state-space decode step

@pytest.mark.parametrize("slots,hq,n,lanes,groups", [
    (128, 64, 128, 128, 8),      # the Nemotron cell's layer
    (16, 8, 64, 128, 1)])        # one group, all rows a program
def test_ssm_decode_compiles(one_chip, slots, hq, n, lanes, groups):
    """``bigdl_ssm_decode`` at the serve cell's state ``[128, 64, 128,
    128]`` float32 (two heads a lane tile): the state aliased to its
    output, and a block of rows that fits the kernel's VMEM."""
    from bigdl_tpu.kernels.ssm_decode import ssm_decode_pallas

    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,
                                          sharding=one_chip)
    compiled = ssm_decode_pallas.lower(
        sds(slots, hq, n, lanes), sds(slots, hq, lanes),
        sds(slots, hq, lanes), sds(slots, n, 2 * groups)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "bigdl_ssm_decode" in text
    assert "output_to_operand_aliasing={{1}: (0, {})}" in text


@pytest.fixture(scope="module")
def hybrid_programs(one_chip):
    """The Nemotron cell's decode step (128 slots, rung 4096) and its
    one-shot prefill of ``[1, 2048]``, at the configuration's full
    widths and eleven layers, compiled at the policy a TPU gets."""
    import json
    import os
    import sys

    from bigdl_tpu import kernels
    from bigdl_tpu.generation.engine import DecodeEngine
    from bigdl_tpu.generation.kv_cache import KVCache
    from bigdl_tpu.kernels import KernelConfig
    from bigdl_tpu.serving.compile_cache import (BucketLadder,
                                                 CompileCache)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.models import nemotron_h

    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron3-super-ep4.json")) as f:
        cfg = json.load(f)
    slots = 128
    model = nemotron_h.build_program_model(cfg).evaluate()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    model.set_parameters(params)
    state = jax.eval_shape(model.initial_state)
    engine = DecodeEngine(CompileCache(), BucketLadder(4096, [2048, 4096]),
                          slots, 4)
    spec = KVCache.spec_for_model(model, slots, 4096)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape,
                                                    np.dtype(dtype))
    out = {"spec": spec}
    with kernels.use(KernelConfig.all_on(interpret=False)):
        rows, chunk = out["shape"] = engine.prefill_shape(model, 2048)
        calls = {
            "prefill": (engine._prefill_jit(model, 2048, lambda: None,
                                            chunk == 2048),
                        (params, state, spec, sds((rows, chunk), "int32"),
                         sds((rows,), "int32"), sds((rows,), "int32"),
                         sds((rows,), "int32"))),
            "decode": (engine._decode_jit(model, 4096, lambda: None),
                       (params, state, spec, sds((slots,), "int32"),
                        sds((slots,), "int32"), sds((slots,), "bool")))}
        for name, (jitted, args) in calls.items():
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            out[name] = jitted.lower(*args).compile()
    return out


def test_hybrid_decode_step_updates_the_state_in_place(hybrid_programs):
    """Five ``[128, 64, 128, 128]`` float32 states, five convolution
    tails and one K/V entry in ONE donated pytree: the compiled step
    aliases all of it, keeps temporaries under a tenth of one layer's
    state, holds the three kernels (5 state updates, 1 attention, 5 x 2
    grouped products) and nothing but the kernel produces an array of a
    state's shape - no copy, no select over 5.4 GB."""
    from bigdl_tpu.analysis.hlo import parse_hlo

    compiled, spec = hybrid_programs["decode"], hybrid_programs["spec"]
    leaves = jax.tree.leaves(spec)
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in leaves)
    state_bytes = 128 * 64 * 128 * 128 * 4
    assert [sorted(e) for e in spec] == [
        ["conv", "ssm"], [], ["conv", "ssm"], [], ["conv", "ssm"], [],
        ["conv", "ssm"], ["k", "v"], [], ["conv", "ssm"], []]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < state_bytes // 10, \
        mem.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 16
    for name in ("bigdl_ssm_decode", "bigdl_ragged_decode",
                 "bigdl_moe_gmm"):
        assert name in text
    module = parse_hlo(text)
    made = [(op.name, op.opcode) for _, op in module.find_ops()
            if "f32[128,64,128,128]" in op.result_type.split("{")[0]
            and op.opcode not in ("parameter", "custom-call", "tuple",
                                  "get-tuple-element")]
    assert not made, made


@pytest.mark.parametrize("family,fixture", [
    ("gpt2", "gpt2_prefill"), ("pattern", "pattern_programs"),
    ("hybrid", "hybrid_programs")])
def test_a_fresh_prefill_holds_no_copy_of_the_cache(family, fixture,
                                                    request):
    """A one-shot (``fresh``) prefill reads nothing of the cache, in all
    three served families: the compiled program aliases every donated
    leaf to its output, holds no operation under ``kv_write/gather``,
    and no ``slice``, ``copy``, ``gather`` or ``while`` (alone, as a
    fusion's root or as an asynchronous pair) results in an array of a
    layer's slots - only the aliased ``kv_write/scatter`` writes touch
    an entry. *gpt2* is the case that paid: XLA served the gather of
    FOUR rows of ``f32[64,12,64,1024]`` by copying each whole layer
    entry in column pieces (a ``slice-done f32[64,12,64,256]``, a
    two-output ``f32[64,12,64,384]`` fusion and two ``while`` loops a
    leaf; 256.8 MB of temporaries). *pattern* and *hybrid* prefill ONE
    row, which already compiled to a ``dynamic-slice``: they are the
    guard (no copy of a ``[128, 64, 128, 128]`` state either)."""
    from bigdl_tpu.analysis.hlo import parse_hlo

    programs = request.getfixturevalue(fixture)
    compiled = programs["prefill"]
    leaves = (programs["cache"] if "cache" in programs
              else jax.tree.leaves(programs["spec"]))
    if family == "gpt2":
        assert [a.shape for a in leaves] == [(64, 12, 64, 1024)] * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    module = parse_hlo(compiled.as_text())
    ops = [op for _, op in module.find_ops()]
    gathers = [(op.name, op.opcode) for op in ops
               if op.metadata.get("op_name", "").endswith("kv_write/gather")]
    assert not gathers, gathers
    # an array of a layer's slots: a leaf's shape up to its last axis,
    # at any width of that (the copies came in column pieces)
    marks = {"[" + ",".join(map(str, a.shape[:-1])) + "," for a in leaves}
    moved = [(op.name, _root_opcode(module, op), op.result_type)
             for op in ops
             if _root_opcode(module, op).split("-")[0] in (
                 "slice", "copy", "gather", "while")
             and any(m in op.result_type for m in marks)]
    assert not moved, moved
    if family == "gpt2":
        assert mem.temp_size_in_bytes < 240e6, mem.temp_size_in_bytes


def test_hybrid_programs_fit_the_chip(hybrid_programs):
    """Weights 9.3 GB + cache 3.26 GB + the larger program's
    temporaries stay inside 16 GiB; the prefill is one row in one shot
    and attends through the flash kernel."""
    assert hybrid_programs["shape"] == (1, 2048)
    text = hybrid_programs["prefill"].as_text()
    assert "flash_attention" in text and "bigdl_moe_gmm" in text
    worst = max(m.argument_size_in_bytes + m.temp_size_in_bytes
                for m in (hybrid_programs[k].memory_analysis()
                          for k in ("prefill", "decode")))
    assert worst < 14.5e9, worst
