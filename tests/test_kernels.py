"""Pallas kernel layer (bigdl_tpu.kernels, ISSUE 12): interpret-mode
equivalence of all three kernels against the pure-jnp fallback on CPU
— the real kernel bodies execute in tier-1. Pins the load-bearing
claims: the flash forward is tolerance-bounded vs the einsum reference
and its backward passes a gradient check vs ``jax.grad`` of the
reference; the packed-slab segment-mask case is BIT-EXACT per token vs
the unpacked reference; the ragged decode kernel matches the
length-masked reference at EVERY length in a bucket (length 1 and
bucket max included); the int8 kernel is BITWISE equal to
dequantize-then-matmul; greedy decode through the service stays
token-bit-identical to full re-forward with kernels enabled; the
per-bucket compiled-program count stays <= 2 per version (kernel
variants add no program keys); and program profiles carry the
``kernel=pallas|reference`` label the bench KERNELS row compares."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import kernels
from bigdl_tpu.kernels.flash_attention import (blockwise_flash_attention,
                                               fit_block,
                                               flash_attention)
from bigdl_tpu.kernels.int8_gemm import pallas_quantized_matmul
from bigdl_tpu.kernels.ragged_decode import (block_columns, kv_tile,
                                             ragged_decode_attention)
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.utils.random import RandomGenerator

ON = kernels.KernelConfig.all_on(interpret=True)
OFF = kernels.KernelConfig.off()


def _qkv(b=2, h=2, s=32, d=8, seed=0):
    r = np.random.default_rng(seed)
    return tuple(jnp.asarray(r.standard_normal((b, h, s, d))
                             .astype(np.float32)) for _ in range(3))


def _ref_attention(q, k, v, causal=False, mask=None):
    """The einsum reference — the exact fallback path
    ``nn.attention.dot_product_attention`` runs with kernels off."""
    from bigdl_tpu.nn.attention import dot_product_attention
    with kernels.use(OFF):
        return dot_product_attention(q, k, v, causal=causal, mask=mask,
                                     use_flash=False)


def _tiny_lm(vocab=50, seed=3):
    RandomGenerator.set_seed(seed)
    m = TransformerLM(vocab_size=vocab, hidden_size=16, num_layers=2,
                      num_heads=2, max_len=64).evaluate()
    m.ensure_initialized()
    return m


# ------------------------------------------------------------- config

class TestKernelConfig:
    def test_env_grammar(self):
        on = kernels.KernelConfig.from_env("1")
        assert on.flash_attention and on.decode_attention \
            and on.int8_matmul
        off = kernels.KernelConfig.from_env("off")
        assert not off.any_enabled
        subset = kernels.KernelConfig.from_env("flash,int8")
        assert subset.flash_attention and subset.int8_matmul
        assert not subset.decode_attention
        with pytest.raises(ValueError):
            kernels.KernelConfig.from_env("flash,warp")  # typo is loud

    def test_default_off_on_cpu_and_label(self):
        # tier-1 runs on CPU: the resolved default must be the
        # reference path ("defaulting off on CPU")
        kernels.configure(None)  # re-resolve the backend default
        assert not kernels.get_config().any_enabled
        assert kernels.active_label() == "reference"
        assert not kernels.enabled("flash")

    @pytest.mark.parametrize("backend, env, flash", [
        ("tpu", None, True),     # PR 35: the measured default
        ("cpu", None, False),
        ("tpu", "0", False),     # the switch still switches it off
        ("tpu", "decode,int8", False),
    ])
    def test_default_by_backend(self, monkeypatch, backend, env, flash):
        """``_default()`` turns flash on for a ``tpu`` backend and for
        no other; ``BIGDL_KERNELS`` still overrides it."""
        from bigdl_tpu.kernels import config
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if env is None:
            monkeypatch.delenv("BIGDL_KERNELS", raising=False)
        else:
            monkeypatch.setenv("BIGDL_KERNELS", env)
        cfg = config._default()
        assert cfg.flash_attention is flash
        assert cfg.decode_attention is (backend == "tpu" and env != "0")
        # on a TPU nothing interprets unless asked to
        assert cfg.resolve_interpret() is (backend != "tpu")

    def test_no_field_holds_a_tile_size(self):
        """Chunk and tile follow from the shape (``flash_route``): the
        config has seven fields and none of them is a block size."""
        import dataclasses
        names = [f.name for f in dataclasses.fields(kernels.KernelConfig)]
        assert len(names) == 7
        assert not [n for n in names if n.startswith("block")]

    def test_use_scope_restores(self):
        before = kernels.get_config()
        with kernels.use(ON):
            assert kernels.enabled("decode")
            assert kernels.active_label() == "pallas"
        assert kernels.get_config() == before

    def test_unknown_op_raises(self):
        with pytest.raises(ValueError):
            kernels.enabled("warp")

    def test_interpret_auto_resolves_off_tpu(self):
        assert kernels.KernelConfig.all_on().resolve_interpret() is True
        assert kernels.KernelConfig.all_on(
            interpret=False).resolve_interpret() is False

    def test_fit_block(self):
        assert fit_block(256, 128) == 128
        assert fit_block(48, 128) == 48
        assert fit_block(48, 16) == 16
        assert fit_block(19, 16) == 1  # prime: one query per tile

    def test_fit_block_aligned_to_vector_tiles(self):
        """Tiles the TPU compiler takes: a multiple of the alignment
        that divides the dim, else the WHOLE dim — never something in
        between (a 125-row tile of 1000 is a dynamic slice Mosaic
        cannot prove aligned)."""
        from bigdl_tpu.kernels.common import sublanes
        assert fit_block(1024, 128, align=8) == 128
        assert fit_block(1000, 128, align=8) == 40
        assert fit_block(1000, 128, align=16) == 1000   # 16 ∤ any divisor
        assert fit_block(19, 16, align=8) == 19         # prime: one tile
        assert fit_block(4, 128, align=8) == 4          # below a tile
        assert fit_block(8192, 128, align=128) == 128
        assert fit_block(48, 128, align=128) == 48
        assert (sublanes("float32"), sublanes("bfloat16"),
                sublanes("int8")) == (8, 16, 32)


# ----------------------------------------------------- flash attention

class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("s,block_q", [(32, 16), (48, 16), (19, 16)])
    def test_forward_matches_reference(self, causal, s, block_q):
        q, k, v = _qkv(s=s, seed=1)
        out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              interpret=True)
        ref = _ref_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)

    def test_segment_mask_matches_reference(self):
        q, k, v = _qkv(s=48, seed=2)
        r = np.random.default_rng(3)
        seg = jnp.asarray(r.integers(0, 3, (2, 48)).astype(np.int32))
        out = flash_attention(q, k, v, seg, causal=True, block_q=16,
                              interpret=True)
        mask = seg[:, None, :, None] == seg[:, None, None, :]
        ref = _ref_attention(q, k, v, causal=True, mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)
        assert np.isfinite(np.asarray(out)).all()

    def test_gradient_check_vs_reference(self):
        """The backward kernel vs jax.grad of the einsum reference —
        plain causal and segment-masked."""
        q, k, v = _qkv(s=32, seed=4)
        r = np.random.default_rng(5)
        seg = jnp.asarray(r.integers(1, 3, (2, 32)).astype(np.int32))
        mask = seg[:, None, :, None] == seg[:, None, None, :]

        for kern_loss, ref_loss in [
            (lambda q_, k_, v_: (flash_attention(
                q_, k_, v_, causal=True, block_q=16,
                interpret=True) ** 2).sum(),
             lambda q_, k_, v_: (_ref_attention(
                 q_, k_, v_, causal=True) ** 2).sum()),
            (lambda q_, k_, v_: (flash_attention(
                q_, k_, v_, seg, causal=True, block_q=16,
                interpret=True) ** 2).sum(),
             lambda q_, k_, v_: (_ref_attention(
                 q_, k_, v_, causal=True, mask=mask) ** 2).sum()),
        ]:
            gk = jax.grad(kern_loss, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gk, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-4, rtol=1e-4)

    def test_grad_under_jit(self):
        """The custom-VJP kernel must survive the train-step shape:
        jit(grad(...)) — the compile path every real step takes."""
        q, k, v = _qkv(s=32, seed=6)

        @jax.jit
        def g(q_, k_, v_):
            return jax.grad(lambda t: (flash_attention(
                t, k_, v_, causal=True, block_q=16,
                interpret=True) ** 2).sum())(q_)

        ref = jax.grad(lambda t: (_ref_attention(
            t, k, v, causal=True) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(g(q, k, v)),
                                   np.asarray(ref), atol=2e-4,
                                   rtol=1e-4)

    def test_packed_slab_agrees_with_unpacked(self):
        """THE packed-slab contract with the kernel enabled: every
        document's logits in a packed slab agree with running that
        document alone through the same kernel — the datapipe
        guarantee (test_datapipe) survives the pallas path. The
        tolerance is float32 reduction order between programs of
        different shapes (a ``[1, L]`` row against the slab; observed
        1e-7 to 6e-7 on logits of order 1); a leak across documents
        moves them by more than 1e-4 (test_datapipe's control), and the
        bitwise form of the leak-proof property is the next test's."""
        import bigdl_tpu.datapipe.packing as dp

        m = _tiny_lm()
        p, st = m.get_parameters(), m.get_state()
        r = np.random.RandomState(1)
        docs = [r.randint(1, 50, r.randint(4, 10)).astype(np.int32)
                for _ in range(7)]
        toks, segs, pos, _ = dp.pack_documents(docs, 16)
        with kernels.use(ON):
            packed = np.asarray(m.apply(p, st, [toks, segs, pos],
                                        training=False)[0])
            checked = 0
            for row in range(len(toks)):
                for sid in range(1, int(segs[row].max()) + 1):
                    at = np.flatnonzero(segs[row] == sid)
                    alone = np.asarray(m.apply(
                        p, st, toks[row, at][None].astype(np.int32),
                        training=False)[0])
                    np.testing.assert_allclose(
                        packed[row, at], alone[0], rtol=1e-5, atol=1e-6,
                        err_msg=f"row {row} seg {sid} leaked across "
                                f"documents")
                    checked += 1
        assert checked >= 7

    def test_packed_slab_content_independence_bitwise(self):
        """The leak-proof property at kernel level, robust to any
        block geometry: a document's output is bitwise UNCHANGED when
        every other segment's content is scrambled — masked lanes
        contribute exact zeros, so foreign content cannot perturb even
        the last ulp."""
        r = np.random.default_rng(7)
        h, d, s = 2, 8, 64
        l1, l2 = 25, 30  # doc boundaries straddle the 16-wide tiles
        seg = np.zeros((1, s), np.int32)
        seg[0, :l1], seg[0, l1:l1 + l2] = 1, 2
        q, k, v = _qkv(b=1, h=h, s=s, d=d, seed=8)
        out = np.asarray(flash_attention(q, k, v, jnp.asarray(seg),
                                         causal=True, block_q=16,
                                         interpret=True))
        scr = jnp.asarray(r.standard_normal((1, h, s, d))
                          .astype(np.float32))
        doc2 = (jnp.arange(s) >= l1) & (jnp.arange(s) < l1 + l2)
        sel = doc2[None, None, :, None]
        q2 = jnp.where(sel, q, scr)
        k2 = jnp.where(sel, k, scr)
        v2 = jnp.where(sel, v, scr)
        out2 = np.asarray(flash_attention(q2, k2, v2, jnp.asarray(seg),
                                          causal=True, block_q=16,
                                          interpret=True))
        assert np.array_equal(out[:, :, l1:l1 + l2, :],
                              out2[:, :, l1:l1 + l2, :])

    def test_dispatch_declines_off_and_ineligible(self):
        q, k, v = _qkv()
        with kernels.use(OFF):
            assert kernels.attention(q, k, v, causal=True) is None
        with kernels.use(ON):
            # rank-3 input is the einsum path's, not the kernel's
            assert kernels.attention(q[:, 0], k[:, 0], v[:, 0]) is None
            assert kernels.attention(q, k, v, causal=True) is not None

    def test_over_vmem_budget_routes_blockwise_or_declines(self):
        """Past the VMEM budget the dispatch routes to the BLOCKWISE
        long-context kernel (S=32K runs fused, no einsum fallback);
        with long_context switched off the historical decline→einsum
        escape hatch survives — Mosaic never sees an OOM shape."""
        from bigdl_tpu.kernels import dispatch, flash_attention as fa
        big = jax.ShapeDtypeStruct((1, 1, 32768, 128), jnp.bfloat16)
        cfg = kernels.KernelConfig.all_on(interpret=False)
        assert dispatch._flash_vmem_bytes(32768, 128, 2, 512) \
            > cfg.resolve_vmem_budget()
        with kernels.use(kernels.KernelConfig.all_on(
                interpret=False, long_context=False)):
            assert kernels.attention(big, big, big,
                                     causal=True) is None
        small = _qkv(s=512, d=64, seed=13)
        with kernels.use(ON):
            assert kernels.attention(*small, causal=True) is not None
        # a tiny budget steers a small shape down the blockwise path
        # (the same routing an over-budget shape takes on TPU) — and
        # the result stays tolerance-equal to the einsum reference
        routed = []
        real = fa.blockwise_flash_attention

        def spy(*a, **kw):
            routed.append(True)
            return real(*a, **kw)

        fa.blockwise_flash_attention = spy
        try:
            with kernels.use(kernels.KernelConfig.all_on(
                    interpret=True, vmem_budget_mb=1)):
                q, k, v = _qkv(b=1, h=1, s=1024, d=16, seed=13)
                out = kernels.attention(q, k, v, causal=True)
        finally:
            fa.blockwise_flash_attention = real
        assert routed and out is not None
        ref = _ref_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)

    def test_vmem_budget_env_and_bounds(self):
        """BIGDL_VMEM_BUDGET_MB overrides the default (the 32 MiB the
        kernels ask the compiler for, less 4); an explicit
        vmem_budget_mb wins over the env; nonsense values are loud."""
        import os
        from bigdl_tpu.kernels.common import FLASH_VMEM_LIMIT_MB
        cfg = kernels.KernelConfig.all_on()
        assert cfg.resolve_vmem_budget() == BUDGET
        assert BUDGET == (FLASH_VMEM_LIMIT_MB - 4) << 20
        os.environ["BIGDL_VMEM_BUDGET_MB"] = "3"
        try:
            assert cfg.resolve_vmem_budget() == 3 * 1024 * 1024
            explicit = kernels.KernelConfig.all_on(vmem_budget_mb=5)
            assert explicit.resolve_vmem_budget() == 5 * 1024 * 1024
            os.environ["BIGDL_VMEM_BUDGET_MB"] = "lots"
            with pytest.raises(ValueError):
                cfg.resolve_vmem_budget()
        finally:
            del os.environ["BIGDL_VMEM_BUDGET_MB"]
        with pytest.raises(ValueError):
            kernels.KernelConfig.all_on(
                vmem_budget_mb=0).resolve_vmem_budget()

    def test_mask_and_segments_are_exclusive(self):
        """A free-form mask cannot ride the kernel, so passing both
        mask= and segments= raises instead of silently dropping what
        the mask adds beyond segment equality."""
        from bigdl_tpu.nn.attention import dot_product_attention
        q, k, v = _qkv(s=16, seed=14)
        seg = jnp.ones((2, 16), jnp.int32)
        mask = seg[:, None, :, None] == seg[:, None, None, :]
        with pytest.raises(ValueError, match="not both"):
            dot_product_attention(q, k, v, mask=mask, segments=seg)
        # segments alone derives the same-segment mask for the
        # fallback: kernels-off output == explicit-mask output bitwise
        with kernels.use(OFF):
            a = dot_product_attention(q, k, v, causal=True,
                                      segments=seg, use_flash=False)
            b = dot_product_attention(q, k, v, causal=True, mask=mask,
                                      use_flash=False)
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_model_forward_on_vs_off_tolerance(self):
        """The full TransformerLM forward with kernels on agrees with
        the reference forward at float32 reduction tolerance, and
        greedy argmax is unchanged."""
        m = _tiny_lm(seed=9)
        p, st = m.get_parameters(), m.get_state()
        toks = np.random.RandomState(2).randint(
            1, 50, (2, 16)).astype(np.int32)
        ref = np.asarray(m.apply(p, st, toks, training=False)[0])
        with kernels.use(ON):
            out = np.asarray(m.apply(p, st, toks, training=False)[0])
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=0)
        assert np.array_equal(out.argmax(-1), ref.argmax(-1))


# ------------------------------------------------ the flash selection

BUDGET = 28 << 20     # the default: the kernels' 32 MiB less 4


class TestFlashRoute:
    """``dispatch.flash_route``: full-row, blockwise or declined, from
    the shape alone (PERF.md section 6, PR 35 has the measured table
    the rule rests on)."""

    @pytest.mark.parametrize("shape, itemsize, segmented, want", [
        # the train cell and its neighbours in the measured table
        ((4, 16, 1024, 64), 2, False, ("full", 512)),
        ((8, 12, 1024, 64), 2, False, ("full", 512)),
        ((4, 16, 1024, 64), 4, False, ("full", 512)),
        ((4, 16, 512, 64), 2, False, ("full", 512)),
        ((2, 16, 2048, 64), 2, False, ("full", 512)),
        ((1, 16, 4096, 64), 2, False, ("full", 512)),
        # no 512 divides 768: the largest lane-aligned divisor under it
        ((4, 16, 768, 64), 2, False, ("full", 384)),
        # under 512 keys the einsum form measured faster
        ((4, 16, 256, 64), 2, False, ("declined", "shape")),
        ((4, 16, 384, 64), 2, False, ("declined", "shape")),
        # held by the full-row kernel at the 32 MiB it asks for
        ((1, 16, 8192, 64), 2, False, ("full", 512)),
        ((1, 8, 4096, 128), 2, False, ("full", 512)),
        ((1, 2, 10752, 64), 2, False, ("full", 512)),
        # past the full-row kernel's VMEM: the blockwise kernel
        ((1, 2, 11264, 64), 2, False, ("blockwise", 512)),
        ((1, 8, 16384, 64), 2, False, ("blockwise", 512)),
        ((1, 1, 32768, 128), 2, False, ("blockwise", 512)),
        ((1, 1, 33024, 128), 2, False, ("blockwise", 384)),
        # a packed slab takes the full-row form or none
        ((4, 16, 1024, 64), 2, True, ("full", 512)),
        ((1, 16, 8192, 64), 2, True, ("full", 512)),
        ((1, 8, 16384, 64), 2, True, ("declined", "vmem")),
        # no 512 divides these: the largest lane-aligned divisor
        ((4, 16, 1152, 64), 2, False, ("full", 384)),
        ((4, 16, 1280, 64), 2, False, ("full", 256)),
        # ... which at 128 is on neither kernel's measured path
        ((4, 16, 640, 64), 2, False, ("declined", "shape")),
        ((4, 16, 896, 64), 2, False, ("declined", "shape")),
        ((1, 16, 1664, 64), 2, False, ("declined", "shape")),
        ((1, 1, 33664, 128), 2, False, ("declined", "shape")),
        # no whole number of lane tiles: blocks nobody compiled
        ((4, 16, 600, 64), 2, False, ("declined", "shape")),
        ((4, 16, 900, 64), 2, True, ("declined", "shape")),
        ((1, 4, 5000, 64), 2, False, ("declined", "shape")),
    ])
    def test_compiled_rule(self, shape, itemsize, segmented, want):
        assert dispatch_route(shape, itemsize, segmented,
                              interpret=False) == want

    def test_long_context_off_declines_past_the_budget(self):
        from bigdl_tpu.kernels.dispatch import flash_route
        assert flash_route((1, 8, 16384, 64), 2, segmented=False,
                           interpret=False, vmem_budget=BUDGET,
                           long_context=False) == ("declined", "vmem")

    @pytest.mark.parametrize("shape, want", [
        ((2, 2, 32, 8), ("full", 32)),      # no length is too short
        ((2, 4, 256, 64), ("full", 256)),
        ((1, 1, 1100, 8), ("full", 275)),   # any divisor will do
    ])
    def test_the_interpreter_takes_test_sizes(self, shape, want):
        assert dispatch_route(shape, 4, False, interpret=True) == want

    def test_the_estimate_counts_the_forwards_strip(self):
        """From S = 2048 on the forward's ``[S, chunk]`` float32 score
        strip is what binds, not the backward's accumulators."""
        from bigdl_tpu.kernels.dispatch import _flash_vmem_bytes
        strip = 4096 * 512 * 4
        assert _flash_vmem_bytes(4096, 64, 2, 512) > strip
        assert _flash_vmem_bytes(10752, 64, 2, 512) <= BUDGET
        assert _flash_vmem_bytes(11264, 64, 2, 512) > BUDGET


def dispatch_route(shape, itemsize, segmented, interpret):
    from bigdl_tpu.kernels.dispatch import flash_route
    return flash_route(shape, itemsize, segmented=segmented,
                       interpret=interpret, vmem_budget=BUDGET)


class TestDispatchedAttention:
    """The path ``nn.attention`` takes with flash on, against the
    einsum form it replaces, at cuts of the train cell's shape with
    its head size: ``[2, 4, 256, 64]`` (one chunk) and ``[1, 2, 1024,
    64]`` (the cell's two chunks of 512: the diagonal one and the one
    left of it)."""

    @pytest.mark.parametrize("segmented", [False, True])
    @pytest.mark.parametrize("dtype, tol", [("float32", 2e-5),
                                            ("bfloat16", 3e-2)])
    @pytest.mark.parametrize("shape", [(2, 4, 256, 64), (1, 2, 1024, 64)])
    def test_forward_and_grad_match_the_einsum_form(self, shape, dtype,
                                                    tol, segmented):
        r = np.random.default_rng(40)
        q, k, v, cot = (jnp.asarray(r.standard_normal(shape), dtype)
                        for _ in range(4))
        seg = mask = None
        if segmented:
            seg = jnp.asarray(np.sort(r.integers(0, 3, shape[::2]),
                                      axis=1).astype(np.int32))
            mask = seg[:, None, :, None] == seg[:, None, None, :]
        f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731

        def loss(attn):
            return lambda *a: (attn(*a).astype(jnp.float32)
                               * cot.astype(jnp.float32)).sum()

        def fused(q_, k_, v_):
            with kernels.use(kernels.KernelConfig(flash_attention=True,
                                                  interpret=True)):
                out = kernels.attention(q_, k_, v_, causal=True,
                                        segment_ids=seg)
            assert out is not None
            return out

        ref = lambda *a: _ref_attention(*a, causal=True,  # noqa: E731
                                        mask=mask)
        before = kernels.dispatch.taken_in_thread("flash")
        np.testing.assert_allclose(f32(fused(q, k, v)), f32(ref(q, k, v)),
                                   atol=tol, rtol=0)
        assert kernels.dispatch.taken_in_thread("flash") == before + 1
        got = jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype
            np.testing.assert_allclose(f32(a), f32(b), atol=10 * tol,
                                       rtol=0)

    def test_declined_twin_counts_every_reason(self):
        q, k, v = _qkv()
        d0 = kernels.dispatch.declined_in_thread("flash")
        with kernels.use(OFF):
            assert kernels.attention(q, k, v) is None          # config
        with kernels.use(ON):
            assert kernels.attention(q[:, 0], k[:, 0], v[:, 0]) is None
        assert kernels.dispatch.declined_in_thread("flash") == d0 + 2


class TestPartitionedPrograms:
    """The partitioner cannot split a Mosaic kernel, and jax refuses to
    lower one in a program it may split: compiled, the flash dispatch
    takes the kernel only where the trace shows one device's program -
    a process that sees one device, or a ``shard_map`` with every mesh
    axis manual - and declines with ``reason=mesh`` elsewhere
    (``DistriOptimizer``'s step on a data mesh is a plain ``jit``).
    Traced here, never lowered; tests/test_chip_compile.py compiles the
    same cases for a described four-chip mesh."""

    SHAPE = (4, 2, 2048, 64)      # a quarter of it still a kernel's
    COMPILED = kernels.KernelConfig(flash_attention=True, interpret=False)

    @staticmethod
    def _mesh(**axes):
        n = math.prod(axes.values())
        return jax.sharding.Mesh(
            np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
            tuple(axes))

    def _took(self, wrap=lambda f: f):
        """Whether a trace of the dispatched attention, through
        ``wrap``, took the kernel - and the reasons it gave if not."""
        import bigdl_tpu.telemetry as telemetry
        declined = telemetry.registry().counter(
            "kernels/dispatch/reference")
        took = []

        def attn(q, k, v):
            out = kernels.attention(q, k, v, causal=True)
            took.append(out is not None)
            return q if out is None else out

        x = jax.ShapeDtypeStruct(self.SHAPE, jnp.bfloat16)
        before = declined.value(op="flash", reason="mesh")
        with kernels.use(self.COMPILED):
            jax.eval_shape(wrap(attn), x, x, x)
        after = declined.value(op="flash", reason="mesh")
        assert after == before + (took == [False])
        return took == [True]

    def test_a_process_with_one_device_takes_the_kernel(self, monkeypatch):
        monkeypatch.setattr(jax, "device_count", lambda: 1)
        assert self._took()
        assert self._took(jax.jit)

    def test_a_plain_jit_beside_other_devices_declines(self):
        assert jax.device_count() > 1          # conftest's eight
        assert not self._took()
        assert not self._took(jax.jit)

    @pytest.mark.parametrize("axes, manual, took", [
        ({"data": 4}, None, True),                  # all of one axis
        ({"data": 2, "model": 2}, None, True),      # all of two
        ({"data": 2, "seq": 2}, {"seq"}, False),    # Ulysses beside DP
        ({"data": 1, "seq": 4}, {"seq"}, False),    # jax asks by name
    ])
    def test_inside_a_shard_map_the_manual_axes_decide(self, axes, manual,
                                                       took):
        from jax.sharding import PartitionSpec as P
        mesh = self._mesh(**axes)
        first = next(iter(axes))
        spec = P(first) if manual is None else P(None, None, "seq")

        def wrap(f):
            # check_vma off, as every shard_map of this repo has it:
            # pallas_call states no varying axes for what it returns
            kw = {} if manual is None else dict(
                axis_names=frozenset(manual))
            return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec,
                                         out_specs=spec, check_vma=False,
                                         **kw))

        assert self._took(wrap) is took

    def test_the_interpreter_is_not_asked(self):
        """Interpreted, a kernel is plain jax operations, which the
        partitioner splits like any others: tier-1 keeps running the
        kernel bodies on its eight CPU devices."""
        q, k, v = _qkv()
        with kernels.use(ON):
            assert kernels.attention(q, k, v, causal=True) is not None


# -------------------------------------------- blockwise (long-context)

class TestBlockwiseFlashAttention:
    """The online-softmax long-context path: VMEM working set
    independent of S. Tolerance contract (the rescale rounds per block
    boundary — flash_attention.py's section comment), checked against
    the same einsum reference at several block geometries, including
    boundaries that straddle documents and ragged tiles."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("block_k", [8, 16, 48])
    def test_forward_matches_reference(self, causal, block_k):
        q, k, v = _qkv(s=48, seed=30)
        out = blockwise_flash_attention(q, k, v, causal=causal,
                                        block_q=16, block_k=block_k,
                                        interpret=True)
        ref = _ref_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)

    def test_segment_mask_matches_reference(self):
        """Packed segment masks under the blockwise form — including
        key tiles that are FULLY masked for some query row (the
        all-masked-carry NaN hazard the exp guards exist for)."""
        q, k, v = _qkv(s=48, seed=31)
        r = np.random.default_rng(32)
        seg = jnp.asarray(r.integers(0, 3, (2, 48)).astype(np.int32))
        out = blockwise_flash_attention(q, k, v, seg, causal=True,
                                        block_q=16, block_k=16,
                                        interpret=True)
        mask = seg[:, None, :, None] == seg[:, None, None, :]
        ref = _ref_attention(q, k, v, causal=True, mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)
        assert np.isfinite(np.asarray(out)).all()

    def test_gradient_check_vs_reference(self):
        """The two-pass tiled backward vs jax.grad of the einsum
        reference — plain causal and segment-masked."""
        q, k, v = _qkv(s=32, seed=33)
        r = np.random.default_rng(34)
        seg = jnp.asarray(r.integers(1, 3, (2, 32)).astype(np.int32))
        mask = seg[:, None, :, None] == seg[:, None, None, :]

        for kern_loss, ref_loss in [
            (lambda q_, k_, v_: (blockwise_flash_attention(
                q_, k_, v_, causal=True, block_q=16, block_k=8,
                interpret=True) ** 2).sum(),
             lambda q_, k_, v_: (_ref_attention(
                 q_, k_, v_, causal=True) ** 2).sum()),
            (lambda q_, k_, v_: (blockwise_flash_attention(
                q_, k_, v_, seg, causal=True, block_q=16, block_k=8,
                interpret=True) ** 2).sum(),
             lambda q_, k_, v_: (_ref_attention(
                 q_, k_, v_, causal=True, mask=mask) ** 2).sum()),
        ]:
            gk = jax.grad(kern_loss, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gk, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-4, rtol=1e-4)

    def test_grad_under_jit(self):
        """jit(grad(...)) — the train-step compile shape — over the
        blockwise custom VJP."""
        q, k, v = _qkv(s=32, seed=35)

        @jax.jit
        def g(q_, k_, v_):
            return jax.grad(lambda t: (blockwise_flash_attention(
                t, k_, v_, causal=True, block_q=16, block_k=16,
                interpret=True) ** 2).sum())(q_)

        ref = jax.grad(lambda t: (_ref_attention(
            t, k, v, causal=True) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(g(q, k, v)),
                                   np.asarray(ref), atol=2e-4,
                                   rtol=1e-4)

    def test_matches_fullrow_kernel_tolerance(self):
        """The two kernel forms agree within float32 reduction
        tolerance — the property that makes the budget-based routing
        switch invisible to callers."""
        q, k, v = _qkv(s=64, seed=36)
        a = blockwise_flash_attention(q, k, v, causal=True, block_q=16,
                                      block_k=16, interpret=True)
        b = flash_attention(q, k, v, causal=True, block_q=16,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=0)


# ------------------------------------------------------- ragged decode

def _decode_operands(slots, h, d, t, seed, dtype="float32"):
    """q ``[slots, H, D]`` and one layer's cache ``[slots, H, D, T]``
    (time last — the form ``KVCache`` stores)."""
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.standard_normal((slots, h, d)), dtype),
            jnp.asarray(r.standard_normal((slots, h, d, t)), dtype),
            jnp.asarray(r.standard_normal((slots, h, d, t)), dtype))


def _attend_cache(q, k, v, lengths, **kwargs):
    """The kernel over a cache that already holds every slot's newest
    column: handed that column (``lengths - 1``) again as the step's
    new token, it attends the columns ``< lengths`` and writes back
    what is there. Returns the output alone."""
    at = jnp.clip(lengths.astype(jnp.int32), 1,
                  kwargs.get("attend_len", k.shape[3])) - 1
    newest = lambda c: jnp.take_along_axis(  # noqa: E731
        c, at[:, None, None, None], axis=3)[..., 0]
    out, k2, v2 = ragged_decode_attention(q, k, v, lengths, at, newest(k),
                                          newest(v), **kwargs)
    assert k2.shape == k.shape and v2.dtype == v.dtype
    return out


def _masked_decode_reference(q, k, v, lengths):
    """The length-masked einsum over a ``[slots, H, D, T]`` cache."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    t = k.shape[3]
    s = jnp.einsum("shd,shdt->sht", f32(q), f32(k)) \
        / math.sqrt(q.shape[-1])
    mask = jnp.arange(t)[None, None, :] < lengths[:, None, None]
    return jnp.einsum("sht,shdt->shd",
                      jax.nn.softmax(jnp.where(mask, s, -jnp.inf),
                                     axis=-1), f32(v))


class TestRaggedDecode:
    def test_every_length_in_bucket(self):
        """The ragged kernel vs the length-masked reference at EVERY
        length of a 16-wide bucket — length 1 and bucket-max
        included."""
        slots, h, t, d = 4, 2, 16, 8
        q, k, v = _decode_operands(slots, h, d, t, seed=10)
        for n in range(1, t + 1):
            lengths = jnp.full((slots,), n, jnp.int32)
            out = _attend_cache(q, k, v, lengths, interpret=True)
            ref = _masked_decode_reference(q, k, v, lengths)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, rtol=0,
                                       err_msg=f"length {n}")

    def test_mixed_ragged_lengths(self):
        """Three 128-column tiles under the dynamic loop bound, with
        lengths on both sides of every tile edge."""
        slots, h, t, d = 6, 2, 384, 8
        q, k, v = _decode_operands(slots, h, d, t, seed=11)
        lengths = jnp.asarray(np.array([1, 127, 128, 129, 300, 384],
                                       np.int32))
        out = _attend_cache(q, k, v, lengths, interpret=True)
        ref = _masked_decode_reference(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)

    @pytest.mark.parametrize("t, dtype", [
        (1, "bfloat16"),      # one column: the static single-tile path
        (100, "float32"),     # under one lane tile: one whole-T tile
        (1000, "float32"),    # no 128-aligned divisor: one whole-T tile
        (1024, "float32"),    # eight lane tiles under a dynamic bound
        (256, "bfloat16"),    # bf16 tiles are (16, 128): D = 16 fits
    ])
    def test_tpu_legal_tiles_match_reference(self, t, dtype):
        """The tiles the TPU compiler takes (whole lane tiles or the
        whole of T) give the same answers as the masked reference."""
        slots, h, d = 3, 2, 16
        q, k, v = _decode_operands(slots, h, d, t, 13, dtype)
        lengths = jnp.asarray(np.array([1, max(1, t // 2), t], np.int32))
        out = _attend_cache(q, k, v, lengths, interpret=True)
        assert out.shape == (slots, h, d) and out.dtype == q.dtype
        ref = _masked_decode_reference(q, k, v, lengths)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)), np.asarray(ref), rtol=0,
            atol=1e-5 if dtype == "float32" else 2e-2)

    @pytest.mark.parametrize("attend_len", [256, 32])
    def test_lower_rung_reads_the_whole_cache_unsliced(self, attend_len):
        """A lower ladder rung on a full-``T`` cache: the kernel takes
        the whole array and a block of that rung's width (rounded up
        to a lane tile below 128), and agrees with the masked einsum
        over the first ``attend_len`` columns — whatever lies beyond
        them, and beyond each slot's length, is never read into the
        result."""
        slots, h, d, t = 3, 2, 8, 512
        q, k, v = _decode_operands(slots, h, d, t, seed=14)
        lengths = jnp.asarray(np.array([1, attend_len // 2, attend_len],
                                       np.int32))
        out = _attend_cache(q, k, v, lengths, attend_len=attend_len,
                            interpret=True)
        ref = _masked_decode_reference(q, k[..., :attend_len],
                                       v[..., :attend_len], lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)
        # poison everything past the rung: the answer must not move
        poison = jnp.arange(t) >= attend_len
        out_p = _attend_cache(
            q, jnp.where(poison, jnp.nan, k), jnp.where(poison, 1e9, v),
            lengths, attend_len=attend_len, interpret=True)
        assert np.array_equal(np.asarray(out_p), np.asarray(out))

    @pytest.mark.parametrize("a", [64, 128, 1024, 4096, 6144])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("g", [1, 6])
    @pytest.mark.parametrize("d", [64, 128])
    def test_tile_edges_and_poisoned_tails(self, d, g, dtype, a):
        """Both serve cells' query groups, head sizes and dtypes over
        short, one-tile and many-tile blocks: lengths at 1, one under /
        at / one over every edge of the tile the kernel sizes for the
        shape, and full, against the masked einsum — with every column
        past a slot's length poisoned with NaN in K and in V, which a
        skipped tile never fetches and a walked tile masks out of both
        products."""
        tile = kv_tile(a, d, g, np.dtype(dtype).itemsize)
        edges = range(tile, a, tile)
        lens = sorted({1, a} | {e + o for e in edges for o in (-1, 0, 1)})
        r = np.random.default_rng(d + g + a)
        q = jnp.asarray(r.standard_normal((len(lens), g, d)), dtype)
        k, v = (r.standard_normal((len(lens), 1, d, a)).astype("float32")
                for _ in range(2))
        lengths = np.asarray(lens, np.int32)
        past = np.arange(a)[None, None, None, :] \
            >= lengths[:, None, None, None]
        out = _attend_cache(
            q, jnp.asarray(np.where(past, np.nan, k), dtype),
            jnp.asarray(np.where(past, np.nan, v), dtype),
            jnp.asarray(lengths), interpret=True)
        assert out.shape == q.shape and out.dtype == q.dtype
        # the masked einsum over the same rounded operands, tails zeroed
        f32 = lambda x: jnp.asarray(np.where(past, 0.0, x),  # noqa: E731
                                    dtype).astype(jnp.float32)[:, 0]
        sc = jnp.einsum("sgd,sdt->sgt", q.astype(jnp.float32), f32(k)) \
            / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(~past[:, 0], sc, -jnp.inf), axis=-1)
        ref = jnp.einsum("sgt,sdt->sgd", p, f32(v))
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)), np.asarray(ref), rtol=0,
            atol=1e-5 if dtype == "float32" else 2e-2)

    @pytest.mark.parametrize("itemsize", [2, 4])
    @pytest.mark.parametrize("d, g", [(64, 1), (128, 6), (128, 1)])
    def test_tile_is_a_lane_aligned_divisor_at_every_rung(self, d, g,
                                                          itemsize):
        """At every rung of the default ladders up to both serve cells'
        lengths (and caches no lane tile divides) the tile divides the
        block and is whole lane tiles, or is the whole block; it comes
        from the shapes alone, as the flash kernels' chunk does."""
        import inspect

        from bigdl_tpu.serving.compile_cache import BucketLadder

        for max_len in (1024, 6144, 200, 1000):
            for rung in BucketLadder(max_len):
                a = block_columns(max_len, rung)
                assert a >= min(rung, max_len)
                tile = kv_tile(a, d, g, itemsize)
                assert a % tile == 0
                assert tile % 128 == 0 or tile == a
        # no knob: not an argument, and since PR 35 not a config field
        assert "block_k" not in inspect.signature(
            ragged_decode_attention).parameters
        assert not hasattr(kernels.KernelConfig(), "block_k")
        # the two serve cells: one tile a GPT-2 row, 2048 columns of a
        # ring or of the global entry
        assert kv_tile(1024, 64, 1, 4) == 1024
        assert kv_tile(4096, 128, 6, 2) == kv_tile(6144, 128, 6, 2) == 2048

    def test_dispatch_shapes_and_toggle(self):
        q, kv, _ = _decode_operands(2, 2, 8, 16, seed=12)
        lengths = jnp.asarray(np.array([3, 9], np.int32))
        step = dict(new_k=kv[..., 0], new_v=kv[..., 1],
                    write_at=lengths - 1)
        with kernels.use(OFF):
            assert kernels.decode_attention(q, kv, kv, lengths,
                                            **step) is None
        with kernels.use(ON):
            wrote = kernels.dispatch.taken_in_thread("decode_write")
            out, k, v = kernels.decode_attention(q, kv, kv, lengths,
                                                 **step)
            assert out.shape == (2, 2, 8)
            assert k.shape == v.shape == kv.shape
            assert kernels.dispatch.taken_in_thread(
                "decode_write") == wrote + 1
            # a [B,H,S,D] query is the training shape, not decode's
            assert kernels.decode_attention(kv, kv, kv, lengths,
                                            **step) is None
            # nor is a [slots,H,T,D] cache: time is the last axis
            assert kernels.decode_attention(
                q, jnp.swapaxes(kv, 2, 3), jnp.swapaxes(kv, 2, 3),
                lengths, **step) is None
            # new columns of another dtype than the cache's: declined,
            # as _write_columns would refuse them
            assert kernels.decode_attention(
                q, kv, kv, lengths, new_k=step["new_k"].astype("bfloat16"),
                new_v=step["new_v"], write_at=lengths - 1) is None
            assert kernels.dispatch.taken_in_thread(
                "decode_write") == wrote + 1

    @pytest.mark.parametrize(
        "hkv, g, d, t, dtype, window, attend_len, offsets", [
            # MHA f32 D 64 over 1024 columns: the GPT-2 serve cell's row
            (2, 1, 64, 1024, "float32", None, None, [0, 550, 700, 1023]),
            # grouped bf16 G 6 D 128 over 4096: Trinity's, two tiles
            (1, 6, 128, 4096, "bfloat16", None, None,
             [5, 2047, 2048, 4095]),
            # a ring that has not wrapped: write_at == lengths - 1
            (2, 2, 16, 256, "float32", 256, None, [0, 100, 127, 255]),
            # a ring that has: the oldest token is overwritten and not
            # attended, write_at != lengths - 1
            (2, 2, 16, 256, "float32", 256, None, [256, 300, 511, 1000]),
            # a rung below T, the write in the last of four tiles
            (1, 6, 128, 12288, "bfloat16", None, 8192,
             [6144, 7000, 8191, 100]),
            # lengths == 1: one tile walked, its every column masked
            (2, 1, 64, 1024, "float32", None, None, [0, 0, 0, 0]),
            # a free slot's offset out of range: clamped to the last
            # column, as XLA clamps _write_columns' start
            (2, 2, 16, 256, "float32", None, None, [9, 5000, 40, 255]),
            # T shorter than a lane tile, and not a whole number of them
            (2, 2, 16, 100, "float32", None, None, [0, 50, 98, 99]),
            (2, 2, 16, 200, "bfloat16", None, None, [0, 127, 128, 199]),
        ], ids=["mha_f32_1024", "gqa_bf16_4096", "ring_not_wrapped",
                "ring_wrapped", "rung_below_T_last_tile", "lengths_1",
                "free_slot_out_of_range", "T_under_a_lane_tile",
                "T_no_whole_lane_tiles"])
    def test_writes_the_new_column_as_write_columns_does(
            self, hkv, g, d, t, dtype, window, attend_len, offsets):
        """The kernel, handed the cache as it stands and the step's new
        K/V column, against ``_write_columns`` followed by the
        length-masked ``_attend`` (what a declined dispatch runs): the
        returned K and V bit-equal in EVERY element - the written
        column and all the untouched ones, other slots' rows, columns
        past the rung - and the output inside the decode equivalence
        tests' tolerance."""
        from bigdl_tpu.nn.attention import _attend, _write_columns

        slots = len(offsets)
        r = np.random.default_rng(t + d + g)
        arr = lambda *shape: jnp.asarray(  # noqa: E731
            r.standard_normal(shape), dtype)
        q, k_t, v_t = (arr(slots, hkv * g, 1, d), arr(slots, hkv, d, 1),
                       arr(slots, hkv, d, 1))
        cache = {"k": arr(slots, hkv, d, t), "v": arr(slots, hkv, d, t)}
        offsets = jnp.asarray(offsets, jnp.int32)
        c = t if attend_len is None else attend_len
        want = _write_columns(cache, k_t, v_t, offsets, offsets[:, None],
                              None, window)
        lengths = jnp.clip(offsets + 1, 1, c if window is None else window)
        at = offsets if window is None else offsets % window
        mask = jnp.arange(c)[None, None, :] < lengths[:, None, None]
        ref = _attend(q, [(want["k"][..., :c], want["v"][..., :c],
                           mask[:, None, None])])[:, :, 0]
        out, k, v = ragged_decode_attention(
            q[:, :, 0], cache["k"], cache["v"], lengths, at, k_t[..., 0],
            v_t[..., 0], attend_len=c, interpret=True)
        bits = lambda a: np.asarray(a).view(  # noqa: E731
            {2: np.uint16, 4: np.uint32}[a.dtype.itemsize])
        assert np.array_equal(bits(k), bits(want["k"]))
        assert np.array_equal(bits(v), bits(want["v"]))
        assert out.dtype == q.dtype
        live = np.asarray(offsets) < t          # a free slot's output
        np.testing.assert_allclose(             # is never consumed
            np.asarray(out.astype(jnp.float32))[live],
            np.asarray(ref.astype(jnp.float32))[live], rtol=0,
            atol=1e-5 if dtype == "float32" else 2e-2)
        assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()


# ----------------------------------------------------------- int8 GEMM

class TestInt8Gemm:
    def _quantized(self, m=8, k=32, n=16, seed=0):
        from bigdl_tpu.ops.quant import quantize_symmetric
        r = np.random.default_rng(seed)
        x = r.standard_normal((m, k)).astype(np.float32)
        w = r.standard_normal((n, k)).astype(np.float32)
        w_q, w_scale = quantize_symmetric(w, axis=0)
        x_q, x_scale = quantize_symmetric(x, axis=0)
        return x, x_q, x_scale, w_q, np.asarray(w_scale).reshape(-1)

    @pytest.mark.parametrize("bk", [8, 16, 32])
    def test_bitwise_vs_dequantize_then_matmul(self, bk):
        """The kernel's split-K int32 accumulation + fused dequant is
        BITWISE equal to the reference dequantize-then-matmul at every
        K split."""
        from bigdl_tpu.ops.quant import quantized_linear
        x, x_q, x_scale, w_q, w_scale = self._quantized()
        out = pallas_quantized_matmul(
            jnp.asarray(x_q), jnp.asarray(w_q), jnp.asarray(x_scale),
            jnp.asarray(w_scale), bm=4, bn=8, bk=bk, interpret=True)
        ref = quantized_linear(x, np.asarray(w_q), w_scale, None)
        assert np.array_equal(np.asarray(out), np.asarray(ref))

    def test_dispatch_bitwise_with_bias(self):
        """Through the dispatch layer (bias added OUTSIDE the kernel —
        int8_gemm.py documents the FMA ulp the fused add would cost),
        the with-bias result is bitwise equal to the reference
        layer math."""
        from bigdl_tpu.ops.quant import quantized_linear
        x, x_q, x_scale, w_q, w_scale = self._quantized(seed=1)
        bias = np.random.default_rng(2).standard_normal(16) \
            .astype(np.float32)
        with kernels.use(ON):
            out = kernels.int8_matmul(
                jnp.asarray(x_q), jnp.asarray(w_q),
                jnp.asarray(x_scale), jnp.asarray(w_scale),
                jnp.asarray(bias))
        ref = quantized_linear(x, np.asarray(w_q), w_scale,
                               bias)
        assert np.array_equal(np.asarray(out), np.asarray(ref))

    def test_dispatch_toggle_and_alignment_gate(self):
        x, x_q, x_scale, w_q, w_scale = self._quantized()
        args = (jnp.asarray(x_q), jnp.asarray(w_q),
                jnp.asarray(x_scale), jnp.asarray(w_scale))
        with kernels.use(OFF):
            assert kernels.int8_matmul(*args) is None
        with kernels.use(kernels.KernelConfig.all_on(interpret=False)):
            # compiled mode demands MXU-aligned tiles; 8x32x16 is not
            assert kernels.int8_matmul(*args) is None
        with kernels.use(ON):
            assert kernels.int8_matmul(*args) is not None

    def test_quantized_linear_layer_bitwise_on_vs_off(self):
        """QuantizedLinear routes through the dispatch layer: kernels
        on (interpret) and off produce bitwise-identical layer
        outputs, dynamic AND calibrated activation scales."""
        from bigdl_tpu.nn.linear import Linear
        from bigdl_tpu.nn.quantized import QuantizedLinear
        RandomGenerator.set_seed(21)
        lin = Linear(12, 6)
        lin.ensure_initialized()
        x = jnp.asarray(np.random.RandomState(3)
                        .randn(5, 12).astype(np.float32))
        for act_scale in (None, 0.25):
            qm = QuantizedLinear.from_float(lin, lin.get_parameters(),
                                            act_scale)
            params = qm.init(None)
            with kernels.use(OFF):
                ref = np.asarray(qm.forward_fn(params, x))
            with kernels.use(ON):
                out = np.asarray(qm.forward_fn(params, x))
            assert np.array_equal(out, ref), f"act_scale={act_scale}"


# ---------------------------------------- generation with kernels on

def _greedy_reference(model, prompt, n, pad_to=16):
    @jax.jit
    def fwd(p, s, t):
        logits, _ = model.apply(p, s, t, training=False)
        return logits

    params, state = model.get_parameters(), model.get_state()
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(toks)] = toks
        logits = np.asarray(fwd(params, state, padded))
        nxt = int(np.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def _gen_model(seed=42):
    RandomGenerator.set_seed(seed)
    m = TransformerLM(vocab_size=50, hidden_size=32, num_layers=2,
                      num_heads=4, max_len=32).evaluate()
    m.ensure_initialized()
    return m


class TestGenerationWithKernels:
    def test_greedy_decode_bit_identical_with_kernels_on(self):
        """The acceptance invariant with the ragged kernel live:
        greedy decode through the service is token-bit-identical to
        full-sequence re-forward — two prompt shapes."""
        from bigdl_tpu.generation import (GenerationConfig,
                                          GenerationService)
        model = _gen_model()
        with kernels.use(ON):
            svc = GenerationService(config=GenerationConfig(
                slots=4, max_len=16, length_buckets=(16,),
                prefill_rows=2))
            svc.load("lm", model)
            try:
                prompt = np.array([3, 7, 1, 4, 9], np.int32)
                out = svc.generate("lm", prompt,
                                   max_new_tokens=8).result(60)
                assert list(out) == _greedy_reference(model, prompt, 8)
                prompt2 = np.array([11, 2], np.int32)
                out2 = svc.generate("lm", prompt2,
                                    max_new_tokens=5).result(60)
                assert list(out2) == _greedy_reference(model, prompt2, 5)
            finally:
                svc.shutdown()

    def test_slot_reused_after_free_matches_uncached_forwards(self):
        """Decode -> free -> re-admit on ONE slot, ragged kernel live,
        two ladder rungs: the second occupant is shorter than the
        first, so the slot's columns past its length still hold the
        first stream's K/V (a freed slot is not zeroed) and the lower
        rung reads a narrower block of the same unsliced array. Each
        greedy stream equals the one full, uncached forwards give."""
        from bigdl_tpu.generation import (GenerationConfig,
                                          GenerationService)
        model = _gen_model()
        with kernels.use(ON):
            svc = GenerationService(config=GenerationConfig(
                slots=1, max_len=16, length_buckets=(8, 16),
                prefill_rows=1))
            svc.load("lm", model)
            try:
                first = np.array([3, 7, 1, 4, 9, 12, 5, 8, 2], np.int32)
                out = svc.generate("lm", first,
                                   max_new_tokens=6).result(60)
                assert list(out) == _greedy_reference(model, first, 6)
                second = np.array([11, 2], np.int32)
                out2 = svc.generate("lm", second,
                                    max_new_tokens=10).result(60)
                assert list(out2) == _greedy_reference(model, second, 10)
            finally:
                svc.shutdown()

    def test_program_bound_holds_with_kernels_enabled(self):
        """Kernel variants must not multiply programs: a 2-rung ladder
        warms exactly <= 2 programs per rung with kernels on, and a
        decode burst across every bucket compiles nothing new."""
        from bigdl_tpu.generation.engine import DecodeEngine
        from bigdl_tpu.generation.kv_cache import KVCache
        from bigdl_tpu.serving.compile_cache import (BucketLadder,
                                                     CompileCache)
        from bigdl_tpu.serving.registry import ModelRegistry

        model = _gen_model()
        with kernels.use(ON):
            sv = ModelRegistry().load("m", model)
            ladder = BucketLadder(16, (8, 16))
            eng = DecodeEngine(CompileCache(), ladder, slots=4,
                               prefill_rows=2)
            kv = KVCache.for_model(model, 4, 16)
            compiled = eng.warmup(sv, kv)
            assert compiled <= 2 * len(ladder)
            before = eng.compile_count(sv)
            # a burst touching both rungs: no fresh compiles
            eng.prefill(sv, kv, [np.array([3, 7, 1], np.int32)], [0])
            for _ in range(9):  # crosses the 8 -> 16 rung boundary
                tokens = np.zeros((4,), np.int32)
                positions = kv.lengths.copy()
                active = np.zeros((4,), bool)
                active[0] = True
                eng.decode(sv, kv, tokens, positions, active)
                kv.lengths[0] += 1
            assert eng.compile_count(sv) == before

    def test_ragged_kernel_consumes_host_lengths_vector(self,
                                                        monkeypatch):
        """The decode-path seam: the decode program hands the host
        lengths vector (threaded as `positions`) straight to the
        ragged kernel as its per-slot bound — one [slots] int32
        operand, no re-bucketing inside."""
        from bigdl_tpu.generation.engine import DecodeEngine
        from bigdl_tpu.generation.kv_cache import KVCache
        from bigdl_tpu.serving.compile_cache import (BucketLadder,
                                                     CompileCache)
        from bigdl_tpu.serving.registry import ModelRegistry

        seen = []
        real = kernels.decode_attention

        def spy(q, k, v, lengths, **kw):
            seen.append((tuple(lengths.shape), str(lengths.dtype)))
            return real(q, k, v, lengths, **kw)

        monkeypatch.setattr(kernels, "decode_attention", spy)
        model = _gen_model()
        with kernels.use(ON):
            sv = ModelRegistry().load("m", model)
            eng = DecodeEngine(CompileCache(), BucketLadder(16, (16,)),
                               slots=4, prefill_rows=2)
            kv = KVCache.for_model(model, 4, 16)
            eng.prefill(sv, kv, [np.array([3, 7, 1], np.int32)], [0])
            tokens = np.zeros((4,), np.int32)
            active = np.zeros((4,), bool)
            active[0] = True
            eng.decode(sv, kv, tokens, kv.lengths.copy(), active)
        # one call per layer at trace time, each consuming the [slots]
        # int32 lengths operand
        assert len(seen) == model.num_layers
        assert all(s == ((4,), "int32") for s in seen)


# ------------------------------------------- telemetry kernel labels

class TestKernelProgramLabels:
    def test_explicit_labels_reach_gauges(self):
        import bigdl_tpu.telemetry as telemetry
        from bigdl_tpu.telemetry import programs

        r = telemetry.MetricsRegistry()
        reg = programs.ProgramRegistry(metrics=r)
        analysis = {"flops": 2.0e9, "bytes_accessed": 1e6,
                    "hbm_bytes": 5e6}
        prof = reg.register("kl/model/step", "train",
                            analysis=analysis, compile_s=0.5,
                            kernel="pallas")
        assert prof.kernel == "pallas"
        labels = {"program": "kl/model/step", "kernel": "pallas"}
        assert r.gauge("train/program/flops").value(**labels) == 2.0e9
        reg.record_rate("kl/model/step", 1000.0)
        assert r.gauge("train/program/mfu").value(**labels) > 0
        # explicit reference label: the side-by-side bench form
        prof2 = reg.register("kl/model/step_ref", "train",
                             analysis=analysis, compile_s=0.5,
                             kernel="reference")
        assert prof2.kernel == "reference"
        assert r.gauge("train/program/flops").value(
            program="kl/model/step_ref", kernel="reference") == 2.0e9

    def test_wrapped_site_labels_on_trace_evidence_only(self):
        """maybe_wrap_jitted earns kernel=pallas from the trace
        actually routing through a dispatch — a kernel-free program
        stays unlabeled even under an all-on config (the honest-label
        rule; a config-based guess would tag every TPU program)."""
        import bigdl_tpu.telemetry as telemetry
        from bigdl_tpu.nn.attention import dot_product_attention
        from bigdl_tpu.telemetry import programs

        r = telemetry.MetricsRegistry()
        reg = programs.ProgramRegistry(metrics=r)
        q, k, v = _qkv(s=16, seed=20)
        programs.enable()
        try:
            with kernels.use(ON):
                attn = programs.maybe_wrap_jitted(
                    "kl/evidence/attn", "serving",
                    jax.jit(lambda q_, k_, v_: dot_product_attention(
                        q_, k_, v_, causal=True)), prog_registry=reg)
                attn(q, k, v)
                plain = programs.maybe_wrap_jitted(
                    "kl/evidence/plain", "serving",
                    jax.jit(lambda x: x * 2.0), prog_registry=reg)
                plain(q)
        finally:
            programs.disable()
        assert reg.get("kl/evidence/attn").kernel == "pallas"
        assert reg.get("kl/evidence/plain").kernel is None

    def test_implicit_registration_keeps_unlabeled_series(self):
        """Registrations without explicit labels or trace evidence
        keep the pre-kernel single-label gauge identity — existing
        dashboards/series must not churn, whatever the config."""
        import bigdl_tpu.telemetry as telemetry
        from bigdl_tpu.telemetry import programs

        r = telemetry.MetricsRegistry()
        reg = programs.ProgramRegistry(metrics=r)
        with kernels.use(ON):  # even an all-on config must not leak in
            prof = reg.register("kl/off/step", "train",
                                analysis={"flops": 1.0}, compile_s=0.1)
        assert prof.kernel is None
        assert r.gauge("train/program/flops").value(
            program="kl/off/step") == 1.0

    def test_diagnose_device_rows_show_kernel(self):
        """The golden diagnose shape: device rows carry the kernel
        field and the text line tags it."""
        from bigdl_tpu.tools.diagnose import _device_lines, \
            device_summary

        rows = device_summary([
            {"name": "b/att/pallas", "kind": "serving",
             "kernel": "pallas", "mfu": 0.41, "achieved_tfs": 80.0,
             "flops": 1e12, "hbm_bytes": 2e9, "compile_s": 1.5},
            {"name": "b/att/ref", "kind": "serving",
             "kernel": "reference", "mfu": 0.3,
             "achieved_tfs": 60.0, "flops": 1e12, "hbm_bytes": 2e9,
             "compile_s": 1.0},
        ])
        assert [r["kernel"] for r in rows] == ["pallas", "reference"]
        lines = _device_lines(rows)
        assert "[pallas]" in lines[0] and "[reference]" in lines[1]

    def test_dispatch_counters_count_routing(self):
        import bigdl_tpu.telemetry as telemetry

        c_pallas = telemetry.registry().counter(
            "kernels/dispatch/pallas")
        c_ref = telemetry.registry().counter(
            "kernels/dispatch/reference")
        q, k, v = _qkv()
        before_p = c_pallas.value(op="flash")
        before_c = c_ref.value(op="flash", reason="config")
        before_s = c_ref.value(op="flash", reason="shape")
        before_v = c_ref.value(op="flash", reason="vmem")
        with kernels.use(ON):
            kernels.attention(q, k, v, causal=True)
        with kernels.use(OFF):
            assert kernels.attention(q, k, v, causal=True) is None
        with kernels.use(ON):
            # rank-3 input: declined for shape, attributably
            assert kernels.attention(q[:, 0], k[:, 0], v[:, 0]) is None
        big = jax.ShapeDtypeStruct((1, 1, 32768, 128), jnp.bfloat16)
        with kernels.use(kernels.KernelConfig.all_on(
                interpret=False, long_context=False)):
            assert kernels.attention(big, big, big) is None
        assert c_pallas.value(op="flash") == before_p + 1
        assert c_ref.value(op="flash", reason="config") == before_c + 1
        assert c_ref.value(op="flash", reason="shape") == before_s + 1
        assert c_ref.value(op="flash", reason="vmem") == before_v + 1
