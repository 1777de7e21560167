"""State-space mixers: the Mamba-2 layer, whose memory of the prefix is
a constant-size recurrent state instead of keys and values a position.

One layer over ``u [B, S, h]`` (``H`` heads of ``P`` channels, ``G``
groups of ``N`` state dimensions, a depthwise causal convolution of
``K`` taps over the ``C = H P + 2 G N`` channels of ``x | B | C``):

1. ``[z | xBC | dt] = u W_in``;
2. ``xBC_t <- silu(b + sum_j w_j * xBC_{t-K+1+j})``, zeros before the
   sequence;
3. ``x [H, P]``, ``B [G, N]``, ``C [G, N]`` split from ``xBC``; head
   ``i`` reads group ``i // (H / G)``;
4. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
5. ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
   D x_t``;
6. ``y <- RMSNorm_grouped(y * silu(z))`` (the mean square over each
   group's channels), ``out = y W_out``.

A prompt runs step 5 in its chunked form (:func:`ssd_scan`: inside a
chunk of ``chunk`` tokens a masked quadratic product, between chunks the
carried state; products XLA sees). One new token a row takes the decode
step (:func:`decode_step`): the kernel ``bigdl_ssm_decode`` where the
dispatch takes it, which reads the state once and writes it in place,
else the plain form below.

**The cached entry** is ``{"conv": [B, K-1, C], "ssm": [B, H/e, N, e
P]}``: the convolution's last ``K-1`` inputs, and the state TRANSPOSED
with ``e`` heads side by side on the last axis (``e = 128 / P`` where
the heads of a group allow: a full lane tile, so the step's sum over
``N`` runs down the sublanes and its row vectors ``x``, ``dt`` need no
relayout). :func:`pack_state` / :func:`unpack_state` convert. The state
is float32 whatever the activations are: it is summed into for
thousands of steps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module
from bigdl_tpu.utils.engine import Engine

_LANES = 128


def heads_per_tile(heads: int, groups: int, head_dim: int) -> int:
    """``e``: heads that share one row of the packed state, a divisor of
    a group's heads (no row straddles two groups' ``B``)."""
    return math.gcd(heads // groups, max(1, _LANES // head_dim))


def pack_state(s, groups: int):
    """``[B, H, P, N]`` -> the cached form ``[B, H/e, N, e P]``."""
    b, h, p, n = s.shape
    e = heads_per_tile(h, groups, p)
    return s.reshape(b, h // e, e, p, n).transpose(0, 1, 4, 2, 3) \
        .reshape(b, h // e, n, e * p)


def unpack_state(s, heads: int, groups: int):
    """The cached form back to ``[B, H, P, N]``."""
    b, hq, n, ep = s.shape
    e = heads // hq
    return s.reshape(b, hq, n, e, ep // e).transpose(0, 1, 3, 4, 2) \
        .reshape(b, heads, ep // e, n)


def ssd_scan(x, dt, a, bmat, cmat, s0=None, *, chunk: int = 128):
    """The recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = S_t C_t`` over a whole sequence, chunk by chunk.

    ``x [B, S, H, P]``, ``dt [B, S, H]`` float32 (0 where a token is
    padding: the state then stands still), ``a [H]`` float32 (negative),
    ``bmat`` / ``cmat`` ``[B, S, G, N]``, ``s0 [B, H, P, N]`` float32
    (None: zeros). Returns ``(y [B, S, H, P]`` in ``x``'s dtype, the
    final state ``[B, H, P, N]`` float32``)``. Operands of the products
    are in ``x``'s dtype, sums and every decay in float32."""
    b, s, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    r = h // g
    pad = -s % chunk
    if pad:
        widen = lambda t: jnp.pad(t, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (t.ndim - 2))
        x, dt, bmat, cmat = widen(x), widen(dt), widen(bmat), widen(cmat)
    nc, q = (s + pad) // chunk, chunk
    f32 = jnp.float32
    xd = (x.astype(f32) * dt[..., None]).astype(x.dtype)
    xd = xd.reshape(b, nc, q, g, r, p)
    bc = bmat.reshape(b, nc, q, g, n)
    cc = cmat.reshape(b, nc, q, g, n)
    # log-decays, summed inside each chunk: [B, nc, G, R, Q]
    cs = jnp.cumsum((dt * a).reshape(b, nc, q, g, r), axis=2)
    cs = cs.transpose(0, 1, 3, 4, 2)
    # inside a chunk: token i reads token j <= i through exp(cs_i - cs_j)
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                    preferred_element_type=f32)
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    seg = jnp.where(causal, cs[..., :, None] - cs[..., None, :], -jnp.inf)
    m = (cb[:, :, :, None] * jnp.exp(seg)).astype(x.dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xd,
                   preferred_element_type=f32)
    # what each chunk adds to the state, decayed to the chunk's end
    last = cs[..., -1:]
    xw = (xd.astype(f32) * jnp.exp(last - cs).transpose(0, 1, 4, 2, 3)
          [..., None]).astype(x.dtype)
    add = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw, bc,
                     preferred_element_type=f32)
    keep = jnp.exp(last[..., 0])                       # [B, nc, G, R]
    init = jnp.zeros((b, g, r, p, n), f32) if s0 is None \
        else s0.astype(f32).reshape(b, g, r, p, n)

    def step(carry, inp):
        k, d = inp
        return k[..., None, None] * carry + d, carry

    final, entered = jax.lax.scan(
        step, init, (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(add, 1, 0)))
    entered = jnp.moveaxis(entered, 0, 1)          # [B, nc, G, R, P, N]
    # the state a chunk entered with, read by its tokens
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", cc, entered.astype(x.dtype),
                       preferred_element_type=f32) \
        * jnp.exp(cs).transpose(0, 1, 4, 2, 3)[..., None]
    y = y.reshape(b, nc * q, h, p)[:, :s]
    return y.astype(x.dtype), final.reshape(b, h, p, n)


def decode_operands(x, dt, a, bmat, cmat, start, e: int):
    """One token's operands in the packed state's layout, all float32:
    ``dec`` and ``dtx`` ``[B, H/e, e P]`` (each head's decay ``exp(dt
    a)`` and input ``dt x`` side by side as the state's rows lie;
    ``dec`` 0 where ``start``: the row begins from nothing whatever the
    slot held) and ``bc [B, N, 2 G]`` (``B`` then ``C``, ``N`` down the
    rows as the state has it). ``x [B, H, P]``, ``dt [B, H]``,
    ``bmat`` / ``cmat`` ``[B, G, N]``, ``start`` bool ``[B]``."""
    b, h, p = x.shape
    f32 = jnp.float32
    dec = jnp.where(start[:, None], 0.0, jnp.exp(dt * a))
    dec = jnp.broadcast_to(dec[..., None], (b, h, p))
    dtx = x.astype(f32) * dt[..., None]
    bc = jnp.concatenate([bmat, cmat], axis=1).astype(f32)
    return (dec.reshape(b, h // e, e * p), dtx.reshape(b, h // e, e * p),
            jnp.swapaxes(bc, 1, 2))


def decode_step_reference(state, dec, dtx, bc):
    """The decode step in plain ``jax.numpy`` on :func:`decode_operands`'
    form: ``state [B, Hq, N, eP]`` float32 -> ``(y [B, Hq, eP], new
    state)`` with ``S' = dec * S + B (x) dtx`` and ``y = sum_n C_n
    S'_n``, the rows of group ``j`` being ``j Hq/G .. (j + 1) Hq/G -
    1``. What the kernel is compared with, and what runs where the
    dispatch declines."""
    hq = state.shape[1]
    g = bc.shape[2] // 2
    rows = jnp.repeat(jnp.swapaxes(bc, 1, 2), hq // g, axis=1)
    bm, cm = rows[:, :hq], rows[:, hq:]                  # [B, Hq, N]
    kept = jnp.where(dec[:, :, None, :] > 0,
                     state * dec[:, :, None, :], 0.0)
    new = kept + bm[..., None] * dtx[:, :, None, :]
    return jnp.sum(new * cm[..., None], axis=2), new


def decode_step(state, dec, dtx, bc):
    """One token a row: the kernel where the dispatch takes it (the
    state aliased through), else :func:`decode_step_reference`."""
    from bigdl_tpu import kernels as _kernels

    taken = _kernels.ssm_decode_step(state, dec, dtx, bc)
    return taken if taken is not None \
        else decode_step_reference(state, dec, dtx, bc)


class Mamba2Mixer(Module):
    """The Mamba-2 mixer (module docstring) over ``[B, S, hidden]``.

    **Cached.** ``cache`` is the layer's entry ``{"conv", "ssm"}``
    (:meth:`cache_arrays` has the shapes), taken and returned with the
    output. ``positions`` (int32 ``[B]``): a row at offset 0, and every
    row of a ``fresh`` call, starts from a zero state whatever the slot
    held; any other row continues from its entry (a prefill chunk, a
    decode step). ``valid`` (int ``[B]``): how many of the S new tokens
    of a row are real - ``dt`` is 0 past them, so the state stands
    still, and the convolution's tail is the last ``K-1`` REAL inputs.
    """

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 state_size: int, *, groups: int = 1, conv_kernel: int = 4,
                 chunk: int = 128, norm_eps: float = 1e-5,
                 dt_min: float = 1e-3, dt_max: float = 0.1,
                 dt_floor: float = 1e-4):
        super().__init__()
        if num_heads % groups:
            raise ValueError(f"{num_heads} heads over {groups} groups")
        self.hidden_size = hidden_size
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_size, self.groups = state_size, groups
        self.conv_kernel, self.chunk = conv_kernel, chunk
        self.norm_eps = norm_eps
        self.dt_range = (dt_min, dt_max, dt_floor)
        self.inner = num_heads * head_dim
        self.conv_channels = self.inner + 2 * groups * state_size
        self.pack = heads_per_tile(num_heads, groups, head_dim)

    def cache_arrays(self):
        """``((name, shape of one row, dtype or None = the cache's),
        ...)`` of the recurrent entry."""
        return (("conv", (self.conv_kernel - 1, self.conv_channels), None),
                ("ssm", (self.num_heads // self.pack, self.state_size,
                         self.pack * self.head_dim), "float32"))

    def init(self, rng):
        dtype = Engine.default_dtype()
        ks = jax.random.split(rng, 5)
        h, n_in = self.hidden_size, self.inner + self.conv_channels \
            + self.num_heads
        s_in, s_out = 1.0 / math.sqrt(h), 1.0 / math.sqrt(self.inner)
        lo, hi, floor = self.dt_range
        dt = jnp.exp(jax.random.uniform(ks[3], (self.num_heads,))
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return {
            "w_in": jax.random.uniform(ks[0], (h, n_in), dtype,
                                       -s_in, s_in),
            "conv_w": jax.random.uniform(
                ks[1], (self.conv_kernel, self.conv_channels), dtype,
                -0.5, 0.5),
            "conv_b": jnp.zeros((self.conv_channels,), dtype),
            # the inverse soft-plus of a log-uniform step
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                ks[4], (self.num_heads,), minval=1.0,
                maxval=16.0)).astype(dtype),
            "D": jnp.ones((self.num_heads,), dtype),
            "norm": jnp.ones((self.inner,), dtype),
            "w_out": jax.random.uniform(ks[2], (self.inner, h), dtype,
                                        -s_out, s_out)}

    def _gate_norm(self, params, y, z):
        """``RMSNorm(y * silu(z))``, the mean square over each group's
        channels, in float32."""
        b, s, _ = y.shape
        v = (y * jax.nn.silu(z)).astype(jnp.float32)  # bigdl: disable=implicit-upcast-in-trace
        v = v.reshape(b, s, self.groups, self.inner // self.groups)
        ms = jnp.mean(jnp.square(v), axis=-1, keepdims=True)
        v = (v * jax.lax.rsqrt(ms + self.norm_eps)).reshape(b, s, -1)
        return v.astype(y.dtype) * params["norm"]

    def forward_fn(self, params, input, *, training=False, rng=None,
                   cache=None, positions=None, attend_len=None, valid=None,
                   fresh=False):
        """``attend_len`` is the engine's rung, taken as the attention
        layers take it and unused: a state has no columns."""
        u = input
        b, s, _ = u.shape
        f32 = jnp.float32
        hh, p, g, n = (self.num_heads, self.head_dim, self.groups,
                       self.state_size)
        k = self.conv_kernel
        with jax.named_scope("ssm/in_proj"):
            zxd = u @ params["w_in"]
            z = zxd[..., :self.inner]
            xbc = zxd[..., self.inner:self.inner + self.conv_channels]
            dt = jax.nn.softplus(
                zxd[..., self.inner + self.conv_channels:].astype(f32)  # bigdl: disable=implicit-upcast-in-trace
                + params["dt_bias"].astype(f32))  # bigdl: disable=implicit-upcast-in-trace
            a = -jnp.exp(params["A_log"].astype(f32))  # bigdl: disable=implicit-upcast-in-trace
        count = None
        if valid is not None:
            count = valid.astype(jnp.int32)
            dt = jnp.where(jnp.arange(s)[None, :, None]
                           < count[:, None, None], dt, 0.0)
        if cache is None:
            start = jnp.ones((b,), bool)
            tail = jnp.zeros((b, k - 1, self.conv_channels), u.dtype)
        else:
            if positions is None:
                raise ValueError("cache= needs positions= (per-row int32 "
                                 "offsets of the new tokens)")
            start = jnp.ones((b,), bool) if fresh else positions == 0
            tail = jnp.where(start[:, None, None], 0,
                             cache["conv"]).astype(u.dtype)
        with jax.named_scope("ssm/conv"):
            ext = jnp.concatenate([tail, xbc], axis=1)     # [B, S+K-1, C]
            acc = params["conv_b"].astype(f32)  # bigdl: disable=implicit-upcast-in-trace
            for j in range(k):
                acc = acc + params["conv_w"][j].astype(f32) \
                    * ext[:, j:j + s].astype(f32)  # bigdl: disable=implicit-upcast-in-trace
            conv = jax.nn.silu(acc).astype(u.dtype)
            if cache is not None and count is None:
                tail = ext[:, s:]
            elif cache is not None:
                # the last K-1 REAL inputs: rows n .. n+K-2 of ext
                at = count[:, None] + jnp.arange(k - 1)[None]
                tail = jnp.take_along_axis(ext, at[..., None], axis=1)
        x = conv[..., :self.inner].reshape(b, s, hh, p)
        bmat = conv[..., self.inner:self.inner + g * n].reshape(b, s, g, n)
        cmat = conv[..., self.inner + g * n:].reshape(b, s, g, n)
        with jax.named_scope("ssm/scan"):
            if cache is not None and s == 1:
                y, state = decode_step(
                    cache["ssm"], *decode_operands(
                        x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0],
                        start, self.pack))
                y = y.reshape(b, 1, hh, p).astype(u.dtype)
            else:
                s0 = None
                if cache is not None and not fresh:
                    s0 = jnp.where(start[:, None, None, None], 0.0,
                                   unpack_state(cache["ssm"], hh, g))
                y, state = ssd_scan(x, dt, a, bmat, cmat, s0,
                                    chunk=self.chunk)
                if cache is not None:
                    state = pack_state(state, g)
            y = y + x * params["D"][:, None].astype(x.dtype)
        with jax.named_scope("ssm/gate_norm"):
            y = self._gate_norm(params, y.reshape(b, s, self.inner), z)
        with jax.named_scope("ssm/out_proj"):
            out = y @ params["w_out"]
        if cache is None:
            return out
        return out, {"conv": tail.astype(cache["conv"].dtype),
                     "ssm": state.astype(cache["ssm"].dtype)}
