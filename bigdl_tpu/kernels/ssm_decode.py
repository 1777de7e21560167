"""One decode step of a state-space layer — every slot's recurrent state
read once, updated and written back in place.

The state of a Mamba-2 layer is ``[slots, Hq, N, L]`` float32 (``Hq``
rows of ``e`` heads side by side, ``L = e P`` lanes, the state
dimension ``N`` down the sublanes: :mod:`bigdl_tpu.nn.ssm` has the
layout). A step computes, a row at a time,

    S' = dec * S + B (x) dtx          y = sum_n C_n S'_n

with ``dec`` / ``dtx`` ``[slots, Hq, L]`` (row vectors along the lanes:
each head's decay and ``dt x``) and ``bc [slots, N, 2 G]`` (``B`` then
``C`` of the ``G`` groups, ``N`` down the sublanes; the rows of group
``j`` are ``j Hq/G .. (j + 1) Hq/G - 1``). ``dec`` is 0 where a row
starts anew: the old state is then dropped, whatever it holds.

Grid ``(slots, Hq / rows)``; a program is handed ``rows`` rows of one
slot's state (:func:`state_rows`: about 2 MiB), multiplies and adds on
the VPU, sums down the sublanes, and the state goes out through the
block it came in by (``input_output_aliases``): 2 x 4 bytes a state
element is all the HBM traffic there is, which is what binds the step
(5.4 GB at 128 slots of five 4 MiB layers). The two column vectors a
group needs (``B`` and ``C`` along the sublanes) are picked out of ``bc``
with a lane mask and a lane sum, once a group and program.

Used through :func:`bigdl_tpu.kernels.ssm_decode`, which owns
eligibility; :func:`bigdl_tpu.nn.ssm.decode_step_reference` is the
plain form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.kernels.common import tpu_compiler_params

__all__ = ["ssm_decode_pallas", "state_rows"]

#: bytes of state one program is handed; in and out, double buffered,
#: four of them stay inside the 16 MiB a kernel may use on a v5e
_STATE_BLOCK_BYTES = 2 << 20


def state_rows(hq: int, per_group: int, n: int, lanes: int) -> int:
    """Rows of one slot's state a program takes: the most that fit the
    block budget among the divisors of ``hq`` that are whole groups (or
    whole parts of one) and, unless all of ``hq``, whole sublane tiles
    (the row vectors' blocks are ``[rows, lanes]``). None where no
    divisor qualifies."""
    fit = max(1, _STATE_BLOCK_BYTES // (n * lanes * 4))
    ok = [r for r in range(1, hq + 1)
          if hq % r == 0 and (r % per_group == 0 or per_group % r == 0)
          and (r % 8 == 0 or r == hq)]
    small = [r for r in ok if r <= fit]
    return max(small) if small else (min(ok) if ok else None)


def _ssm_kernel(s_ref, dec_ref, dtx_ref, bc_ref, y_ref, so_ref, *,
                rows: int, per_group: int, groups: int):
    j = pl.program_id(1)
    bc = bc_ref[0]                                       # [N, 2G]
    lane = jax.lax.broadcasted_iota(jnp.int32, bc.shape, 1)

    def column(at):
        """Column ``at`` of ``bc`` as ``[N, 1]``."""
        return jnp.sum(jnp.where(lane == at, bc, 0.0), axis=1,
                       keepdims=True)

    cols = {}
    for i in range(rows):
        first = i // per_group * per_group      # the group's first row
        if first not in cols:
            grp = (j * rows + first) // per_group
            cols[first] = (column(grp), column(groups + grp))
        b_col, c_col = cols[first]
        dec = dec_ref[0, pl.ds(i, 1), :]                 # [1, L]
        dtx = dtx_ref[0, pl.ds(i, 1), :]
        old = s_ref[0, i]                                # [N, L]
        new = jnp.where(dec > 0, old * dec, 0.0) + b_col * dtx
        so_ref[0, i] = new
        y_ref[0, pl.ds(i, 1), :] = jnp.sum(new * c_col, axis=0,
                                           keepdims=True)


# jitted for the reason ragged_decode_attention is: a decoder's layers
# share one trace of the kernel and one lowering to Mosaic
@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_pallas(state, dec, dtx, bc, *, interpret: bool = False):
    """``(y [slots, Hq, L], state')`` of one decode step (module
    docstring), ``state`` aliased to its output. All float32."""
    slots, hq, n, lanes = state.shape
    groups = bc.shape[2] // 2
    if (dec.shape != (slots, hq, lanes) or dtx.shape != dec.shape
            or bc.shape != (slots, n, 2 * groups) or hq % groups):
        raise ValueError(f"state {state.shape} / dec {dec.shape} / dtx "
                         f"{dtx.shape} / bc {bc.shape}")
    per_group = hq // groups
    rows = state_rows(hq, per_group, n, lanes)
    if rows is None:
        raise ValueError(f"no block of rows fits state {state.shape}")
    block = pl.BlockSpec((1, rows, n, lanes), lambda s, j: (s, j, 0, 0))
    vec = pl.BlockSpec((1, rows, lanes), lambda s, j: (s, j, 0))
    y, state = pl.pallas_call(
        functools.partial(_ssm_kernel, rows=rows, per_group=per_group,
                          groups=groups),
        grid=(slots, hq // rows),
        in_specs=[block, vec, vec,
                  pl.BlockSpec((1, n, 2 * groups), lambda s, j: (s, 0, 0))],
        out_specs=[vec, block],
        out_shape=[jax.ShapeDtypeStruct(dec.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={0: 1},
        compiler_params=tpu_compiler_params(("parallel", "parallel")),
        interpret=interpret,
        name="bigdl_ssm_decode",
    )(state, dec, dtx, bc)
    return y, state
