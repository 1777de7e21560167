"""Shared K=1-vs-K=8 fused-window measurement protocol.

``bench.py`` and ``tools.perf --sync-compare`` both quantify what
bounded async dispatch buys over per-step host sync. The protocol —
warm/compile outside the clock, then time ``n = max(1, total // k)``
windows each synced the way the real driver syncs (full carry first,
THEN the loss fetch; loss alone would let the param-update tail overlap
the next dispatch and flatter K=1) — lives here once so the two tools
can never drift apart in what their ``steps_per_sec_k*`` numbers mean.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Sequence, Tuple


def measure_sync_compare(build_chunk: Callable, carry,
                         make_keys: Callable, total: int,
                         ks: Sequence[int] = (1, 8)) -> Tuple[Dict, object]:
    """Time scanned train-step windows at each ``k`` in ``ks``.

    ``build_chunk(k)`` returns a jitted ``chunk(carry, keys) ->
    (carry, losses)`` (callers reuse an already-compiled program when
    ``k`` matches their main loop); ``make_keys(k, i)`` returns the
    window's key batch (``i = -1`` for the untimed warm call);
    ``total`` is the per-``k`` step budget. Returns
    ``({"steps_per_sec_k<k>": float, ...}, final_carry)`` — the carry
    is threaded through every call, so donated buffers stay live.
    """
    import jax
    import jax.numpy as jnp

    def fetch(losses):
        # a VALUE fetch, not just readiness: the value cannot exist
        # before the execution that computes it has completed
        return float(jnp.sum(jnp.asarray(losses).astype(jnp.float32)))

    out: Dict[str, float] = {}
    for k in ks:
        chunk = build_chunk(k)
        carry, losses = chunk(carry, make_keys(k, -1))
        fetch(losses)  # compile + settle outside the clock
        n = max(1, total // k)
        t0 = time.perf_counter()
        for i in range(n):
            carry, losses = chunk(carry, make_keys(k, i))
            # deliberate once-per-window sync — it IS the measurement
            jax.block_until_ready(carry[0])  # bigdl: disable=sync-in-loop
            fetch(losses)  # bigdl: disable=sync-in-loop
        out[f"steps_per_sec_k{k}"] = n * k / (time.perf_counter() - t0)
    return out, carry
