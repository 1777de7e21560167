"""Host->device transfer sizing (the measured device_put "cliff").

On link-limited hosts a single large ``jax.device_put`` can fall off a
throughput cliff that the same bytes in smaller pieces avoid.
``probe_device_put_chunk`` measures ascending sizes once per process and
returns the largest piece size that stays near peak throughput — the
auto-tuned chunk every piecewise staging path (fed bench, shard
rotation) should use. The reference's counterpart decision is caching
decoded images to dodge its IO wall (dataset/DataSet.scala:240); here
the wall is the link, so we size around it instead.
"""
from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

_cached_chunk: Optional[int] = None


def probe_device_put_chunk(max_mb: int = 96, *, drop_ratio: float = 0.5,
                           device=None) -> int:
    """Measure device_put throughput at 4,8,...,max_mb MB and return the
    largest size (bytes) whose throughput holds >= ``drop_ratio`` x the
    best seen. Ascending order stops at the first cliff, so at most one
    slow transfer is ever issued. Result is cached per process; the
    BENCH_CHUNK_MB env var short-circuits the probe."""
    global _cached_chunk
    if _cached_chunk is not None:
        return _cached_chunk
    env = os.environ.get("BENCH_CHUNK_MB")
    if env:
        _cached_chunk = int(float(env) * (1 << 20))
        return _cached_chunk

    import jax

    dev = device or jax.devices()[0]
    best_bps = 0.0
    chosen = 4 << 20
    mb = 4
    while mb <= max_mb:
        arr = np.random.RandomState(mb).randint(0, 256, mb << 20,
                                                dtype=np.uint8)
        t0 = time.time()
        out = jax.device_put(arr, dev)
        # the probe measures completed transfers; per-piece
        # sync is the alternation rule under test
        out.block_until_ready()  # bigdl: disable=sync-in-loop
        # fetch a slice: a readback is a completion signal that holds
        # on every backend, and a random payload cannot be deduplicated
        # anywhere on the way
        np.asarray(out[:64])
        dt = max(time.time() - t0, 1e-9)
        bps = arr.nbytes / dt
        if bps >= best_bps:
            best_bps = bps
            chosen = arr.nbytes
        elif bps < drop_ratio * best_bps:
            break  # over the cliff: stop probing larger sizes
        else:
            chosen = arr.nbytes  # slower but acceptable; keep growing
        mb *= 2
    _cached_chunk = chosen
    return chosen
