"""Environment capability probes — ONE auditable reason per exclusion.

One long-standing tier-1 exclusion is environmental, not a bug: some
CPU runtimes rendezvous fine but cannot EXECUTE cross-process
collectives ("Multiprocess computations aren't implemented on the CPU
backend"). Tests and the chaos host-kill leg used to discover this by
crashing; the probe discovers it ONCE, caches the verdict for the
process, and hands back a precise reason string — so a skip reads
"env: <exact missing capability>" instead of a stack trace, and a
runtime that DOES support the surface runs the real tests with no code
change.
"""
from __future__ import annotations

import functools
import os
import subprocess
import sys
from typing import Tuple

#: the two-process collective probe: rendezvous + ONE jitted
#: cross-process reduction. Prints PROBE_OK only if the computation
#: actually executed — rendezvous alone is not the capability.
_PROBE_SRC = """\
import sys
import jax
# the probe asks about the CPU backend, and the chip is the parent's
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=sys.argv[1],
                           num_processes=2, process_id=int(sys.argv[2]))
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np
mesh = Mesh(np.array(jax.devices()), ("d",))
sh = NamedSharding(mesh, P("d"))
x = jax.make_array_from_process_local_data(
    sh, jnp.ones((1,), jnp.float32), (2,))
y = jax.jit(lambda a: a.sum(),
            out_shardings=NamedSharding(mesh, P()))(x)
v = float(jax.device_get(y.addressable_shards[0].data))
assert v == 2.0, v
print("PROBE_OK")
"""


@functools.lru_cache(maxsize=None)
def multiprocess_cpu(timeout_s: float = 120.0) -> Tuple[bool, str]:
    """Probe (once per process) whether this runtime can EXECUTE
    cross-process collectives on the CPU backend: spawn a two-process
    gang, rendezvous, run one jitted cross-process reduction. Returns
    ``(ok, reason)`` — the reason is the auditable skip string when
    not ok. Override with ``BIGDL_ASSUME_MULTIPROCESS_CPU=1|0`` (CI
    images that already know their runtime skip the ~10s probe)."""
    forced = os.environ.get("BIGDL_ASSUME_MULTIPROCESS_CPU")
    if forced == "1":
        return True, "forced by BIGDL_ASSUME_MULTIPROCESS_CPU=1"
    if forced == "0":
        return False, ("env: multiprocess CPU collectives disabled by "
                       "BIGDL_ASSUME_MULTIPROCESS_CPU=0")
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one device per probe process
    procs = [subprocess.Popen(
        [sys.executable, "-c", _PROBE_SRC, coord, str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout_s)
            outs.append(out or "")
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
            p.communicate()
        return False, ("env: multiprocess CPU probe timed out "
                       f"after {timeout_s:.0f}s (rendezvous or "
                       "collective never completed)")
    if all(p.returncode == 0 for p in procs) \
            and all("PROBE_OK" in o for o in outs):
        return True, "multiprocess CPU collectives available"
    tail = next((o for p, o in zip(procs, outs) if p.returncode != 0),
                outs[0] if outs else "")
    lines = [ln for ln in tail.strip().splitlines() if ln.strip()]
    detail = lines[-1][-160:] if lines else "no output"
    return False, ("env: CPU backend cannot execute cross-process "
                   f"collectives ({detail})")


__all__ = ["multiprocess_cpu"]
