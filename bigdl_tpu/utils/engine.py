"""Engine — runtime/topology configuration (BigDL utils/Engine.scala:36).

BigDL's ``Engine`` discovers node/core counts from the Spark conf and owns two
thread pools. On TPU those roles collapse into: device discovery via
``jax.devices()``, a ``jax.sharding.Mesh`` describing the pod slice, and dtype
policy. XLA owns all threading; there is no ThreadPool equivalent
(utils/ThreadPool.scala is intentionally absent — stragglers don't exist on a
synchronous TPU pod, so ``invokeAndWait2``'s timeout machinery is moot).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


#: the checkout's root — where the compile cache lives when nobody
#: placed it from outside
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. For command-line entry points only — never on
    ``import bigdl_tpu`` and never under pytest.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: JAX
    reads the variable itself, so this touches nothing. Unset, the
    cache goes to ``<checkout>/.jax_cache``. The directory is part of
    the cache key's neighbourhood — a path that moved (a temp dir, a
    pid, a date) never hits — so it is fixed, and no other code sets
    one."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Engine:
    """Process-global runtime config: devices, mesh, dtype policy.

    ``Engine.init()`` must run before training, like the reference's
    ``Engine.init`` (Engine.scala:93) — but here it only snapshots device
    topology and builds the default data-parallel mesh.
    """

    _initialized = False
    _distributed_started = False
    _mesh: Optional[jax.sharding.Mesh] = None
    _node_number = 1
    _core_number = 1
    _default_dtype = jnp.float32
    _compute_dtype = jnp.float32

    # -- lifecycle ---------------------------------------------------------
    @classmethod
    def init(cls, node_number: Optional[int] = None,
             core_number: Optional[int] = None,
             mesh_axes: Sequence[str] = ("data",),
             mesh_shape: Optional[Sequence[int]] = None) -> "Engine":
        """Discover devices and build the default mesh.

        node_number/core_number are accepted for reference API parity
        (Engine.scala:93 signature) but topology truly comes from
        ``jax.devices()``: nodes = process count, cores = local device count.
        """
        devices = jax.devices()
        cls._node_number = jax.process_count()
        cls._core_number = max(1, len(devices) // max(1, jax.process_count()))
        if mesh_shape is None:
            mesh_shape = [len(devices)] + [1] * (len(mesh_axes) - 1)
        mesh_devices = np.array(devices).reshape(tuple(mesh_shape))
        cls._mesh = jax.sharding.Mesh(mesh_devices, tuple(mesh_axes))
        cls._initialized = True
        return cls

    @classmethod
    def init_distributed(cls, coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         initialization_timeout: Optional[int] = None,
                         **init_kwargs) -> "Engine":
        """Multi-host bring-up: ``jax.distributed.initialize`` then
        ``init()`` — the role the reference's Engine.init played on Spark
        (executor discovery, Engine.scala:100-103). Parameters default to
        the standard JAX env vars (JAX_COORDINATOR_ADDRESS etc.), so a
        pod launcher only needs to set the environment.
        """
        if not cls._distributed_started:
            # honor the documented env contract ourselves —
            # jax.distributed.initialize only auto-detects managed
            # clusters (Slurm etc.), not raw JAX_* variables (which is
            # what tools/launch provides, the spark-submit role)
            if coordinator_address is None:
                coordinator_address = os.environ.get(
                    "JAX_COORDINATOR_ADDRESS")
            if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
                num_processes = int(os.environ["JAX_NUM_PROCESSES"])
            if process_id is None and "JAX_PROCESS_ID" in os.environ:
                process_id = int(os.environ["JAX_PROCESS_ID"])
            # jax.distributed.initialize is once-per-process and cannot
            # be undone by Engine.reset()
            kw = {}
            if initialization_timeout is not None:
                kw["initialization_timeout"] = initialization_timeout
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id, **kw)
            cls._distributed_started = True
        return cls.init(**init_kwargs)

    @classmethod
    def is_initialized(cls) -> bool:
        return cls._initialized

    @classmethod
    def reset(cls):
        cls._initialized = False
        cls._mesh = None

    # -- topology ----------------------------------------------------------
    @classmethod
    def mesh(cls) -> jax.sharding.Mesh:
        if not cls._initialized:
            cls.init()
        return cls._mesh

    @classmethod
    def set_mesh(cls, mesh: jax.sharding.Mesh):
        cls._mesh = mesh
        cls._initialized = True
        return cls

    @classmethod
    def node_number(cls) -> int:
        """Host count (Engine.nodeNumber, Engine.scala:147)."""
        return cls._node_number

    @classmethod
    def core_number(cls) -> int:
        """Per-host device count (Engine.coreNumber, Engine.scala:152)."""
        return cls._core_number

    @classmethod
    def device_count(cls) -> int:
        return len(jax.devices())

    # -- dtype policy ------------------------------------------------------
    @classmethod
    def set_default_dtype(cls, dtype):
        """Parameter dtype (BigDL's Float/Double TensorNumeric choice)."""
        cls._default_dtype = jnp.dtype(dtype)
        return cls

    @classmethod
    def default_dtype(cls):
        return cls._default_dtype

    @classmethod
    def set_compute_dtype(cls, dtype):
        """Activation/matmul dtype; bf16 is the TPU analogue of the
        reference's fp16 gradient compression (FP16CompressedTensor.scala)."""
        cls._compute_dtype = jnp.dtype(dtype)
        return cls

    @classmethod
    def compute_dtype(cls):
        return cls._compute_dtype
