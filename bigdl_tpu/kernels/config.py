"""Kernel selection policy: which pallas kernels run, and how.

The reference's L0 was a build-time choice (MKL JNI vs BigQuant C++,
SURVEY.md §1); ours is a *runtime policy*: a :class:`KernelConfig`
names which pallas kernels the dispatch layer
(:mod:`bigdl_tpu.kernels.dispatch`) may select, everything else runs
the pure-jnp reference path. The default is resolved lazily from the
backend — **every kernel on on a real TPU** (decode, int8 and gmm skip
work the reference cannot skip; flash since PR 35, whose measured table
is in PERF.md section 6: a layer of ``[4,16,1024,64]`` attention,
forward + backward, takes 0.68 ms fused against 2.89 ms as einsums, and
the dispatch declines the short lengths where the einsum form won),
**everything off on CPU** — and the ``BIGDL_KERNELS`` env var overrides
it without touching code:

- ``BIGDL_KERNELS=1`` / ``on`` / ``all`` — every kernel on;
- ``BIGDL_KERNELS=0`` / ``off`` — every kernel off;
- ``BIGDL_KERNELS=flash,decode`` — a comma subset of
  ``flash`` / ``decode`` / ``int8`` / ``gmm``.

``interpret`` (``None`` = auto) runs the kernels through the pallas
interpreter instead of Mosaic — auto means *interpret everywhere but
real TPU*, which is how tier-1 on CPU executes the real kernel bodies
(docs/kernels.md "Interpret-mode testing"). On a TPU backend nothing
resolves to the interpreter unless ``KernelConfig(interpret=True)`` was
passed explicitly, and the first resolution of the default policy logs
the backend it found and whether kernels compile or interpret — a JAX
that fell back to the CPU does not pass for a chip in silence.

The active config is read at TRACE time: a compiled program bakes in
the kernel choice that was active when it was built (the serving
``CompileCache`` keys programs per servable, so a toggle never mutates
an already-compiled program — build a fresh engine/service to switch).
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading
from dataclasses import dataclass
from typing import Iterator, Optional

__all__ = ["KernelConfig", "configure", "get_config", "use", "enabled",
           "interpret_mode", "active_label"]

logger = logging.getLogger("bigdl_tpu")

#: the ops a config can enable, in the order the env parser accepts
_OPS = ("flash", "decode", "int8", "gmm")


@dataclass(frozen=True)
class KernelConfig:
    """Which pallas kernels the dispatch layer may select.

    ``flash_attention`` — the tiled flash-attention training kernel;
    ``decode_attention`` — the ragged decode kernel (reads only
    ``lengths[i]`` valid KV per slot); ``int8_matmul`` — the fused
    dequant-int8-GEMM serving kernel; ``grouped_matmul`` — the routed
    expert layer's grouped product. ``interpret=None`` auto-selects
    the pallas interpreter off-TPU. No field holds a tile size: the
    flash dispatch derives chunk and tile from the shape
    (:func:`bigdl_tpu.kernels.dispatch.flash_route`), the ragged decode
    kernel its K/V tile from the shapes it is handed
    (:func:`bigdl_tpu.kernels.ragged_decode.kv_tile`)."""

    flash_attention: bool = False
    decode_attention: bool = False
    int8_matmul: bool = False
    grouped_matmul: bool = False
    interpret: Optional[bool] = None
    #: compiled-mode VMEM working-set budget (MiB) for one full-row
    #: flash program; ``None`` reads ``BIGDL_VMEM_BUDGET_MB`` and falls
    #: back to the default: the scoped VMEM those kernels ask the
    #: compiler for (``common.FLASH_VMEM_LIMIT_MB``) less 4 MiB, which
    #: is inside what it takes (tests/test_chip_compile.py;
    #: ``dispatch._flash_vmem_bytes`` has the budget math). A budget
    #: over the limit only moves where the compiler refuses
    vmem_budget_mb: Optional[int] = None
    #: whether shapes past the VMEM budget, which the full-row kernel
    #: cannot hold, route to the blockwise long-context flash kernel
    #: (key dimension tiled through VMEM) instead of declining to the
    #: einsum reference
    long_context: bool = True

    @classmethod
    def all_on(cls, **kw) -> "KernelConfig":
        """Every kernel enabled — ``BIGDL_KERNELS=1``, the test/bench
        on-legs, and since PR 35 the real-TPU default (module
        docstring)."""
        return cls(flash_attention=True, decode_attention=True,
                   int8_matmul=True, grouped_matmul=True, **kw)

    @classmethod
    def off(cls) -> "KernelConfig":
        """Every kernel disabled — the pure-jnp reference everywhere
        (the CPU default)."""
        return cls()

    @classmethod
    def from_env(cls, value: str) -> "KernelConfig":
        """Parse a ``BIGDL_KERNELS`` value (module docstring has the
        grammar); unknown op names raise so a typo cannot silently run
        the slow path."""
        v = value.strip().lower()
        if v in ("1", "on", "all", "true"):
            return cls.all_on()
        if v in ("0", "off", "false", "none", ""):
            return cls.off()
        ops = {p.strip() for p in v.split(",") if p.strip()}
        unknown = ops - set(_OPS)
        if unknown:
            raise ValueError(
                f"BIGDL_KERNELS={value!r}: unknown kernel(s) "
                f"{sorted(unknown)} (choose from {list(_OPS)}, "
                "or 1/on/all, 0/off)")
        return cls(flash_attention="flash" in ops,
                   decode_attention="decode" in ops,
                   int8_matmul="int8" in ops,
                   grouped_matmul="gmm" in ops)

    @property
    def any_enabled(self) -> bool:
        """Whether any kernel is selected at all."""
        return (self.flash_attention or self.decode_attention
                or self.int8_matmul or self.grouped_matmul)

    def resolve_interpret(self) -> bool:
        """The effective interpret flag: auto (``None``) means
        interpret everywhere but real TPU."""
        if self.interpret is not None:
            return bool(self.interpret)
        import jax
        return jax.default_backend() != "tpu"

    def resolve_vmem_budget(self) -> int:
        """The effective flash VMEM budget in BYTES: an explicit
        ``vmem_budget_mb`` wins, else ``BIGDL_VMEM_BUDGET_MB``, else
        the kernels' own limit less 4 MiB."""
        mb = self.vmem_budget_mb
        if mb is None:
            env = os.environ.get("BIGDL_VMEM_BUDGET_MB")
            if env is not None:
                try:
                    mb = int(env)
                except ValueError:
                    raise ValueError(
                        f"BIGDL_VMEM_BUDGET_MB={env!r} is not an "
                        f"integer MiB count") from None
        if mb is None:
            from bigdl_tpu.kernels.common import FLASH_VMEM_LIMIT_MB
            mb = FLASH_VMEM_LIMIT_MB - 4
        if mb <= 0:
            raise ValueError(
                f"flash VMEM budget must be positive, got {mb} MiB")
        return mb * 1024 * 1024


_LOCK = threading.Lock()
_CONFIG: Optional[KernelConfig] = None  # None = resolve default lazily


def _default() -> KernelConfig:
    import jax
    backend = jax.default_backend()
    env = os.environ.get("BIGDL_KERNELS")
    if env is not None:
        cfg = KernelConfig.from_env(env)
    elif backend == "tpu":
        # every kernel: which shapes each takes is the dispatch's to
        # decide from what it measured (flash_route declines the short
        # sequences where the einsum form is faster)
        cfg = KernelConfig.all_on()
    else:
        cfg = KernelConfig.off()
    if cfg.any_enabled:
        on = [op for op, flag in zip(_OPS, (cfg.flash_attention,
                                            cfg.decode_attention,
                                            cfg.int8_matmul,
                                            cfg.grouped_matmul)) if flag]
        logger.info(
            "kernel policy: %s on backend %r, %s", "+".join(on), backend,
            "in the pallas INTERPRETER (no TPU backend)"
            if cfg.resolve_interpret() else "compiled by Mosaic")
    return cfg


def get_config() -> KernelConfig:
    """The active :class:`KernelConfig` (resolving the backend/env
    default on first use)."""
    global _CONFIG
    with _LOCK:
        if _CONFIG is None:
            _CONFIG = _default()
        return _CONFIG


def configure(config: Optional[KernelConfig]) -> None:
    """Install ``config`` as the active kernel policy; ``None``
    restores the backend/env default (re-resolved lazily)."""
    global _CONFIG
    with _LOCK:
        _CONFIG = config


@contextlib.contextmanager
def use(config: KernelConfig) -> Iterator[KernelConfig]:
    """Scoped :func:`configure`: the previous policy is restored on
    exit — the tests' and bench legs' on/off toggle."""
    global _CONFIG
    with _LOCK:
        prev = _CONFIG
        _CONFIG = config
    try:
        yield config
    finally:
        with _LOCK:
            _CONFIG = prev


def enabled(op: str) -> bool:
    """Whether kernel ``op`` (``flash`` | ``decode`` | ``int8`` | ``gmm``) is
    enabled under the active config."""
    cfg = get_config()
    try:
        return {"flash": cfg.flash_attention,
                "decode": cfg.decode_attention,
                "int8": cfg.int8_matmul,
                "gmm": cfg.grouped_matmul}[op]
    except KeyError:
        raise ValueError(f"unknown kernel op {op!r} "
                         f"(choose from {list(_OPS)})") from None


def interpret_mode() -> bool:
    """The active config's effective interpret flag."""
    return get_config().resolve_interpret()


def active_label() -> str:
    """``"pallas"`` when any kernel is enabled, else ``"reference"`` —
    the ``kernel=`` label value program profiles carry so MFU/HBM
    gauges compare the two paths side by side
    (:mod:`bigdl_tpu.telemetry.programs`)."""
    return "pallas" if get_config().any_enabled else "reference"


