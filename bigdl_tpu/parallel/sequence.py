"""Sequence-parallel training policy — SP as a train-step concern.

The module-level knob (``MultiHeadAttention(ring_axis=...)``) bakes
sequence parallelism into the MODEL; that is the right shape for a
hand-built network but the wrong one for the Optimizer product path,
where the same model object should train dense on one chip and
sequence-sharded on a mesh without being rebuilt. This module makes SP
a *policy* the train step installs:

- :class:`SeqParallelConfig` names the mesh axis the sequence dim
  shards over and which exact kernel runs it — ``ring``
  (:mod:`bigdl_tpu.parallel.ring_attention`: K/V blocks rotate via
  ``ppermute``, memory linear in the LOCAL length) or ``ulysses``
  (:mod:`bigdl_tpu.parallel.ulysses`: all-to-all head re-sharding,
  full-sequence attention per head group);
- ``build_train_step(seq_parallel=...)`` installs the config for the
  duration of the step TRACE (:func:`use_sequence_parallel` — trace-
  scoped exactly like the kernel dispatch config), and every
  ``MultiHeadAttention`` without an explicit ``ring_axis`` adopts it;
- like ``ZeroConfig``, the policy is a NO-OP when it cannot apply
  (:meth:`SeqParallelConfig.active_on`): no mesh, axis missing or size
  1 — the dense path runs and the exported ``train/seq_parallel/degree`` gauge says 1.

Composition story (docs/performance.md "Long context"): the SP
collectives live INSIDE the traced step, so under
``set_steps_per_sync(K)`` they land inside the scan body — the
windowed dispatch boundary stays collective-free (the ``[hlo]``
``entry-collective`` check covers ``collective-permute`` and
``all-to-all``) — and ZeRO's gradient reduce-scatter / params gather
compose orthogonally: ZeRO shards the *weight update* over the data
axis, SP shards *attention activations* over the sequence axis.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Iterator, Optional

import bigdl_tpu.telemetry as telemetry

__all__ = ["SeqParallelConfig", "use_sequence_parallel",
           "active_sequence_parallel"]

#: the axis sizes the active policy actually achieved — 1 means SP is
#: off or could not apply (no mesh / missing axis), so
#: a dashboard reads the degree it is paying for, not the one asked for
_G_DEGREE = telemetry.gauge(
    "train/seq_parallel/degree",
    "active sequence-parallel mesh degree (1 = dense attention)")


@dataclass(frozen=True)
class SeqParallelConfig:
    """Which sequence-parallel kernel runs attention, over which axis.

    ``impl`` — ``"ring"`` or ``"ulysses"`` (module docstring has the
    trade); ``axis`` the mesh axis carrying the sequence dim; ``mesh``
    the mesh it lives on (None resolves the Engine's, matching
    ``MultiHeadAttention``'s own resolution)."""

    axis: str = "seq"
    impl: str = "ring"
    mesh: Optional[object] = None

    def __post_init__(self):
        if self.impl not in ("ring", "ulysses"):
            raise ValueError(
                f"seq-parallel impl must be 'ring' or 'ulysses', got "
                f"{self.impl!r}")

    def resolve_mesh(self):
        """The mesh the policy would actually run on (None = cannot
        apply here)."""
        from bigdl_tpu.parallel.mesh import resolve_axis_mesh
        return resolve_axis_mesh(self.mesh, self.axis)

    def degree(self) -> int:
        """The sequence-shard count the policy achieves on the
        resolved mesh (1 = it will not apply)."""
        mesh = self.resolve_mesh()
        return int(mesh.shape[self.axis]) if mesh is not None else 1

    def active_on(self, mesh=None) -> bool:
        """Whether the policy applies: the axis splits >1 ways on the
        resolved mesh. Mirrors
        ``ZeroConfig.active_on`` — an inapplicable policy is a quiet
        no-op, not an error, so one training script serves every
        topology."""
        if mesh is not None and self.mesh is None:
            from bigdl_tpu.parallel.mesh import resolve_axis_mesh
            return resolve_axis_mesh(mesh, self.axis) is not None
        return self.resolve_mesh() is not None

    def kernel(self):
        """The per-shard attention kernel the config names."""
        if self.impl == "ulysses":
            from bigdl_tpu.parallel.ulysses import ulysses_attention
            return ulysses_attention
        from bigdl_tpu.parallel.ring_attention import ring_attention
        return ring_attention


_TLS = threading.local()


def active_sequence_parallel() -> Optional[SeqParallelConfig]:
    """The policy installed on this thread's current trace (None =
    dense). Read by ``MultiHeadAttention.forward_fn`` for modules
    without an explicit ``ring_axis``."""
    return getattr(_TLS, "config", None)


@contextlib.contextmanager
def use_sequence_parallel(
        config: Optional[SeqParallelConfig]
) -> Iterator[Optional[SeqParallelConfig]]:
    """Scoped install of ``config`` as the thread's active policy —
    wrapped around the model apply inside ``build_train_step`` so the
    adoption happens at TRACE time (the compiled program bakes the
    routing in; toggling later never mutates an existing program,
    exactly the kernel-config contract)."""
    prev = getattr(_TLS, "config", None)
    _TLS.config = config
    try:
        yield config
    finally:
        _TLS.config = prev


def record_degree(degree: int) -> None:
    """Export the achieved SP degree (``train/seq_parallel/degree``) —
    called once per ``build_train_step``."""
    _G_DEGREE.set(int(degree))
