"""Typed, bounded configuration search spaces for the autotuner.

Every performance knob the package measured into existence — the
``steps_per_sync`` window K (PR 4), ZeRO stage (PR 8), precision preset
(PR 9), the pallas flash toggle (PR 11) for training; length-bucket
ladder, continuous-batching slots, speculation depth and prefix-cache
bytes (PRs 6/14) for serving — becomes one axis of a declared space.
Axes are **bounded at construction** (a space whose values fall outside
the documented knob ranges refuses to exist) and cross-axis validity is
expressed in :func:`enumerate_candidates` as CODE, not prose: invalid
combinations are returned with their reason, never silently dropped.

The grammar is deliberately flat — a space is a cartesian product of
small tuples minus the coded constraints — because every candidate
must be cheap to price statically (``autotune/prune``) and the sweep
must stay enumerable, deterministic and auditable.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["SpaceError", "Candidate", "TrainSpace", "ServingSpace",
           "enumerate_candidates"]

#: the precision presets ``PrecisionPolicy.named`` accepts — the ONE
#: list, mirrored here so a space typo fails at construction, not after
#: an hour of measuring
PRECISION_PRESETS = ("f32", "bf16_mixed", "f16_mixed")

#: train models the tuner knows how to build tiny twins of
TRAIN_MODELS = ("mlp", "transformer_lm")


class SpaceError(ValueError):
    """A search-space axis violated its documented bounds (typed so
    callers can distinguish a bad space from a bad candidate)."""


@dataclass(frozen=True)
class Candidate:
    """One point of a search space: an immutable ``(key, value)``
    mapping plus the regime it configures. ``cid`` is the stable
    identifier the leaderboard, the pruned-candidate log and the tuned
    artifact all key on — same values, same cid, every process."""

    regime: str  # "train" | "serving"
    items: Tuple[Tuple[str, object], ...]

    @property
    def config(self) -> Dict[str, object]:
        """The candidate's axis values as a plain dict."""
        return dict(self.items)

    @property
    def cid(self) -> str:
        """Deterministic candidate id, e.g.
        ``train:batch_size=16,steps_per_sync=8,...`` (keys sorted)."""
        parts = ",".join(f"{k}={_fmt(v)}" for k, v in sorted(self.items))
        return f"{self.regime}:{parts}"

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready form (lists for tuple-valued axes)."""
        return {"regime": self.regime, "cid": self.cid,
                "config": {k: (list(v) if isinstance(v, tuple) else v)
                           for k, v in self.items}}

    def __repr__(self) -> str:
        return f"Candidate({self.cid})"


def _fmt(v) -> str:
    if isinstance(v, tuple):
        return "[" + "x".join(str(e) for e in v) + "]"
    return str(v)


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SpaceError(msg)


@dataclass(frozen=True)
class TrainSpace:
    """The training-regime axes: ``steps_per_sync`` K x ZeRO stage x
    precision preset x flash attention on/off x batch size x
    sequence-parallel degree x blockwise long-context routing, over a
    named tiny model twin (``mlp`` | ``transformer_lm``). Bounds are
    enforced at construction; cross-axis validity (ZeRO divisibility,
    flash needs attention, SP needs shard_map + devices, blockwise
    needs flash) lives in :func:`enumerate_candidates`."""

    steps_per_sync: Tuple[int, ...] = (1, 8)
    zero_stage: Tuple[int, ...] = (0,)
    precision: Tuple[str, ...] = ("f32",)
    flash: Tuple[bool, ...] = (False,)
    batch_size: Tuple[int, ...] = (16,)
    #: sequence-parallel degree (0 = dense attention; >= 2 installs a
    #: SeqParallelConfig over a degree-wide "seq" mesh axis)
    seq_parallel: Tuple[int, ...] = (0,)
    #: blockwise long-context flash routing past the VMEM budget
    #: (KernelConfig.long_context) — only meaningful with flash=True
    long_context: Tuple[bool, ...] = (False,)
    model: str = "mlp"

    def __post_init__(self):
        for name in ("steps_per_sync", "zero_stage", "precision",
                     "flash", "batch_size", "seq_parallel",
                     "long_context"):
            _require(len(getattr(self, name)) > 0,
                     f"TrainSpace.{name} must be non-empty")
        _require(all(d == 0 or 2 <= d <= 64 for d in self.seq_parallel),
                 f"seq_parallel degrees must be 0 (off) or in [2, 64], "
                 f"got {self.seq_parallel}")
        _require(all(isinstance(b, bool) for b in self.long_context),
                 f"long_context values must be bools, got "
                 f"{self.long_context}")
        _require(all(1 <= k <= 512 for k in self.steps_per_sync),
                 f"steps_per_sync values must be in [1, 512], got "
                 f"{self.steps_per_sync}")
        _require(all(s in (0, 1, 2, 3) for s in self.zero_stage),
                 f"zero_stage values must be in 0..3, got "
                 f"{self.zero_stage}")
        _require(all(p in PRECISION_PRESETS for p in self.precision),
                 f"precision values must be from {PRECISION_PRESETS}, "
                 f"got {self.precision}")
        _require(all(isinstance(f, bool) for f in self.flash),
                 f"flash values must be bools, got {self.flash}")
        _require(all(1 <= b <= 65536 for b in self.batch_size),
                 f"batch_size values must be in [1, 65536], got "
                 f"{self.batch_size}")
        _require(self.model in TRAIN_MODELS,
                 f"model must be one of {TRAIN_MODELS}, "
                 f"got {self.model!r}")

    def axes(self) -> Dict[str, Sequence]:
        """Axis name -> value tuple, enumeration order (sorted by axis
        name so candidate order is a pure function of the space)."""
        return {"batch_size": self.batch_size, "flash": self.flash,
                "long_context": self.long_context,
                "precision": self.precision,
                "seq_parallel": self.seq_parallel,
                "steps_per_sync": self.steps_per_sync,
                "zero_stage": self.zero_stage}


@dataclass(frozen=True)
class ServingSpace:
    """The serving-regime axes: length-bucket ladder x slots x
    speculation depth k x prefix-cache bytes x chunked-prefill width,
    at a fixed ``max_len``. The GenerationService contract — the top
    ladder rung IS the cache time axis — is checked per ladder at
    construction; the chunk-divides-every-larger-rung admission rule
    per candidate in :func:`enumerate_candidates`."""

    max_len: int = 64
    length_buckets: Tuple[Tuple[int, ...], ...] = ((64,),)
    slots: Tuple[int, ...] = (4,)
    speculation_k: Tuple[int, ...] = (0,)
    prefix_cache_bytes: Tuple[int, ...] = (0,)
    #: chunked-prefill width (0 = single-shot): long prompts admit in
    #: fixed [rows, chunk] pieces — the engine's divide-every-larger-
    #: rung admission rule is coded per ladder in enumerate_candidates
    prefill_chunk: Tuple[int, ...] = (0,)

    def __post_init__(self):
        _require(1 <= self.max_len <= 131072,
                 f"max_len must be in [1, 131072], got {self.max_len}")
        _require(all(0 <= c <= self.max_len for c in self.prefill_chunk),
                 f"prefill_chunk values must be in [0, max_len="
                 f"{self.max_len}], got {self.prefill_chunk}")
        for name in ("length_buckets", "slots", "speculation_k",
                     "prefix_cache_bytes", "prefill_chunk"):
            _require(len(getattr(self, name)) > 0,
                     f"ServingSpace.{name} must be non-empty")
        for ladder in self.length_buckets:
            _require(len(ladder) > 0 and
                     all(isinstance(b, int) and b > 0 for b in ladder),
                     f"ladder {ladder} must be positive ints")
            _require(tuple(sorted(set(ladder))) == tuple(ladder),
                     f"ladder {ladder} must be strictly ascending")
            _require(ladder[-1] == self.max_len,
                     f"ladder {ladder} top rung must equal "
                     f"max_len={self.max_len} (the cache time axis)")
        _require(all(1 <= s <= 1024 for s in self.slots),
                 f"slots values must be in [1, 1024], got {self.slots}")
        _require(all(0 <= k <= 8 for k in self.speculation_k),
                 f"speculation_k values must be in [0, 8], got "
                 f"{self.speculation_k}")
        _require(all(b >= 0 for b in self.prefix_cache_bytes),
                 f"prefix_cache_bytes values must be >= 0, got "
                 f"{self.prefix_cache_bytes}")

    def axes(self) -> Dict[str, Sequence]:
        """Axis name -> value tuple, enumeration order."""
        return {"length_buckets": self.length_buckets,
                "prefill_chunk": self.prefill_chunk,
                "prefix_cache_bytes": self.prefix_cache_bytes,
                "slots": self.slots,
                "speculation_k": self.speculation_k}


def _train_constraints(cfg: Dict[str, object], space: TrainSpace,
                       ndev: int) -> Optional[str]:
    """The coded validity rules for one train candidate; returns the
    violation reason or None. These mirror REAL runtime refusals
    (``tools/perf`` exits on ZeRO/batch mismatch; flash attention has
    nothing to dispatch on an attention-free model), so an invalid
    point is rejected here instead of wasting a measurement window."""
    if cfg["zero_stage"] > 0 and cfg["batch_size"] % ndev:
        return (f"zero_stage={cfg['zero_stage']} needs batch_size "
                f"divisible by the {ndev}-device data mesh, got "
                f"{cfg['batch_size']}")
    if cfg["flash"] and space.model != "transformer_lm":
        return (f"flash=True has no attention to dispatch on "
                f"model={space.model!r} (the toggle would silently "
                f"measure the identical program twice)")
    if cfg["long_context"] and not cfg["flash"]:
        return ("long_context=True is a routing of the flash dispatch "
                "(blockwise past the VMEM budget); with flash=False "
                "it would measure the identical reference program "
                "twice")
    sp = int(cfg["seq_parallel"])
    if sp > 0:
        if space.model != "transformer_lm":
            return (f"seq_parallel={sp} has no attention to shard on "
                    f"model={space.model!r}")
        if sp > ndev:
            return (f"seq_parallel={sp} needs a {sp}-device sequence "
                    f"mesh, process has {ndev}")
        if cfg["zero_stage"] > 0:
            return (f"seq_parallel={sp} with zero_stage="
                    f"{cfg['zero_stage']}: the default measure "
                    f"harness builds a 1-D mesh per candidate — "
                    f"compose SP with ZeRO on a 2-D mesh via a custom "
                    f"runner=")
    return None


def _serving_constraints(cfg: Dict[str, object], space: ServingSpace
                         ) -> Optional[str]:
    """Coded validity rules for one serving candidate."""
    if cfg["speculation_k"] >= space.max_len:
        return (f"speculation_k={cfg['speculation_k']} must be < "
                f"max_len={space.max_len} (the verify forward needs "
                f"room for k proposed tokens)")
    if cfg["speculation_k"] > 0 and cfg["prefix_cache_bytes"] > 0:
        return ("speculation_k > 0 with prefix_cache_bytes > 0: the "
                "speculative decoder manages its own cache seeding and "
                "does not compose with the prefix cache in one service")
    chunk = int(cfg["prefill_chunk"])
    if chunk > 0:
        # the engine's own admission rule (DecodeEngine raises on it):
        # chunked rungs must split into an exact number of chunks
        bad = [b for b in cfg["length_buckets"] if b > chunk and b % chunk]
        if bad:
            return (f"prefill_chunk={chunk} must divide every larger "
                    f"ladder rung, fails on {bad} of "
                    f"{cfg['length_buckets']}")
        if all(b <= chunk for b in cfg["length_buckets"]):
            return (f"prefill_chunk={chunk} >= the top rung "
                    f"{cfg['length_buckets'][-1]}: no rung ever "
                    f"chunks, the candidate measures the single-shot "
                    f"program twice")
    return None


def enumerate_candidates(space, ndev: Optional[int] = None
                         ) -> Tuple[List[Candidate],
                                    List[Tuple[Candidate, str]]]:
    """Deterministically enumerate a space: the cartesian product of
    its axes (axis-name-sorted, value order as declared) split by the
    coded validity constraints into ``(valid, invalid)`` where each
    invalid entry carries its reason — nothing is silently dropped.

    ``ndev`` is the data-mesh width the ZeRO divisibility rule checks
    against (default: the process's JAX device count)."""
    if isinstance(space, TrainSpace):
        regime, check = "train", _train_constraints
    elif isinstance(space, ServingSpace):
        regime, check = "serving", _serving_constraints
    else:
        raise SpaceError(f"not a search space: {type(space).__name__}")
    if ndev is None and regime == "train":
        import jax
        ndev = len(jax.devices())
    axes = space.axes()
    names = list(axes)
    valid: List[Candidate] = []
    invalid: List[Tuple[Candidate, str]] = []
    for values in itertools.product(*(axes[n] for n in names)):
        cfg = dict(zip(names, values))
        items = dict(cfg)
        if regime == "train":
            # the model twin is per-space, not an axis, but pruning and
            # measurement are per-candidate — carry it on each point
            items["model"] = space.model
        cand = Candidate(regime, tuple(sorted(items.items())))
        reason = check(cfg, space, ndev) if regime == "train" \
            else check(cfg, space)
        if reason is None:
            valid.append(cand)
        else:
            invalid.append((cand, reason))
    return valid, invalid
