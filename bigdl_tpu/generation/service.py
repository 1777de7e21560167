"""GenerationService — the autoregressive-serving façade.

``GenerationService(registry, config)`` turns any decoder model with
the incremental-decode contract (``apply(..., cache=, positions=,
attend_len=)`` — :class:`~bigdl_tpu.models.transformer.TransformerLM`
out of the box) into a token-streaming generation service on the same
chassis as batched serving: the :class:`~bigdl_tpu.serving.registry.
ModelRegistry` for versioned hot-swap, the :class:`~bigdl_tpu.serving.
compile_cache.CompileCache` for counted, bounded compilation, and one
:class:`~bigdl_tpu.generation.loop.DecodeLoop` per model name for
continuous batching. Everything runs on plain threads
(``JAX_PLATFORMS=cpu`` works end to end; on TPU the same programs jit
onto the chips).

    from bigdl_tpu.generation import GenerationService, GenerationConfig

    svc = GenerationService(config=GenerationConfig(
        slots=8, max_len=256, eos_token=0))
    svc.load("lm", model)                      # warms 2K programs
    stream = svc.generate("lm", prompt_ids, max_new_tokens=32)
    for tok in stream:                         # tokens as they decode
        ...
    svc.load("lm", new_model)                  # hot-swap under traffic
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import numpy as np

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.generation.engine import DecodeEngine
from bigdl_tpu.generation.kv_cache import KVCache, RecurrentStateError
from bigdl_tpu.generation.loop import DecodeLoop
from bigdl_tpu.generation.sampling import SamplingParams
from bigdl_tpu.generation.stream import TokenStream
from bigdl_tpu.serving.compile_cache import BucketLadder, CompileCache
from bigdl_tpu.serving.registry import ModelRegistry, Servable


_G_WINDOW_BYTES = telemetry.gauge(
    "serving/cache/window_bytes",
    "device bytes of the newest cache's sliding-window (ring) entries")
_G_GLOBAL_BYTES = telemetry.gauge(
    "serving/cache/global_bytes",
    "device bytes of the newest cache's whole-context entries")
_G_STATE_BYTES = telemetry.gauge(
    "serving/cache/state_bytes",
    "device bytes of the newest cache's recurrent-state entries")
_G_SLOTS = telemetry.gauge(
    "serving/cache/slots",
    "slots of the newest cache: what its bytes by kind divide by for "
    "the bytes one session holds")


@dataclass
class GenerationConfig:
    """Tuning surface (docs/serving.md "Generation" has the math).

    ``slots`` is the continuous-batching width — the number of
    concurrent generations one cache holds; ``max_len`` bounds
    prompt+generation length and sizes the cache's time axis;
    ``length_buckets`` overrides the powers-of-two ladder over sequence
    length (K rungs ⇒ ≤ 2K compiled programs per version: one
    prefill + one decode per rung — fewer rungs, fewer compiles, more
    padded attention). ``prefill_rows`` is the padded-prompt batch
    width admissions share. ``timeout_ms`` is the default per-request
    deadline (None = no deadline)."""
    slots: int = 8
    max_len: int = 256
    length_buckets: Optional[Sequence[int]] = None
    prefill_rows: int = 4
    #: chunked prefill (long context): prompts whose ladder rung
    #: exceeds this width prefill in fixed ``[prefill_rows, chunk]``
    #: pieces through the SAME per-rung program instead of one
    #: rung-wide shot — a 128K prompt never mints a 128K-wide token
    #: shape. Must divide every larger rung. None = single-shot.
    prefill_chunk: Optional[int] = None
    max_queue: int = 256
    eos_token: Optional[int] = None
    max_new_tokens: int = 64
    timeout_ms: Optional[float] = None
    #: device-byte budget for the prefix/KV reuse cache
    #: (``bigdl_tpu.fleet.prefix``): repeated full prompts seed their
    #: slot by device copy and skip prefill entirely. 0 disables.
    prefix_cache_bytes: int = 0


def apply_tuned_config(tuned, base: Optional[GenerationConfig] = None,
                       *, allow_mismatch: bool = False
                       ) -> GenerationConfig:
    """Build a :class:`GenerationConfig` from an autotuner artifact's
    serving winner (``python -m bigdl_tpu.tools.autotune``).

    ``tuned`` is a ``tuned.json`` path or an already-loaded
    ``autotune.TunedConfig``; paths are fingerprint-checked on load
    (typed ``FingerprintMismatchError`` on a foreign environment unless
    ``allow_mismatch``). The winner's ``length_buckets`` / ``slots`` /
    ``prefix_cache_bytes`` / ``prefill_chunk`` land on a copy of
    ``base`` (default: a fresh
    :class:`GenerationConfig`), with ``max_len`` snapped to the
    winner's ladder top — the service's own top-rung-is-the-cache-axis
    invariant. A winner tuned for the speculative decoder
    (``speculation_k > 0``) is refused: that path is configured on
    :class:`~bigdl_tpu.generation.speculative.SpeculativeDecoder`, not
    here, and dropping the axis silently would misapply the tuning."""
    import dataclasses

    from bigdl_tpu.autotune.config import (TunedConfig,
                                           TunedConfigError, load_tuned)

    if not isinstance(tuned, TunedConfig):
        tuned = load_tuned(tuned, allow_mismatch=allow_mismatch)
    winner = tuned.winner("serving")
    if int(winner.get("speculation_k", 0) or 0) > 0:
        raise TunedConfigError(
            f"serving winner has speculation_k="
            f"{winner['speculation_k']}: apply it to a "
            f"SpeculativeDecoder, not GenerationConfig")
    cfg = base or GenerationConfig()
    updates: Dict[str, object] = {}
    if "length_buckets" in winner:
        ladder = tuple(int(b) for b in winner["length_buckets"])
        updates["length_buckets"] = ladder
        updates["max_len"] = ladder[-1]
    if "slots" in winner:
        updates["slots"] = int(winner["slots"])
    if "prefix_cache_bytes" in winner:
        updates["prefix_cache_bytes"] = int(winner["prefix_cache_bytes"])
    if "prefill_chunk" in winner:
        pc = int(winner["prefill_chunk"] or 0)
        updates["prefill_chunk"] = pc if pc > 0 else None
    return dataclasses.replace(cfg, **updates)


class GenerationService:
    """Token-streaming generation over a hot-swappable multi-model
    registry (module docstring has the wiring; ``generate`` is the
    whole data plane)."""

    def __init__(self, registry: Optional[ModelRegistry] = None,
                 config: Optional[GenerationConfig] = None,
                 metrics_registry=None):
        # share a ModelRegistry (and metrics pane) with an
        # InferenceService by passing either the registry itself or
        # the service: score and generate the same versioned snapshots
        if registry is not None and hasattr(registry, "registry"):
            if metrics_registry is None:
                metrics_registry = registry.metrics_registry
            registry = registry.registry
        self.registry = registry or ModelRegistry()
        self.config = config or GenerationConfig()
        self.ladder = BucketLadder(self.config.max_len,
                                   self.config.length_buckets)
        if self.ladder.max_batch_size != self.config.max_len:
            # the top rung IS the cache's time axis; a shorter ladder
            # would leave unreachable cache rows, a longer one would
            # write past the cache
            raise ValueError(
                f"length_buckets top rung {self.ladder.max_batch_size} "
                f"must equal max_len={self.config.max_len}")
        self.metrics_registry = metrics_registry \
            if metrics_registry is not None else telemetry.MetricsRegistry()
        self.cache = CompileCache(metrics=self.metrics_registry)
        self.engine = DecodeEngine(self.cache, self.ladder,
                                   self.config.slots,
                                   self.config.prefill_rows,
                                   prefill_chunk=self.config.prefill_chunk)
        self.prefix = None
        if self.config.prefix_cache_bytes > 0:
            from bigdl_tpu.fleet.prefix import PrefixCache
            self.prefix = PrefixCache(self.config.prefix_cache_bytes,
                                      metrics=self.metrics_registry)
        self._lock = threading.Lock()
        self._loops: Dict[str, DecodeLoop] = {}
        self._unloading: set = set()
        self._warm_caches: Dict[tuple, "KVCache"] = {}
        self._shut_down = False

    # ------------------------------------------------------ lifecycle
    def load(self, name: str, model=None, *, path: Optional[str] = None,
             version: Optional[int] = None, activate: bool = True,
             warmup: bool = True) -> Servable:
        """Registry load + eager prefill/decode warmup.

        The version is registered inactive, its 2K program pair set is
        compiled (``warmup=True``, the default), and only THEN swapped
        in — a hot-swap under live decode traffic never serves a cold
        bucket, and in-flight generations keep decoding on the old
        snapshot throughout."""
        servable = self.registry.load(name, model, path=path,
                                      version=version, activate=False)
        if warmup:
            # warm into the cache the decode loop will ADOPT at this
            # version's first admission — one full-size K/V allocation
            # per version, not one for warmup plus one for serving
            kv = self._new_cache(servable)
            self.engine.warmup(servable, kv=kv)
            with self._lock:
                # at most ONE stashed cache per name: a previously
                # warmed version that never took traffic must not pin
                # its buffers forever (rolling back to it just
                # rebuilds a fresh cache at admission)
                for k in [k for k in self._warm_caches if k[0] == name]:
                    del self._warm_caches[k]
                self._warm_caches[servable.key] = kv
        if activate:
            self.registry.swap(name, servable.version)
        return servable

    def warmup(self, name: str) -> int:
        """Compile the prefill+decode pair for every ladder rung of
        the CURRENT version; returns how many programs that
        compiled."""
        return self.engine.warmup(self.registry.current(name))

    def swap(self, name: str, version: int) -> Servable:
        """Atomic hot-swap: generations already occupying slots finish
        on the snapshot they prefilled with; every later admission
        decodes ``version``."""
        return self.registry.swap(name, version)

    def unload(self, name: str, version: Optional[int] = None) -> None:
        """Unload a version (or the whole name, draining its decode
        loop) and release its compiled programs. While a whole-name
        unload is in flight the name admits nothing — a concurrent
        ``generate`` must not resurrect a loop for a model that is
        about to disappear."""
        if version is None:
            with self._lock:
                loop = self._loops.pop(name, None)
                self._unloading.add(name)
            try:
                if loop is not None:
                    loop.shutdown(drain=True)
                for key in self.registry.unload(name, version):
                    self.engine.drop(key)
                    self.cache.drop(key)
                    self._warm_caches.pop(key, None)
                    if self.prefix is not None:
                        self.prefix.drop_version(key)
            finally:
                with self._lock:
                    self._unloading.discard(name)
            return
        for key in self.registry.unload(name, version):
            self.engine.drop(key)
            self.cache.drop(key)
            self._warm_caches.pop(key, None)
            if self.prefix is not None:
                self.prefix.drop_version(key)

    def shutdown(self, drain: bool = True) -> None:
        """Stop admission on every decode loop; with ``drain`` finish
        queued + live generations first, else fail them typed."""
        with self._lock:
            self._shut_down = True
            loops = list(self._loops.values())
        for loop in loops:
            loop.shutdown(drain=drain)

    # ------------------------------------------------------- generate
    def _loop(self, name: str) -> DecodeLoop:
        with self._lock:
            loop = self._loops.get(name)
            if loop is None:
                if self._shut_down:
                    raise RuntimeError("GenerationService is shut down")
                if name in self._unloading:
                    raise KeyError(f"{name!r} is being unloaded")
                self.registry.current(name)  # fail fast on unknown names
                loop = DecodeLoop(
                    name, self.registry, self.engine,
                    max_len=self.config.max_len,
                    eos_token=self.config.eos_token,
                    max_queue=self.config.max_queue,
                    default_max_new=self.config.max_new_tokens,
                    timeout_ms=self.config.timeout_ms,
                    metrics=self.metrics_registry,
                    cache_provider=self._cache_for,
                    prefix_cache=self.prefix)
                self._loops[name] = loop
        return loop

    def _cache_for(self, servable) -> KVCache:
        """The decode loop's cache source: adopt the buffers load-time
        warmup already allocated for this version, else build fresh."""
        with self._lock:
            kv = self._warm_caches.pop(servable.key, None)
        if kv is not None:
            return kv
        return self._new_cache(servable)

    def _new_cache(self, servable) -> KVCache:
        """One version's cache, its entries of the kinds the model
        declares; the bytes by kind go to the always-on gauges."""
        kv = KVCache.for_model(servable.model, self.config.slots,
                               self.config.max_len)
        if self.prefix is not None and kv.recurrent:
            raise RecurrentStateError(
                f"{servable.name!r} keeps a recurrent state; the prefix "
                "cache stores a prompt's columns up to a position and "
                "would need a snapshot of the state there, which is not "
                "built (prefix_cache_bytes=0 serves it)")
        if self.prefix is not None and not kv.uniform:
            raise ValueError(
                f"{servable.name!r} keeps cache entries of several kinds "
                f"({sorted(set(kv.layout))}); the prefix cache stores one "
                "kind of block (prefix_cache_bytes=0 serves it)")
        by_kind = kv.kind_bytes()
        _G_WINDOW_BYTES.set(by_kind["window"], model=servable.name)
        _G_GLOBAL_BYTES.set(by_kind["global"], model=servable.name)
        _G_STATE_BYTES.set(by_kind["state"], model=servable.name)
        _G_SLOTS.set(kv.slots, model=servable.name)
        return kv

    def generate(self, name: str, prompt, *,
                 max_new_tokens: Optional[int] = None,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None, seed: int = 0,
                 timeout_ms: Optional[float] = None) -> TokenStream:
        """Submit one generation; returns a :class:`TokenStream` that
        streams tokens as the continuous-batching loop decodes them.
        ``temperature=0`` (default) is greedy; a positive temperature
        samples (optionally top-k-restricted) from the request's own
        seeded RNG stream, so identical requests are identical token
        for token."""
        return self._loop(name).submit(
            np.asarray(prompt),
            max_new_tokens=max_new_tokens,
            sampling=SamplingParams(temperature=temperature,
                                    top_k=top_k, seed=seed),
            timeout_ms=timeout_ms)

    def generate_tokens(self, name: str, prompt, **kw) -> np.ndarray:
        """Blocking convenience: the full generated token array."""
        return self.generate(name, prompt, **kw).result()

    def preempt(self, name: str, stream: TokenStream,
                err: BaseException):
        """Fail one of ``name``'s in-flight generations *typed* so its
        decode slot (or queue slot) goes to a higher-priority request —
        the fleet admission layer's preemption hook (see
        :meth:`~bigdl_tpu.generation.loop.DecodeLoop.preempt`).
        Returns ``"queued"``/``"live"``/None."""
        with self._lock:
            loop = self._loops.get(name)
        if loop is None:
            return None
        return loop.preempt(stream, err)

    # -------------------------------------------------------- metrics
    def compile_count(self, name: str,
                      version: Optional[int] = None) -> int:
        """Generation programs compiled for ``name`` (one version, or
        all) — the quantity the ≤ 2K acceptance bound is asserted
        on."""
        versions = [version] if version is not None \
            else self.registry.versions(name)
        return sum(self.engine.compile_count(_KeyOnly(name, v))
                   for v in versions)

    def metrics(self, name: str) -> Dict[str, float]:
        """Point-in-time generation stats for one model name: request/
        token counts, queue depth, live slots, cache occupancy,
        padding efficiency, TTFT and per-token-latency percentiles,
        and the compile count."""
        from bigdl_tpu.utils.profiling import percentile_summary
        labels = {"model": name}
        r = self.metrics_registry
        out: Dict[str, float] = {
            "request_count": int(r.counter(
                "serving/generation/requests").value(**labels)),
            "rejected": int(r.counter(
                "serving/generation/rejected").value(**labels)),
            "timed_out": int(r.counter(
                "serving/generation/timed_out").value(**labels)),
            "tokens": int(r.counter(
                "serving/generation/tokens").value(**labels)),
            "finished": int(r.counter(
                "serving/generation/finished").value(**labels)),
            "worker_restarts": int(r.counter(
                "serving/generation/worker_restarts").value(**labels)),
            "prefill_chunks": int(r.counter(
                "serving/generation/prefill_chunks").value(**labels)),
            "cache_occupancy": float(r.gauge(
                "serving/generation/cache_occupancy").value(**labels)),
            "padding_efficiency": float(r.gauge(
                "serving/generation/padding_efficiency").value(**labels)),
            "queue_depth": 0, "live_slots": 0,
        }
        with self._lock:
            loop = self._loops.get(name)
        if loop is not None:
            out["queue_depth"] = loop.queue_depth()
            out["live_slots"] = loop.live_slots()
        if self.prefix is not None:
            out["prefix_hits"] = int(r.counter(
                "fleet/prefix/hits").value(**labels))
            out["prefix_misses"] = int(r.counter(
                "fleet/prefix/misses").value(**labels))
            out["prefix_entries"] = len(self.prefix)
        for metric, hist in (("ttft_ms", "serving/generation/ttft_ms"),
                             ("token_ms", "serving/generation/token_ms")):
            samples = r.histogram(hist).samples(**labels)
            for k, v in percentile_summary(samples, (50, 99)).items():
                out[f"{metric}_{k}"] = v
        out["compile_count"] = self.compile_count(name)
        return out


class _KeyOnly:
    """A (name, version) stand-in with the Servable ``key`` shape, for
    compile-count lookups of non-current versions."""

    __slots__ = ("key",)

    def __init__(self, name: str, version: int):
        self.key = (name, version)
