"""Cross-process telemetry aggregation: snapshot shipping + merging.

The PR 3 registry/tracer are strictly per-process, but the system spans
gang-launched multi-host training (``tools/launch``) and subprocess
fleet replicas (``fleet/replica.ProcessReplica``). This module is the
fleet-wide plane:

- a **snapshot shipper**: each process periodically appends an
  identity-stamped registry snapshot (the ``JsonlExporter`` wire
  format, histogram reservoirs included) to its own file in a shared
  directory. Arm with :func:`start_shipping` or
  ``BIGDL_TELEMETRY_SHIP_DIR=/path``; disarmed :func:`maybe_ship`
  costs ONE module-flag check (the ``telemetry.span`` discipline,
  micro-benchmark-asserted).
- an **aggregator** (:func:`aggregate_snapshots`) with defined
  semantics per instrument kind: counters sum, gauges keep per-source
  series (a ``host=``/``replica=`` label is injected), histograms
  merge exactly on count/sum and deterministically on reservoirs.
  Merged totals equal the sum of per-process snapshots to the digit
  (:func:`check_merge_invariant` asserts it; sums go through
  ``math.fsum`` over sorted values so the merge is order-independent
  and associative).
- a **trace merger** (:func:`merge_chrome_traces`): per-host Chrome
  trace files combine into one Perfetto timeline — each source becomes
  its own process track (pids remapped, a ``process_name`` metadata
  row added), thread/virtual-track tids are preserved verbatim, and
  flow-event ids are namespaced per source so PR 10 request flows
  never collide across hosts.

``tools/diagnose --fleet <dir>`` renders the merged
where-did-the-time-go report from a shipped-snapshot directory;
``telemetry.slo`` evaluates SLOs over the merged rows.
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import bigdl_tpu.telemetry as telemetry
from bigdl_tpu.telemetry.export import (JsonlExporter, process_identity,
                                        read_jsonl_with_identity)
from bigdl_tpu.telemetry.metrics import MetricsRegistry, _label_key
from bigdl_tpu.utils.profiling import percentile_summary

__all__ = ["start_shipping", "stop_shipping", "shipping", "maybe_ship",
           "read_snapshot_dir", "aggregate_snapshots",
           "check_merge_invariant", "detect_stragglers", "source_tag",
           "merge_chrome_traces", "merge_chrome_trace_files",
           "write_merged_trace", "register_agg_instruments",
           "MERGE_RESERVOIR"]

#: merged-reservoir cap per histogram series; below it the reservoir
#: merge is the exact sorted multiset union (associative and
#: order-independent), above it an even-stride decimation applies.
MERGE_RESERVOIR = 8192


def register_agg_instruments(r: MetricsRegistry) -> dict:
    """Get-or-create the ``telemetry/agg/*`` instruments in ``r``
    (covered by ``check --telemetry-audit``)."""
    return {
        "ship_lines": r.counter(
            "telemetry/agg/ship_lines",
            "snapshot lines appended by the periodic shipper"),
        "merges": r.counter(
            "telemetry/agg/merges", "aggregate_snapshots() calls"),
        "sources": r.counter(
            "telemetry/agg/sources",
            "per-process sources consumed by merges"),
    }


_INST = register_agg_instruments(telemetry.registry())

# the ONE flag the disarmed maybe_ship() fast path reads
_ARMED = False
_LOCK = threading.Lock()
# "last": when the last line was shipped on time.monotonic()'s clock,
# -inf before the first (that clock starts near 0 at boot, so 0.0 would
# gate the first ship of a long interval on a machine up for less)
_STATE: dict = {"exporter": None, "interval_s": 1.0,
                "last": float("-inf"), "path": None}


def shipping() -> bool:
    """Whether the periodic snapshot shipper is armed."""
    return _ARMED


def source_tag(identity: Optional[dict]) -> str:
    """Stable human tag for one source: the replica name when the
    identity carries one, else ``host<N>``, else the pid."""
    ident = identity or {}
    if ident.get("replica"):
        return str(ident["replica"])
    if ident.get("host") is not None:
        return f"host{ident['host']}"
    if ident.get("pid") is not None:
        return f"pid{ident['pid']}"
    return str(ident.get("file", "?"))


def start_shipping(directory: str, interval_s: float = 1.0,
                   registry: Optional[MetricsRegistry] = None,
                   identity: Optional[dict] = None) -> str:
    """Arm the shipper: :func:`maybe_ship` appends identity-stamped
    snapshots (reservoirs included) of ``registry`` (default: the
    process registry) to ``<directory>/snap-<tag>-<pid>.jsonl`` at most
    every ``interval_s`` seconds. Returns the snapshot file path.
    Also armed at import by ``BIGDL_TELEMETRY_SHIP_DIR=/path``
    (interval from ``BIGDL_TELEMETRY_SHIP_EVERY_S``)."""
    global _ARMED
    ident = identity if identity is not None else process_identity()
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(
        directory, f"snap-{source_tag(ident)}-{os.getpid()}.jsonl")
    with _LOCK:
        _STATE["exporter"] = JsonlExporter(
            registry if registry is not None else telemetry.registry(),
            path, identity=ident, include_samples=True)
        _STATE["interval_s"] = max(float(interval_s), 0.0)
        _STATE["last"] = float("-inf")
        _STATE["path"] = path
        _ARMED = True
    return path


def stop_shipping(final: bool = True) -> None:
    """Disarm the shipper; ``final=True`` ships one last snapshot
    first so the file carries the end-of-life totals."""
    global _ARMED
    if final and _ARMED:
        maybe_ship(force=True)
    _ARMED = False


def maybe_ship(force: bool = False) -> Optional[str]:
    """Ship one snapshot line if armed and the interval elapsed
    (``force=True`` skips the interval gate). Disarmed cost: ONE
    module-flag check — safe at optimizer-step cadence. Returns the
    snapshot file path when a line was written, else None."""
    if not _ARMED:
        return None
    return _ship(force)


def _ship(force: bool) -> Optional[str]:
    with _LOCK:
        exporter = _STATE["exporter"]
        if exporter is None:
            return None
        now = time.monotonic()
        if not force and now - _STATE["last"] < _STATE["interval_s"]:
            return None
        _STATE["last"] = now
    exporter.export()
    _INST["ship_lines"].inc()
    return exporter.path


def read_snapshot_dir(directory: str
                      ) -> List[Tuple[dict, List[dict]]]:
    """``[(identity, snapshot_rows)]`` from every ``*.jsonl`` file in
    ``directory`` (sorted by name, so merges are deterministic). The
    LAST record per file wins — counters are cumulative, so the final
    snapshot carries the totals. Torn trailing lines (a SIGKILLed
    shipper) are skipped; headerless files get a file-derived
    identity."""
    out: List[Tuple[dict, List[dict]]] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".jsonl"):
            continue
        path = os.path.join(directory, name)
        identity, records = read_jsonl_with_identity(path, tolerant=True)
        records = [r for r in records if isinstance(r.get("metrics"), list)]
        if not records:
            continue
        if identity is None:
            identity = {"file": name}
        out.append((identity, records[-1]["metrics"]))
    return out


def _fsum_sorted(values) -> float:
    return math.fsum(sorted(float(v) for v in values))


def _percentile_keys(series: dict) -> List[str]:
    return [k for k in series
            if k.startswith("p") and k[1:].isdigit()]


class _HistAcc:
    __slots__ = ("labels", "counts", "sums", "samples", "digests",
                 "exact")

    def __init__(self, labels):
        self.labels = labels
        self.counts: List[float] = []
        self.sums: List[float] = []
        self.samples: List[float] = []
        self.digests: List[Tuple[float, dict]] = []
        self.exact = True  # every source carried its reservoir


def _merge_histogram(acc: _HistAcc) -> dict:
    count = int(_fsum_sorted(acc.counts))
    total = _fsum_sorted(acc.sums)
    samples = sorted(acc.samples)
    if len(samples) > MERGE_RESERVOIR:
        stride = len(samples) / float(MERGE_RESERVOIR)
        samples = [samples[int(i * stride)]
                   for i in range(MERGE_RESERVOIR)]
    if acc.exact:
        pcts = percentile_summary(samples, (50, 90, 99))
    else:
        # a source shipped only its digest: fall back to the
        # count-weighted mean of per-source percentiles (deterministic,
        # documented as approximate in docs/telemetry.md)
        pcts = {}
        weight = sum(w for w, _ in acc.digests) or 1.0
        keys = sorted({k for _, d in acc.digests
                       for k in _percentile_keys(d)})
        for k in keys:
            pcts[k] = math.fsum(
                w * float(d.get(k, 0.0)) for w, d in acc.digests
            ) / weight
    out = {"labels": dict(acc.labels), "count": count, "sum": total}
    out.update(pcts)
    out["samples"] = samples
    return out


def aggregate_snapshots(sources: Sequence[Tuple[dict, List[dict]]]
                        ) -> List[dict]:
    """Merge per-process registry snapshots into one fleet snapshot
    (same row schema, so ``scalarize``/exporters/diagnose consume it
    unchanged).

    Per-kind semantics (the merge-algebra tests pin these):

    - **counters**: values sum per label set, exactly — ``fsum`` over
      sorted addends, so the total is independent of source order and
      equals the per-process sums to the digit.
    - **gauges**: a level has no cross-process sum; each source's
      series keeps its own identity via an injected ``replica=<name>``
      or ``host=<n>`` label (two files from one identity: the later
      file in sorted order wins).
    - **histograms**: count/sum merge exactly; reservoirs merge as the
      sorted multiset union (associative and order-independent up to
      :data:`MERGE_RESERVOIR`, then even-stride decimation) and
      percentiles are re-digested from the merged reservoir. A source
      without shipped samples degrades that series' percentiles to a
      count-weighted mean of per-source digests (count/sum stay
      exact).

    ``sources`` is ``[(identity, snapshot_rows)]`` as returned by
    :func:`read_snapshot_dir`.
    """
    _INST["merges"].inc()
    _INST["sources"].inc(len(sources))
    merged: Dict[str, dict] = {}
    for identity, rows in sources:
        ident = identity or {}
        if ident.get("replica"):
            skey, sval = "replica", str(ident["replica"])
        elif ident.get("host") is not None:
            skey, sval = "host", str(ident["host"])
        else:
            skey, sval = "host", source_tag(ident)
        for row in rows:
            name = row["name"]
            m = merged.get(name)
            if m is None:
                m = merged[name] = {
                    "name": name, "kind": row["kind"],
                    "description": row.get("description", ""),
                    "_series": {}}
            elif m["kind"] != row["kind"]:
                raise ValueError(
                    f"{name!r}: kind conflict across sources "
                    f"({m['kind']} vs {row['kind']})")
            acc = m["_series"]
            for s in row["series"]:
                labels = dict(s.get("labels") or {})
                if row["kind"] == "gauge":
                    labels[skey] = sval
                key = _label_key(labels)
                if row["kind"] == "counter":
                    acc.setdefault(key, {"labels": labels,
                                         "values": []})
                    acc[key]["values"].append(float(s["value"]))
                elif row["kind"] == "gauge":
                    acc[key] = {"labels": labels,
                                "value": float(s["value"])}
                else:
                    h = acc.get(key)
                    if h is None:
                        h = acc[key] = _HistAcc(labels)
                    h.counts.append(s["count"])
                    h.sums.append(s["sum"])
                    if "samples" in s:
                        h.samples.extend(float(v)
                                         for v in s["samples"])
                    else:
                        h.exact = False
                    h.digests.append(
                        (float(s["count"]),
                         {k: s[k] for k in _percentile_keys(s)}))
    out: List[dict] = []
    for name in sorted(merged):
        m = merged[name]
        series = []
        for key in sorted(m["_series"]):
            s = m["_series"][key]
            if m["kind"] == "counter":
                series.append({"labels": s["labels"],
                               "value": _fsum_sorted(s["values"])})
            elif m["kind"] == "gauge":
                series.append({"labels": s["labels"],
                               "value": s["value"]})
            else:
                series.append(_merge_histogram(s))
        out.append({"name": name, "kind": m["kind"],
                    "description": m["description"], "series": series})
    return out


def check_merge_invariant(sources: Sequence[Tuple[dict, List[dict]]],
                          merged: List[dict]) -> List[str]:
    """Violations of the merged-registry agreement (empty = clean):
    every counter total and histogram count/sum in ``merged`` must
    equal the per-process sums EXACTLY (same ``fsum``-over-sorted
    reduction on both sides, so float addition order cannot excuse a
    mismatch). Asserted by the merge-algebra tests and the
    ``diagnose --fleet`` invariant check."""
    bad: List[str] = []
    per_name: Dict[str, dict] = {}
    for _, rows in sources:
        for row in rows:
            e = per_name.setdefault(
                row["name"], {"kind": row["kind"], "values": [],
                              "counts": [], "sums": []})
            for s in row["series"]:
                if row["kind"] == "counter":
                    e["values"].append(s["value"])
                elif row["kind"] == "histogram":
                    e["counts"].append(s["count"])
                    e["sums"].append(s["sum"])
    for row in merged:
        e = per_name.get(row["name"])
        if e is None:
            bad.append(f"{row['name']}: present in merged snapshot "
                       "but in no source")
            continue
        if row["kind"] == "counter":
            want = _fsum_sorted(e["values"])
            got = _fsum_sorted(s["value"] for s in row["series"])
            if got != want:
                bad.append(f"{row['name']}: merged counter total "
                           f"{got!r} != per-process sum {want!r}")
        elif row["kind"] == "histogram":
            want_c = int(_fsum_sorted(e["counts"]))
            got_c = int(_fsum_sorted(s["count"]
                                     for s in row["series"]))
            if got_c != want_c:
                bad.append(f"{row['name']}: merged histogram count "
                           f"{got_c} != per-process sum {want_c}")
            want_s = _fsum_sorted(e["sums"])
            got_s = _fsum_sorted(s["sum"] for s in row["series"])
            if got_s != want_s:
                bad.append(f"{row['name']}: merged histogram sum "
                           f"{got_s!r} != per-process sum {want_s!r}")
    return bad


def detect_stragglers(sources: Sequence[Tuple[dict, List[dict]]],
                      metric: str = "train/optimizer/computing_time",
                      stat: str = "p50",
                      threshold: float = 1.5) -> dict:
    """Per-host skew on one histogram ``metric`` vs the fleet median.

    For each source, ``stat`` (``p50``/``p90``/``p99``) of ``metric``
    is computed — exactly from shipped reservoir samples when present,
    else as the count-weighted mean of per-series digests. A source
    whose value exceeds ``threshold`` x the fleet median is a
    straggler. Returns ``{"metric", "stat", "threshold", "per_source",
    "median", "stragglers"}`` where ``stragglers`` entries carry
    ``source``/``value``/``ratio``. Rendered by ``tools/diagnose
    --fleet`` (step time AND data wait) and fed to the host-kill chaos
    leg's SLO as a skew observation."""
    per_source: Dict[str, float] = {}
    for ident, rows in sources:
        tag = source_tag(ident)
        for row in rows:
            if row["name"] != metric or row["kind"] != "histogram":
                continue
            samples: List[float] = []
            digests: List[Tuple[float, float]] = []
            for s in row["series"]:
                if s.get("samples"):
                    samples.extend(float(v) for v in s["samples"])
                elif stat in s:
                    digests.append((float(s.get("count", 1)) or 1.0,
                                    float(s[stat])))
            if samples:
                q = int(stat[1:]) if stat.startswith("p") \
                    and stat[1:].isdigit() else 50
                val = percentile_summary(samples, (q,)).get(stat, 0.0)
            elif digests:
                weight = sum(c for c, _ in digests)
                val = math.fsum(c * v for c, v in digests) / weight
            else:
                continue
            per_source[tag] = float(val)
    values = sorted(per_source.values())
    if values:
        mid = len(values) // 2
        median = values[mid] if len(values) % 2 \
            else (values[mid - 1] + values[mid]) / 2.0
    else:
        median = 0.0
    stragglers = []
    for tag in sorted(per_source):
        val = per_source[tag]
        ratio = val / median if median > 0 \
            else (0.0 if val == 0.0 else float("inf"))
        if median > 0 and ratio > threshold:
            stragglers.append({"source": tag, "value": val,
                               "ratio": round(ratio, 3)})
    return {"metric": metric, "stat": stat, "threshold": threshold,
            "per_source": per_source, "median": median,
            "stragglers": stragglers}


# ------------------------------------------------------------ trace merge

def merge_chrome_traces(sources: Sequence[Tuple[object, List[dict]]]
                        ) -> List[dict]:
    """Combine per-host Chrome trace event lists into ONE Perfetto
    timeline. Each source ``(identity_or_label, events)`` becomes its
    own process track: pids are remapped to a deterministic per-source
    pid (1-based source index) with a ``process_name`` metadata row,
    tids — including the tracer's virtual-track tids — are preserved
    verbatim, and flow-event ``id``\\ s are prefixed with the source
    tag so request flows from different hosts never pair up."""
    merged: List[dict] = []
    seen_tags: Dict[str, int] = {}
    for idx, (identity, events) in enumerate(sources):
        tag = identity if isinstance(identity, str) \
            else source_tag(identity)
        if tag in seen_tags:
            seen_tags[tag] += 1
            tag = f"{tag}#{seen_tags[tag]}"
        else:
            seen_tags[tag] = 0
        pid = idx + 1
        merged.append({"ph": "M", "pid": pid, "tid": 0,
                       "name": "process_name", "args": {"name": tag}})
        for ev in events:
            ev = dict(ev)
            ev["pid"] = pid
            if "id" in ev:
                ev["id"] = f"{tag}:{ev['id']}"
            merged.append(ev)
    return merged


def merge_chrome_trace_files(paths: Sequence[str]) -> List[dict]:
    """Merge Chrome trace FILES (``{"traceEvents": [...]}`` or a bare
    event list; the tracer and flight bundles write the former) into
    one merged event list, labelling each source by file stem."""
    sources: List[Tuple[object, List[dict]]] = []
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"] if isinstance(doc, dict) else doc
        stem = os.path.splitext(os.path.basename(path))[0]
        sources.append((stem, events))
    return merge_chrome_traces(sources)


def write_merged_trace(path: str,
                       sources: Sequence[Tuple[object, List[dict]]]
                       ) -> int:
    """Write the merged timeline of ``sources`` (see
    :func:`merge_chrome_traces`) as Chrome trace-event JSON; returns
    the merged event count."""
    events = merge_chrome_traces(sources)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return len(events)


if os.environ.get("BIGDL_TELEMETRY_SHIP_DIR", "").strip():
    try:
        _every = float(
            os.environ.get("BIGDL_TELEMETRY_SHIP_EVERY_S", "") or 1.0)
    except ValueError:
        _every = 1.0
    start_shipping(os.environ["BIGDL_TELEMETRY_SHIP_DIR"],
                   interval_s=_every)
