"""Bench regression sentinel: gate CI on "no silent perf regression".

    python -m bigdl_tpu.tools.regress                 # BENCH_r*.json in .
        [trajectory files...]                         # explicit points
        --candidate FILE                              # fresh bench/perf
                                                      # JSON to judge
        --tolerance 0.10                              # fractional band
        --window 5 --min-points 2
        --json

Five BENCH_r*.json points make throughput a *regression surface*:
without a gate, a 20% drop ships silently as long as the number is
still positive. The sentinel parses the banked trajectory (the driver's
``{"parsed": {...}}`` wrappers, raw ``bench.py`` lines, or
``tools/perf`` JSON tails all work), fits a **rolling baseline** per
metric (median of the last ``--window`` points), and judges the
candidate (``--candidate``, or the trajectory's last point) against a
per-metric tolerance band:

- **higher-is-better** metrics (``*_per_sec*``, ``*_per_chip``,
  ``mfu``/``achieved_tfs``, ``*_speedup``, ``*efficiency*``,
  ``*fraction*``, ``vs_baseline``, ``value``) regress when they fall
  below ``baseline * (1 - tolerance)``;
- **lower-is-better** metrics (``*_ms``/``*_ms_p*`` latencies,
  ``*bytes*``, ``*compile*``, ``*delta*``) regress when they rise above
  ``baseline * (1 + tolerance)``;
- every other key (units, config echo like ``steps_per_sync``, request
  counts) is ignored — the checked key set is exactly the two lists
  above, so adding a config knob to bench.py can never trip the gate.

Metrics with fewer than ``--min-points`` baseline points are reported
``new`` and skipped — a fresh bench row never fails the build the day
it lands.

**Schema:** ``bench.py`` stamps ``schema_version`` (currently 2) into
its JSON line; points without one are accepted as legacy (version 1).
A candidate or trajectory point carrying an *unknown* version is
refused with exit 2 — the sentinel must not guess at keys a future
bench renamed.

Exit codes: 0 no regression, 1 regression(s), 2 usage/schema error.
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
from typing import Dict, List, Optional, Tuple

__all__ = ["KNOWN_SCHEMA_VERSIONS", "extract_metrics", "classify_key",
           "judge", "main"]

#: bench.py schema versions this sentinel understands; version 1 is
#: the implicit pre-schema_version format (records without the key)
KNOWN_SCHEMA_VERSIONS = (1, 2)

_HIGHER_MARKS = ("per_sec", "per_chip", "mfu", "achieved_tfs",
                 "speedup", "efficiency", "fraction")
_HIGHER_EXACT = ("value", "vs_baseline")
_LOWER_MARKS = ("_ms", "bytes", "compile", "delta")


def classify_key(key: str) -> Optional[str]:
    """``"higher"`` / ``"lower"`` / None (ignored) for one metric key —
    the documented stable key-direction rule (module docstring).
    Lower-is-better marks win ties: ``*_bytes_per_chip`` is a memory
    footprint, not a throughput."""
    k = key.lower()
    if any(m in k for m in _LOWER_MARKS) or k.endswith("_s"):
        return "lower"
    if k in _HIGHER_EXACT or any(m in k for m in _HIGHER_MARKS):
        return "higher"
    return None


def _schema_version(metrics: Dict) -> int:
    v = metrics.get("schema_version", 1)
    try:
        return int(v)
    except (TypeError, ValueError):
        return -1


def extract_metrics(record: Dict, source: str = "?") -> Dict[str, float]:
    """Numeric metrics from one trajectory/candidate record: unwraps
    the driver's ``{"parsed": {...}}`` BENCH wrapper, accepts raw
    bench lines and perf tails directly; refuses unknown
    ``schema_version`` with :class:`SystemExit` (code 2)."""
    metrics = record.get("parsed") if isinstance(record.get("parsed"),
                                                 dict) else record
    version = _schema_version(metrics)
    if version not in KNOWN_SCHEMA_VERSIONS:
        print(f"{source}: unknown schema_version "
              f"{metrics.get('schema_version')!r} (this sentinel knows "
              f"{list(KNOWN_SCHEMA_VERSIONS)}); update "
              "bigdl_tpu/tools/regress.py before trusting its verdict",
              file=sys.stderr)
        raise SystemExit(2)
    return {k: float(v) for k, v in metrics.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
            and k != "schema_version"}


def _load(path: str) -> Dict:
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError as e:
        print(f"cannot read {path}: {e}", file=sys.stderr)
        raise SystemExit(2)
    # a file may hold one JSON object or JSONL (last line wins: the
    # freshest bench append)
    try:
        return json.loads(text)
    except ValueError:
        lines = [ln for ln in text.splitlines() if ln.strip()]
        try:
            return json.loads(lines[-1])
        except (ValueError, IndexError):
            print(f"{path}: not JSON", file=sys.stderr)
            raise SystemExit(2)


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def judge(trajectory: List[Dict[str, float]],
          candidate: Dict[str, float], tolerance: float,
          window: int, min_points: int) -> Tuple[List[dict], bool]:
    """Judge ``candidate`` against the rolling per-metric baseline of
    ``trajectory``; returns (per-metric report rows, any_regression).
    Rows carry ``status``: ``ok`` / ``REGRESSION`` / ``new`` (too few
    baseline points) / ``ignored`` (key outside the direction rules)."""
    rows: List[dict] = []
    regressed = False
    for key in sorted(candidate):
        direction = classify_key(key)
        value = candidate[key]
        if direction is None:
            rows.append({"metric": key, "status": "ignored",
                         "value": value})
            continue
        history = [p[key] for p in trajectory if key in p]
        if len(history) < min_points:
            rows.append({"metric": key, "status": "new", "value": value,
                         "points": len(history)})
            continue
        baseline = _median(history[-window:])
        if direction == "higher":
            bound = baseline * (1.0 - tolerance)
            bad = value < bound
        else:
            bound = baseline * (1.0 + tolerance)
            bad = value > bound
        regressed = regressed or bad
        rows.append({"metric": key, "status":
                     "REGRESSION" if bad else "ok", "value": value,
                     "baseline": baseline, "bound": bound,
                     "direction": direction,
                     "points": len(history[-window:])})
    return rows, regressed


def main(argv=None) -> int:
    """CLI entry point (module docstring has flags and exit codes)."""
    ap = argparse.ArgumentParser(
        prog="python -m bigdl_tpu.tools.regress", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("trajectory", nargs="*",
                    help="trajectory point files (default: BENCH_r*.json"
                         " in the working directory, sorted)")
    ap.add_argument("--candidate", default=None,
                    help="the fresh bench/perf JSON to judge; default: "
                         "the trajectory's last point")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="fractional tolerance band (default 0.10)")
    ap.add_argument("--window", type=int, default=5,
                    help="rolling-baseline width in points (default 5)")
    ap.add_argument("--min-points", type=int, default=2,
                    help="baseline points a metric needs before it can "
                         "regress (default 2)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    paths = args.trajectory or sorted(glob.glob("BENCH_r*.json"))
    if not paths:
        print("no trajectory files (pass paths or run where "
              "BENCH_r*.json live)", file=sys.stderr)
        return 2
    points = [extract_metrics(_load(p), p) for p in paths]
    if args.candidate:
        candidate = extract_metrics(_load(args.candidate),
                                    args.candidate)
        baseline_points = points
    else:
        if len(points) < 2:
            print("need >= 2 trajectory points when no --candidate "
                  "is given", file=sys.stderr)
            return 2
        candidate = points[-1]
        baseline_points = points[:-1]

    rows, regressed = judge(baseline_points, candidate, args.tolerance,
                            args.window, args.min_points)
    if args.json:
        print(json.dumps({"tolerance": args.tolerance,
                          "points": len(baseline_points),
                          "regressed": regressed, "metrics": rows},
                         indent=2))
    else:
        for r in rows:
            if r["status"] == "ignored":
                continue
            line = f"{r['status']:<10s} {r['metric']}: {r['value']:g}"
            if "baseline" in r:
                arrow = ">=" if r["direction"] == "higher" else "<="
                line += (f" (baseline {r['baseline']:g} over "
                         f"{r['points']} pts, needs {arrow} "
                         f"{r['bound']:g})")
            print(line)
        checked = sum(1 for r in rows if r["status"] in ("ok",
                                                         "REGRESSION"))
        bad = sum(1 for r in rows if r["status"] == "REGRESSION")
        print(f"regression sentinel: {checked - bad}/{checked} tracked "
              f"metrics within {100 * args.tolerance:.0f}% of baseline")
    return 1 if regressed else 0


if __name__ == "__main__":
    raise SystemExit(main())
