"""From a profiler trace (``.xplane.pb``) to the few numbers the
per-layer readers use. One reduction for every cell and every PR.

What a TPU trace looks like (looked at by hand, PR 25): one plane per
chip named ``/device:TPU:<n>``; in it the line ``XLA Ops`` holds one
event per executed HLO operation (a ``while`` event spans the events of
its body, so per-name totals are SELF times) and ``XLA Modules`` one
event per program run. Host threads are lines of ``/host:CPU``; the
benchmark's ``jax.profiler.TraceAnnotation("bench/...")`` calls land
there on the same clock.

``reduce_trace`` returns a plain dict:

- ``window_s``   length of the ``bench/window`` annotation (the traced
                 part of the measured window);
- ``busy_s``     union of device-op intervals inside it, averaged over
                 the device planes;
- ``ops``        {op name: summed self seconds} (mean over planes);
- ``op_events``  {op name: event count} on the first device plane;
- ``modules``    {program name: [count, seconds]} on the first plane;
- ``top_ops``    [[operation, seconds], ...]: the ten that took most
                 self time, one name for the same operation of every
                 unrolled layer (``fusion.12``, ``fusion.13`` -> ``fusion``
                 with its result shape);
- ``gaps``       the longest idle gaps [[what the host was doing,
                 seconds], ...];
- ``annotations`` {name: [count, seconds]} of the ``bench/`` host spans;
- ``planes``     the device planes found.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench/window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[^\]]*\])")


def short_name(name: str) -> str:
    """An HLO operation's event name is its whole text; keep the
    instruction's name and first result shape."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def kind_name(name: str) -> str:
    """``short_name`` without the instruction's number, so that the
    same operation of every unrolled layer falls under one name."""
    return re.sub(r"^([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)[.\d]*( |$)", r"\1\2",
                  short_name(name))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            return ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(path)


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo, hi):
    """The uncovered stretches of ``[lo, hi]`` as ``[(start, end)]``."""
    gaps, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


def self_times(events):
    """``events``: [(start, end, name)]. A parent's self time is its
    duration less the part its children cover (a ``while`` spans its
    body's operations). Returns {name: seconds-units-of-input}."""
    out = defaultdict(float)
    stack = []                       # [end, name, start, child_cover]
    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, cover = stack.pop()
            out[name] += max(0.0, (end - start) - cover)
            if stack:
                stack[-1][3] += end - start
    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        close(s)
        stack.append([e, name, s, 0.0])
    close(float("inf"))
    return dict(out)


def _events(line, lo=None, hi=None):
    for ev in line.events:
        s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
        if lo is not None:
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
        yield s, e, ev.name


def _host_spans(profile):
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench/"):
                    spans.append((float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns),
                                  ev.name))
    return spans


def _device_planes(profile, allow_host_ops):
    planes = [p for p in profile.planes if _DEVICE_PLANE.match(p.name)]
    if planes or not allow_host_ops:
        return [(p.name, p, False) for p in planes]
    # CPU rehearsal only: XLA's CPU client writes its operations on
    # host threads, marked by an ``hlo_op`` stat
    return [(p.name, p, True) for p in profile.planes
            if p.name.startswith("/host:") and any(
                k == "hlo_op" for l in p.lines for ev in l.events
                for k, _ in ev.stats)]


def _op_lines(plane, host_ops):
    lines = list(plane.lines)
    if host_ops:
        return lines, []
    ops = [l for l in lines if l.name == "XLA Ops"]
    mods = [l for l in lines if l.name == "XLA Modules"]
    return (ops or lines), mods


def reduce_trace(path: str, *, allow_host_ops: bool = False,
                 top: int = 10) -> dict:
    profile = load(path)
    spans = _host_spans(profile)
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise RuntimeError(f"trace {path} holds no '{WINDOW}' span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    planes = _device_planes(profile, allow_host_ops)
    if not planes:
        raise RuntimeError(f"trace {path} holds no device plane")

    busy, op_tot = [], defaultdict(float)
    op_events, modules, first_iv = defaultdict(int), {}, None
    for idx, (_, plane, host_ops) in enumerate(planes):
        op_l, mod_l = _op_lines(plane, host_ops)
        evs = []
        for line in op_l:
            if host_ops:
                evs += [(max(float(ev.start_ns), lo),
                         min(float(ev.start_ns + ev.duration_ns), hi),
                         ev.name) for ev in line.events
                        if any(k == "hlo_op" for k, _ in ev.stats)
                        and ev.start_ns + ev.duration_ns > lo
                        and ev.start_ns < hi]
            else:
                evs += list(_events(line, lo, hi))
        iv = [(s, e) for s, e, _ in evs]
        busy.append(union_length(iv))
        for name, t in self_times(evs).items():
            op_tot[name] += t / len(planes)
        if idx == 0:
            first_iv = iv
            for _, _, name in evs:
                op_events[name] += 1
            for line in mod_l:
                for s, e, name in _events(line, lo, hi):
                    c = modules.setdefault(name, [0, 0.0])
                    c[0] += 1
                    c[1] += (e - s) * 1e-9
    if not any(busy):
        raise RuntimeError(
            f"trace {path}: no operation ran on the device in the window")

    # name each long gap by the bench/ span (other than the window
    # itself) that covers most of it
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW]
    gap_tot = defaultdict(float)
    for a, b in idle_gaps(first_iv, lo, hi):
        best, cover = "host:unannotated", 0.0
        for s, e, n in inner:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = n, c
        gap_tot[best] += (b - a) * 1e-9
    ann = {}
    for s, e, n in spans:
        c = ann.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    by_kind = defaultdict(float)
    for k, v in op_tot.items():
        by_kind[kind_name(k)] += v * 1e-9
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "ops": {k: v * 1e-9 for k, v in op_tot.items()},
        "op_events": dict(op_events),
        "modules": modules,
        "gaps": rank(gap_tot),
        "top_ops": rank(by_kind),
        "annotations": ann,
        "planes": [n for n, _, _ in planes],
    }


def describe(path: str, limit: int = 40) -> str:
    """The by-hand look: planes, lines, and the commonest event names."""
    profile, out = load(path), []
    for plane in profile.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            names, stats = defaultdict(lambda: [0, 0.0]), {}
            for ev in line.events:
                c = names[ev.name]
                c[0] += 1
                c[1] += ev.duration_ns
                if "custom-call" in ev.name and ev.name not in stats:
                    stats[ev.name] = {k: str(v)[:300] for k, v in ev.stats}
            out.append(f"  LINE {line.name}: {sum(c[0] for c in names.values())} events")
            for n, (k, d) in sorted(names.items(),
                                    key=lambda kv: -kv[1][1])[:limit]:
                out.append(f"    {k:7d} x {d / 1e6:10.3f} ms  {n[:150]}")
                if n in stats:       # how a kernel can be told apart
                    out.append(f"            {n[:600]}")
                    out.append(f"            stats: {stats[n]}")
    return "\n".join(out)
