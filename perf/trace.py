"""From a profiler trace to numbers: the benchmark's one reduction.

`load` reads the `.xplane.pb` that `jax.profiler` wrote (with nothing but
JAX) into a plain dict of event lists, `[name, start_ns, duration_ns]`
each:

    {"ops":     {device plane: [...]},   one event per executed XLA op,
                                         named as `short_name` says
     "modules": {device plane: [...]},   one event per executed program
     "host":    [...]}                   the benchmark's `perf:*` spans

Every function below works on that dict, so the tests check them on a
small recorded trace kept beside them as JSON (`read`; the file is
`json.dump` of what `load` returned, cut to a slice).

Busy time is the union of the op intervals of one device; where several
devices were used, busy and idle are averaged over them.
"""

from __future__ import annotations

import glob
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "perf:"
# an op's name in the trace is its whole HLO instruction
_HLO = re.compile(r"^(%?[\w.\-]+) = (\(|[a-z0-9]+\[[0-9,]*\])")
# control flow: their events span the ops they run, which have their own
_CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?( |$)")


def short_name(hlo: str) -> str:
    """`%closed_call.325 f32[31296,64] tpu_custom_call` from the HLO text
    the trace carries as an op's name: the instruction's name, the shape
    it produces (`tuple` for several) and, for a custom call, its
    target."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:120]
    shape = "tuple" if m.group(2) == "(" else m.group(2)
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    return " ".join([m.group(1), shape] + ([target.group(1)] if target else []))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(xplane_path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    out: dict = {"ops": {}, "modules": {}, "host": []}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out[key][plane.name] = [
                    [short_name(e.name), float(e.start_ns),
                     float(e.duration_ns)]
                    for e in line.events]
            elif not device:
                out["host"].extend(
                    [e.name[len(HOST_PREFIX):], float(e.start_ns),
                     float(e.duration_ns)]
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    out["ops"] = {p: ev for p, ev in out["ops"].items() if ev}
    return out


def read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) pairs."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_intervals(events, within=None) -> list[tuple[float, float]]:
    """Union of the events' intervals, in ns; clipped to the (start, end)
    pairs of `within` when given."""
    busy = union((s, s + d) for n, s, d in events
                 if not _CONTAINER.match(n))
    if within is None:
        return busy
    out = []
    for lo, hi in union(within):
        out.extend(_clip(busy, lo, hi))
    return out


def window_of(trace: dict, span: str = "window") -> tuple[float, float]:
    """(start, end) in ns of the host span that brackets the measured
    window; without one, the extent of the device events."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == span]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ev = [e for evs in trace["ops"].values() for e in evs]
    return min(s for _, s, _ in ev), max(s + d for _, s, d in ev)


def busy_seconds(trace: dict, within=None) -> float:
    """Seconds in which an op ran on the device, averaged over devices."""
    if not trace["ops"]:
        return 0.0
    if within is None:
        within = [window_of(trace)]
    per_device = [_length(busy_intervals(ev, within))
                  for ev in trace["ops"].values()]
    return sum(per_device) / len(per_device) / 1e9


def idle_share(trace: dict, within=None) -> float:
    """1 - busy / length of `within` (default: the window), in [0, 1]."""
    if within is None:
        within = [window_of(trace)]
    total = _length(union(within)) / 1e9
    return 1.0 - busy_seconds(trace, within) / total


def module_intervals(trace: dict, pattern: str):
    """(start, end) of every executed program whose name matches, on the
    first device that has any."""
    rx = re.compile(pattern)
    for events in trace["modules"].values():
        hits = [(s, s + d) for n, s, d in events if rx.search(n)]
        if hits:
            return hits
    return []


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """[name, seconds] of the ops with most summed device time inside the
    window, averaged over devices."""
    lo, hi = window_of(trace)
    sums: dict[str, float] = {}
    for events in trace["ops"].values():
        for name, s, d in events:
            if lo <= s < hi and not _CONTAINER.match(name):
                sums[name] = sums.get(name, 0.0) + d
    k = max(len(trace["ops"]), 1)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / k / 1e9] for name, t in ranked]


def idle_gaps_by_span(trace: dict, n: int = 10) -> list[list]:
    """[host span, seconds]: the device's idle time inside the window,
    booked to the innermost host span that covered it (the shortest span
    around each stretch), `(none)` where no span did. First device."""
    if not trace["ops"]:
        return []
    lo, hi = window_of(trace)
    busy = busy_intervals(next(iter(trace["ops"].values())), [(lo, hi)])
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    spans = sorted(((d, s, s + d, name) for name, s, d in trace["host"]
                    if name != "window"))
    cuts = sorted({lo, hi} | {t for _, s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    sums: dict[str, float] = {}
    for g_lo, g_hi in gaps:
        edges = [g_lo] + [t for t in cuts if g_lo < t < g_hi] + [g_hi]
        for a, b in zip(edges, edges[1:]):
            mid = 0.5 * (a + b)
            owner = next((name for _, s, e, name in spans if s <= mid < e),
                         "(none)")
            sums[owner] = sums.get(owner, 0.0) + (b - a)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9] for name, t in ranked]
