"""Set-up from inside the program: its compile log
(`predictionio_tpu/telemetry/device.py::COMPILE_LOG`), one record
`(phase, fn, start, end, depth, cache, name)` for every trace, lowering,
backend compile and cache load JAX reports and for the process's first
seconds (`process.import`, `native.load`, `runtime.backend_init`), on
`time.perf_counter()`, the harness's clock.

Only records inside set-up, `[h.t0, h.setup_end]`, are read: the check's
own programs come later. Events nest (an outer trace holds every inner
jit's), so a quantity is the union of its records' intervals, never
their sum. `what` picks the quantity:

- `phase_seconds`: the union of the records of `phase`;
- `cache_misses`: `backend_compile` records marked `miss`: programs the
  backend compiled, no load from the persistent cache inside them (kept
  afterwards or not; JAX's own miss count leaves out the compiles it
  does not go on to store);
- `warmup_unspanned`: the wall of the span that ends set-up (`ends_setup`,
  the warm-up call) less the union of every record inside it, less the
  median wall of the window's calls: what a first call pays that no
  phase owns;
- `unspanned`: set-up's wall less the harness's set-up spans, less the
  union of the first-seconds records before the warm-up call (the
  harness keeps the seconds of its other spans and not where they lay;
  the drivers' `setup.data_s` holds numpy and no code of the program, so
  no such record lies in it).

The first read of a run says the five programs with the most trace +
lower seconds (and what was traced directly under each, by JAX's name
for it) and every record inside the window. A program without the
log (the commit before it) gives nothing.
"""

import statistics

from perf import spans, trace
from perf.harness import say

JIT_PHASES = ("trace", "lower", "backend_compile", "cache_load")
_said: set[int] = set()


def _log():
    try:
        from predictionio_tpu.telemetry import device
    except ImportError:
        return None
    return getattr(device, "COMPILE_LOG", None)


def union_s(records) -> float:
    return sum(e - s for s, e in trace.union([(r.start, r.end)
                                              for r in records]))


def warmup_interval(spec: dict, h) -> tuple[float, float]:
    return h.setup_end - h.setup_spans[spec["ends_setup"]], h.setup_end


def read(spec: dict, h):
    log = _log()
    if log is None:
        return None
    setup = log.records(h.t0, h.setup_end)
    if id(h) not in _said:
        _said.add(id(h))
        describe(log, setup, h)
    what = spec["what"]
    if what == "phase_seconds":
        return union_s(r for r in setup if r.phase == spec["phase"])
    if what == "cache_misses":
        return sum(r.cache == "miss" for r in setup)
    if what == "warmup_unspanned":
        lo, hi = warmup_interval(spec, h)
        steady = statistics.median(spans.seconds(c, "call")
                                   for c in h.recorder.calls)
        return (hi - lo) - union_s(log.records(lo, hi)) - steady
    if what == "unspanned":
        lo, _ = warmup_interval(spec, h)
        first_seconds = [r for r in log.records(h.t0, lo)
                         if r.phase not in JIT_PHASES]
        return ((h.setup_end - h.t0) - sum(h.setup_spans.values())
                - union_s(first_seconds))
    raise ValueError(f"compile_log: unknown quantity {what!r}")


def traced_inside(records) -> list[tuple[str, int, float]]:
    """(JAX's name, traces, seconds) of the jits and kernel bodies traced
    directly under a program's own trace, the dearest first."""
    traces = [r for r in records if r.phase == "trace"]
    if not traces:
        return []
    top = min(r.depth for r in traces)
    by_name: dict[str, list] = {}
    for r in traces:
        if r.depth == top + 1:
            by_name.setdefault(r.name or "?", []).append(r)
    return sorted(((name, len(rs), union_s(rs))
                   for name, rs in by_name.items()), key=lambda x: -x[2])


def describe(log, setup, h) -> None:
    by_fn: dict[str, list] = {}
    for r in setup:
        if r.phase in JIT_PHASES:
            by_fn.setdefault(r.fn, []).append(r)

    def host_s(records):  # Python on the host: no cache removes it
        return union_s(r for r in records if r.phase in ("trace", "lower"))

    top = sorted(by_fn.items(), key=lambda kv: -host_s(kv[1]))[:5]
    built = [r.cache for r in setup if r.phase == "backend_compile"]
    say(f"compile log: {len(setup)} records in set-up, {log.dropped} "
        f"dropped; {len(built)} programs built, {built.count('miss')} of "
        f"them compiled, {built.count('hit')} loaded from the cache; the "
        f"programs with the most trace + lower seconds:")
    for fn, records in top:
        say(f"  {fn}: " + ", ".join(
            f"{p} {union_s(r for r in records if r.phase == p):.3f}"
            for p in JIT_PHASES)
            + f", cache {[r.cache for r in records if r.cache]}")
        inside = traced_inside(records)
        if inside:
            say("    traced inside it: " + ", ".join(
                f"{name} x{n} {s:.3f}" for name, n, s in inside[:6]))
    for r in log.records(*h.window):
        say(f"  built inside the window: {r.phase} {r.fn} "
            f"{r.end - r.start:.3f} s at {r.start - h.window[0]:.3f} s")
