"""Device-plane observability: per-dispatch device time, the jit-cache
inventory with retrace blame, and device-memory history.

Everything host-side is already deep (19 Hz profiler, lineage, fleet
metrics) but the accelerator was dark: `metered_jit` only counted
compiles, `/debug/profile/device.json` was a point-in-time buffer dump,
and nothing said how many device-seconds a route or bucket tier
consumed. Three instruments fix that, all fed by the single
`record_dispatch()` hook that `utils/profiling.metered_jit` calls on
every dispatch:

- `DeviceClock`: per-dispatch device time via a block-until-ready delta
  measured on a drain thread — the caller never sync-stalls; jax-less or
  CPU-backend processes fall back to dispatch wall time labelled
  ``device="cpu"``. Lands in `device_seconds_total{route,fn,tier,device}`
  (routes/tiers come from the `attribution()` context the dispatch sites
  open) plus a rolling 60 s `device_utilization_ratio` gauge. Internally
  time is integer microseconds so the supervisor's fleet merge is
  sum-exact (`total_us == sum(workers.values())`, no float drift).
- The jit-cache inventory (`GET /debug/jit.json`): per-fn compiled
  signatures (abstract shapes/dtypes, compile seconds, dispatch counts,
  last-used) with **retrace blame** — on recompile the new signature is
  diffed against the nearest cached one and the changed argument /
  dimension is named. The runtime twin of pio-lint's static
  `jit-shape-discipline` rule.
- A device-memory sampler feeding `telemetry/history.py` with
  `device_mem_*` high-water gauges plus the headroom burn-rate alert in
  `telemetry/alerts.py`.

Beside them, the compile log (`COMPILE_LOG`): what a program's first call
pays before it runs, phase by phase, from JAX's own monitoring events
(`listen()`), and the process's first seconds (imports, the native library,
the backend's start) as spans of the same list.

Lazy-import discipline: this module never imports jax at module level
and only touches it when ``"jax" in sys.modules`` — event servers and
gate drills stay jax-free.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import queue
import sys
import threading
import time
from typing import (Any, Deque, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple)

from predictionio_tpu.telemetry import tenant
from predictionio_tpu.telemetry.registry import REGISTRY, capped_label

log = logging.getLogger(__name__)

UNTRACKED_ROUTE = "(untracked)"

# Inventory bounds: per-fn signature map is LRU-capped so a shape-unstable
# function cannot grow the payload forever (the eviction count is itself
# a retrace-storm signal); fn labels are capped upstream by capped_label.
MAX_SIGNATURES_PER_FN = 64
MAX_RETRACE_RECORDS = 16
UTILIZATION_WINDOW_S = 60.0

DEVICE_SECONDS = REGISTRY.counter(
    "device_seconds_total",
    "Device-execution seconds per route/fn/bucket-tier, measured as the "
    "block-until-ready delta on the device clock's drain thread "
    "(device=\"cpu\" marks the dispatch-wall-time fallback)",
    labelnames=("route", "fn", "tier", "device"))
DEVICE_DISPATCHES = REGISTRY.counter(
    "device_dispatches_total",
    "Jitted dispatches observed by the device clock, same labels as "
    "device_seconds_total",
    labelnames=("route", "fn", "tier", "device"))
DEVICE_UTILIZATION = REGISTRY.gauge(
    "device_utilization_ratio",
    "Fraction of the last 60 s wall window the device spent executing "
    "dispatched programs (from the device clock)",
    labelnames=("device",))
DEVICE_CLOCK_DROPPED = REGISTRY.counter(
    "device_clock_dropped_total",
    "Dispatches whose ready-delta measurement was skipped because the "
    "device clock's drain queue was full (their wall time was recorded "
    "on the device=\"cpu\" fallback instead)")
DEVICE_CLOCK_QUEUE = REGISTRY.gauge(
    "device_clock_queue_depth",
    "Dispatches currently waiting on the device clock's drain thread")
JIT_RETRACES = REGISTRY.counter(
    "jit_retraces_total",
    "Recompiles of an already-warm jitted function (compile count beyond "
    "its first signature) — each one carries retrace blame in "
    "/debug/jit.json naming the argument/dimension that changed",
    labelnames=("fn",))

JIT_PHASE_SECONDS = REGISTRY.counter(
    "jit_phase_seconds_total",
    "Host seconds spent building jitted programs, by phase (trace, lower, "
    "backend_compile, cache_load; from JAX's monitoring events). Nested "
    "events of one phase count once; cache_load lies inside "
    "backend_compile",
    labelnames=("fn", "phase"))
JIT_CACHE_REQUESTS = REGISTRY.counter(
    "jit_cache_requests_total",
    "Programs the backend was asked for, by what JAX's persistent "
    "compilation cache did: hit (an executable was loaded from it) or "
    "miss (the backend compiled: not held, too small to be kept, or no "
    "cache)",
    labelnames=("result",))

DEVICE_MEM_LIVE = REGISTRY.gauge(
    "device_mem_live_bytes",
    "Live jax buffer bytes per device (device-memory sampler)",
    labelnames=("device",))
DEVICE_MEM_HIGH_WATER = REGISTRY.gauge(
    "device_mem_high_water_bytes",
    "High-water mark of live jax buffer bytes per device since process "
    "start (device-memory sampler)",
    labelnames=("device",))
DEVICE_MEM_LIMIT = REGISTRY.gauge(
    "device_mem_limit_bytes",
    "Device memory capacity as reported by memory_stats (absent on "
    "backends that do not report a limit)",
    labelnames=("device",))
DEVICE_MEM_HEADROOM = REGISTRY.gauge(
    "device_mem_headroom_ratio",
    "(limit - live) / limit per device — 0 means HBM exhausted; the "
    "device-mem-headroom-burn alert fires on a fast-shrinking ratio",
    labelnames=("device",))


def _env_flag(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default) not in ("0", "false", "off", "no")


# -- dispatch-site attribution -------------------------------------------------

_TLS = threading.local()
# `.fn`: the `metered_jit` label whose call is in flight on this thread
# (the wrapper stores it round the call; the compile log reads it)
IN_FLIGHT = _TLS


class Attribution:
    """Open at a dispatch site; every metered_jit dispatch inside the
    block inherits the route/tier labels, and the site can read back the
    host-vs-device split (`t_first_dispatch`, `jit_wall_s`) to record it
    as nested spans. A plain __enter__/__exit__ class, not a generator
    contextmanager: this sits on the batch-of-1 serving hot path, where
    the generator machinery alone is a measurable share of the ≤5%
    per-query overhead bar."""

    __slots__ = ("route", "tier", "t_enter", "t_first_dispatch",
                 "jit_wall_s", "dispatches", "_prev")

    def __init__(self, route: str, tier: str = ""):
        self.route = route
        self.tier = tier
        self.t_enter = time.perf_counter()
        self.t_first_dispatch: Optional[float] = None
        self.jit_wall_s = 0.0
        self.dispatches = 0

    def __enter__(self) -> "Attribution":
        self._prev = getattr(_TLS, "att", None)
        _TLS.att = self
        return self

    def __exit__(self, *exc) -> None:
        _TLS.att = self._prev


def attribution(route: str, tier: str = "") -> Attribution:
    return Attribution(route, tier=str(tier))


def current_attribution() -> Optional[Attribution]:
    return getattr(_TLS, "att", None)


# -- the compile log -----------------------------------------------------------

JIT_PHASES = ("trace", "lower", "backend_compile", "cache_load")
# The log's cap: one big program is a few thousand records (every inner
# jit traced under it is one); past the cap the oldest go, counted.
MAX_COMPILE_RECORDS = 16384
# A record counts as lying inside a later one when it starts no earlier
# than this before it: both starts are `arrival - duration`, and an
# event's arrival trails its end by the listeners' own microseconds.
_NEST_SLACK_S = 1e-4

_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class CompileRecord(NamedTuple):
    """One interval of the compile log on `time.perf_counter()`'s clock.
    `depth` counts the records of the same thread it lies inside;
    `cache` is set on a `backend_compile` record: `hit` where the
    persistent cache gave the executable (a `cache_load` record lies
    inside), `miss` where the backend compiled it (JAX keeps its own miss
    count for compiles it goes on to store only, so that is not read);
    `name` is JAX's own name for the function of a compile event (under
    a label in flight, the jit or kernel body that was traced)."""
    phase: str
    fn: str
    start: float
    end: float
    depth: int
    cache: Optional[str]
    name: Optional[str]


class CompileLog:
    """What building programs cost this process, as intervals: JAX's
    compile events (`phase` one of `JIT_PHASES`, `fn` the `metered_jit`
    label in flight, else JAX's name for the function) and the process's
    first seconds (`phase` = the span's name: `process.import`,
    `native.load`, `runtime.backend_init`).

    Events nest: an outer trace holds the traces of every jit under it,
    a `backend_compile` its `cache_load`, and each arrives as it *ends*.
    So a sum over records is wrong and every total is a union of
    intervals (`phase_seconds`). Bounded as `Timeline.MAX_SPANS` is, but
    from the other end: past `cap` the oldest record goes and is counted,
    so a long-lived server still holds its latest retrace. In memory
    only; read by `/debug/jit.json`'s inventory, the registry, the train
    timeline and the benchmark's reader."""

    def __init__(self, cap: int = MAX_COMPILE_RECORDS):
        self.cap = cap
        self.dropped = 0
        self._lock = threading.Lock()
        # a CompileRecord's fields and the thread, in order of `end`
        # within a thread
        self._records: Deque[list] = collections.deque()

    def add(self, phase: str, fn: str, start: float, end: float,
            cache: Optional[str] = None,
            name: Optional[str] = None) -> float:
        """Keep one record. Returns the seconds of it that no record of
        the same phase still held covers: what a per-phase counter may
        add without counting a nested event twice."""
        thread = threading.get_ident()
        own = end - start
        with self._lock:
            uncovered_to = end
            for rec in reversed(self._records):
                if rec[7] != thread:
                    continue
                if rec[2] < start - _NEST_SLACK_S:
                    break   # it, and all of this thread before it, is past
                rec[4] += 1
                if rec[0] == phase and rec[3] <= uncovered_to:
                    own -= rec[3] - rec[2]
                    uncovered_to = rec[2]
            self._records.append(
                [phase, fn, start, end, 0, cache, name, thread])
            while len(self._records) > self.cap:
                self._records.popleft()
                self.dropped += 1
        return max(0.0, own)

    def records(self, t_lo: float = float("-inf"),
                t_hi: float = float("inf"),
                thread: Optional[int] = None) -> List[CompileRecord]:
        """The records that lie inside [t_lo, t_hi], of one thread or all:
        what was built in a window, by name and phase."""
        with self._lock:
            return [CompileRecord(*rec[:7]) for rec in self._records
                    if rec[2] >= t_lo and rec[3] <= t_hi
                    and (thread is None or rec[7] == thread)]

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self.dropped = 0


def phase_seconds(records: Iterable[CompileRecord]) -> Dict[str, float]:
    """Seconds by phase: the union of each phase's intervals."""
    by_phase: Dict[str, List[Tuple[float, float]]] = {}
    for r in records:
        by_phase.setdefault(r.phase, []).append((r.start, r.end))
    out = {}
    for phase, spans_ in by_phase.items():
        total, reach = 0.0, float("-inf")
        for lo, hi in sorted(spans_):
            if hi > reach:
                total += hi - max(lo, reach)
                reach = hi
        out[phase] = total
    return out


COMPILE_LOG = CompileLog()
# the package's own import, stamped by its `__init__`'s first and last line
COMPILE_LOG.add("process.import", "predictionio_tpu",
                *sys.modules["predictionio_tpu"].IMPORT_SPAN)


@contextlib.contextmanager
def first_seconds(phase: str, what: str):
    """A span of the process's first seconds in the compile log."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        COMPILE_LOG.add(phase, what, t0, time.perf_counter())


_listening = False
_listen_lock = threading.Lock()


def listen() -> None:
    """Hear JAX's compile and compilation-cache events from here on; once
    a process, before its first program is built. Called where the
    program brings jax in (`import_jax`, `pio train`'s entry), where a
    workflow context is made in a process that holds jax already (a
    library's caller imported it) and by `metered_jit` as it wraps a
    function; never from inside a module's import, and the lock is held
    for the flag alone."""
    global _listening
    if _listening:
        return
    import jax

    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


def import_jax():
    """`import jax` for a process entry: the import's seconds go into the
    compile log as `process.import` where this is the process's first,
    and the listeners are on before anything is built."""
    if "jax" not in sys.modules:
        with first_seconds("process.import", "jax"):
            import jax  # noqa: F401
    listen()
    return sys.modules["jax"]


def _on_duration(event: str, duration: float, fun_name: Optional[str] = None,
                 **_) -> None:
    phase = _DURATION_EVENTS.get(event)
    if phase is None:
        return
    end = time.perf_counter()
    tls = _TLS
    if fun_name is None:   # the cache's events name no function
        fun_name = getattr(tls, "jax_fn", None)
    else:
        tls.jax_fn = fun_name
    if fun_name and fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]   # a module's name; a trace gives the bare
    fn = getattr(tls, "fn", None) or fun_name or "(unnamed)"
    cache = None
    if phase == "cache_load":
        tls.cache_loaded = True   # for the backend_compile event round it
    elif phase == "backend_compile":
        cache = "hit" if getattr(tls, "cache_loaded", False) else "miss"
        tls.cache_loaded = False
        JIT_CACHE_REQUESTS.labels(result=cache).inc()
    own = COMPILE_LOG.add(phase, fn, end - float(duration), end, cache,
                          fun_name)
    # a label in flight is `metered_jit`'s, capped already; JAX's names
    # (every eager op has one) get a group of their own, so they cannot
    # use up the labels' room
    label = getattr(tls, "fn", None) or capped_label("jit_eager_fn", fn)
    JIT_PHASE_SECONDS.labels(fn=label, phase=phase).inc(own)


# -- abstract signatures -------------------------------------------------------


def _spec(x: Any) -> str:
    """Abstract spec of one dispatch argument. Arrays abstract to
    dtype[shape] (value-independent, like a jit trace); Python scalars
    keep their value — they are usually static args, where the value IS
    the retrace trigger worth naming."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            dims = ",".join(str(int(d)) for d in shape)
        except (TypeError, ValueError):
            dims = ",".join(str(d) for d in shape)
        return f"{dtype}[{dims}]"
    if isinstance(x, bool):
        return f"bool({x})"
    if isinstance(x, int):
        return f"int({x})"
    if isinstance(x, float):
        return f"float({x:g})"
    if isinstance(x, str):
        return f"str({x[:32]})"
    if x is None:
        return "None"
    if isinstance(x, (list, tuple)):
        return f"{type(x).__name__}(n={len(x)})"
    return type(x).__name__


def signature_of(args: Sequence[Any],
                 kwargs: Optional[Dict[str, Any]]) -> Tuple[str, ...]:
    parts = [f"arg{i}:{_spec(a)}" for i, a in enumerate(args)]
    if kwargs:
        parts.extend(f"{k}={_spec(kwargs[k])}" for k in sorted(kwargs))
    return tuple(parts)


def _split_spec(part: str) -> Tuple[str, str]:
    """'arg0:f32[8]' / 'out_rows=int(32)' → (name, spec)."""
    colon, eq = part.find(":"), part.find("=")
    if colon != -1 and (eq == -1 or colon < eq):
        return part[:colon], part[colon + 1:]
    if eq != -1:
        return part[:eq], part[eq + 1:]
    return part, part


def _dims_of(spec: str) -> Optional[Tuple[str, List[str]]]:
    """dtype[d0,d1] → (dtype, [d0, d1]); None for non-array specs."""
    if not spec.endswith("]") or "[" not in spec:
        return None
    dtype, _, dims = spec[:-1].partition("[")
    return dtype, dims.split(",") if dims else []

def diff_signatures(old: Tuple[str, ...],
                    new: Tuple[str, ...]) -> List[str]:
    """Human-readable per-argument differences; dimension-level when both
    sides are arrays of the same dtype/rank ('arg0 dim0: 8→32')."""
    changed: List[str] = []
    for i in range(max(len(old), len(new))):
        o = old[i] if i < len(old) else None
        n = new[i] if i < len(new) else None
        if o == n:
            continue
        if o is None or n is None:
            changed.append(f"{(n or o)} {'added' if o is None else 'removed'}")
            continue
        oname, ospec = _split_spec(o)
        nname, nspec = _split_spec(n)
        label = nname if oname == nname else f"{oname}/{nname}"
        od, nd = _dims_of(ospec), _dims_of(nspec)
        if (od and nd and od[0] == nd[0] and oname == nname
                and len(od[1]) == len(nd[1])):
            for k, (a, b) in enumerate(zip(od[1], nd[1])):
                if a != b:
                    changed.append(f"{label} dim{k}: {a}→{b}")
            continue
        changed.append(f"{label}: {ospec}→{nspec}")
    return changed


# -- jit-cache inventory -------------------------------------------------------


# The compile log's part of an inventory entry: seconds by phase (unions,
# `cache_load` inside `backend_compile`) and the persistent cache's answers.
_BUILD_FIELDS = tuple(f"{phase}_seconds" for phase in JIT_PHASES) + (
    "cache_hits", "cache_misses")


def _build_fields(built: Sequence[CompileRecord]) -> Dict[str, float]:
    seconds = phase_seconds(built)
    out = {f"{phase}_seconds": seconds.get(phase, 0.0)
           for phase in JIT_PHASES}
    out["cache_hits"] = sum(r.cache == "hit" for r in built)
    out["cache_misses"] = sum(r.cache == "miss" for r in built)
    return out


class _FnInventory:
    __slots__ = ("compiles", "dispatches", "compile_seconds", "retraces",
                 "evicted", "signatures", "blames", "build")

    def __init__(self):
        self.compiles = 0
        self.dispatches = 0
        self.compile_seconds = 0.0
        self.build = dict.fromkeys(_BUILD_FIELDS, 0)
        self.retraces = 0
        self.evicted = 0
        # sig tuple -> {"compiles","dispatches","compile_seconds",
        #               "first_seen","last_used"}; insertion order is the
        # LRU order (entries are re-inserted on use).
        self.signatures: "collections.OrderedDict[Tuple[str, ...], Dict]" = \
            collections.OrderedDict()
        self.blames: "collections.deque[Dict]" = collections.deque(
            maxlen=MAX_RETRACE_RECORDS)


_inventory_lock = threading.Lock()
_INVENTORY: Dict[str, _FnInventory] = {}

# (route, fn, tier, device) -> [microseconds, dispatches]; integer so the
# fleet merge sums exactly.
_attr_lock = threading.Lock()
_ATTR_TOTALS: Dict[Tuple[str, str, str, str], List[int]] = {}


def _nearest_signature(entry: _FnInventory,
                       sig: Tuple[str, ...]) -> Optional[Tuple[str, ...]]:
    best, best_n = None, None
    for cached in entry.signatures:
        n = len(diff_signatures(cached, sig))
        if best_n is None or n < best_n:
            best, best_n = cached, n
    return best


def _record_inventory(fn: str, sig: Tuple[str, ...], compiled: bool,
                      compile_s: float, now: float,
                      built: Sequence[CompileRecord] = ()) -> None:
    build = _build_fields(built) if compiled else None
    with _inventory_lock:
        entry = _INVENTORY.get(fn)
        if entry is None:
            entry = _INVENTORY[fn] = _FnInventory()
        entry.dispatches += 1
        blame = None
        if compiled:
            entry.compiles += 1
            entry.compile_seconds += compile_s
            for key, value in build.items():
                entry.build[key] += value
            if sig not in entry.signatures and entry.signatures:
                # Warm function recompiled: a retrace. Name the culprit.
                entry.retraces += 1
                nearest = _nearest_signature(entry, sig)
                blame = {
                    "ts": time.time(),
                    "signature": list(sig),
                    "against": list(nearest) if nearest else None,
                    "changed": (diff_signatures(nearest, sig)
                                if nearest else []),
                }
                entry.blames.append(blame)
        rec = entry.signatures.pop(sig, None)
        if rec is None:
            rec = {"compiles": 0, "dispatches": 0, "compile_seconds": 0.0,
                   "first_seen": now, "last_used": now,
                   **dict.fromkeys(_BUILD_FIELDS, 0)}
            while len(entry.signatures) >= MAX_SIGNATURES_PER_FN:
                entry.signatures.popitem(last=False)
                entry.evicted += 1
        rec["dispatches"] += 1
        rec["last_used"] = now
        if compiled:
            rec["compiles"] += 1
            rec["compile_seconds"] += compile_s
            for key, value in build.items():
                rec[key] += value
        entry.signatures[sig] = rec    # (re-)insert at MRU end
    if blame is not None:
        JIT_RETRACES.labels(fn=fn).inc()
        log.info("device: %s retraced (%s)", fn,
                 "; ".join(blame["changed"]) or "no cached signature diff")


def _account(route: str, fn: str, tier: str, device: str, us: int,
             app: Optional[str] = None) -> None:
    us = max(0, int(us))
    key = (route, fn, tier, device)
    with _attr_lock:
        slot = _ATTR_TOTALS.get(key)
        if slot is None:
            slot = _ATTR_TOTALS[key] = [0, 0]
        slot[0] += us
        slot[1] += 1
    labels = dict(route=route, fn=fn, tier=tier, device=device)
    DEVICE_SECONDS.labels(**labels).inc(us / 1e6)
    DEVICE_DISPATCHES.labels(**labels).inc()
    # tenant dimension: the same integer microseconds land in the tenant
    # meter, so sum over tenant labels (incl. "-") == device total exactly
    tenant.record_device_us(us, app=app)


# -- the device clock ----------------------------------------------------------


_backend_name: Optional[str] = None


def _backend() -> str:
    """Cached jax backend name; "cpu" when jax is absent (the wall-time
    fallback label)."""
    global _backend_name
    if _backend_name is None:
        if "jax" not in sys.modules:
            return "cpu"    # not cached: jax may load later
        try:
            import jax
            _backend_name = str(jax.default_backend())
        except Exception:  # noqa: BLE001
            _backend_name = "cpu"
    return _backend_name


class DeviceClock:
    """Measures per-dispatch device time without stalling the caller:
    dispatch sites enqueue (out, t0, labels); the drain thread blocks
    until the output buffers are ready and books the delta."""

    def __init__(self, maxsize: int = 2048):
        self._queue: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._running = False
        # (t_ready_monotonic, us) per device for the utilization window
        self._window: Dict[str, "collections.deque"] = {}

    def is_running(self) -> bool:
        return self._running

    def start(self) -> None:
        with self._lock:
            if self._running:
                return
            self._running = True
            self._thread = threading.Thread(
                target=self._drain, name="pio-device-clock", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            if not self._running:
                return
            self._running = False
            thread = self._thread
            self._thread = None
        self._queue.put(None)
        if thread is not None:
            thread.join(timeout=2.0)
        DEVICE_CLOCK_QUEUE.set(0)

    def submit(self, out: Any, t0: float, t1: float, fn: str, route: str,
               tier: str, compiled: bool,
               app: Optional[str] = None) -> bool:
        """Enqueue a dispatch for ready-delta measurement; False when the
        queue is full (caller falls back to wall time).

        `app` is the tenant captured on the DISPATCH thread — the drain
        thread has no contextvar binding, so it must travel in the item."""
        if not self._running:
            self.start()
        try:
            self._queue.put_nowait(
                (out, t0, t1, fn, route, tier, compiled, app))
        except queue.Full:
            DEVICE_CLOCK_DROPPED.inc()
            return False
        DEVICE_CLOCK_QUEUE.set(self._queue.qsize())
        return True

    def flush(self, timeout: float = 5.0) -> bool:
        """Wait (bounded) until every submitted dispatch is measured —
        gate drills and tests; serving never calls this."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._queue.empty():
                return True
            time.sleep(0.005)
        return self._queue.empty()

    def _drain(self) -> None:
        while True:
            try:
                item = self._queue.get(timeout=0.5)
            except queue.Empty:
                if not self._running:
                    return
                continue
            if item is None:
                return
            try:
                self._measure(*item)
            except Exception:  # noqa: BLE001 — the clock must never die
                log.debug("device: drain measurement failed", exc_info=True)
            finally:
                # the item holds the dispatch's outputs: kept across the
                # wait for the next one, a train step's state (gigabytes
                # on the device) would outlive its train
                item = None
                DEVICE_CLOCK_QUEUE.set(self._queue.qsize())

    def _measure(self, out: Any, t0: float, t1: float, fn: str, route: str,
                 tier: str, compiled: bool,
                 app: Optional[str] = None) -> None:
        device = _backend()
        try:
            import jax
            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001
            device = "cpu"
        t_ready = time.perf_counter()
        # A compiled dispatch spent (t1 - t0) mostly tracing+compiling on
        # the host; its device execution is the tail after the call
        # returned. A warm dispatch returns as soon as the work is
        # enqueued, so the whole t0→ready delta is device time.
        start = t1 if compiled else t0
        us = int(max(0.0, t_ready - start) * 1e6)
        _account(route, fn, tier, device, us, app=app)
        self._tick_utilization(device, t_ready, us)

    def _tick_utilization(self, device: str, now: float, us: int) -> None:
        win = self._window.get(device)
        if win is None:
            win = self._window[device] = collections.deque()
        win.append((now, us))
        horizon = now - UTILIZATION_WINDOW_S
        while win and win[0][0] < horizon:
            win.popleft()
        busy_us = sum(u for _, u in win)
        DEVICE_UTILIZATION.labels(device=device).set(
            round(busy_us / (UTILIZATION_WINDOW_S * 1e6), 6))


CLOCK = DeviceClock(
    maxsize=int(os.environ.get("PIO_DEVICE_CLOCK_QUEUE") or 2048))

_clock_enabled = _env_flag("PIO_DEVICE_CLOCK")


def clock_enabled() -> bool:
    return _clock_enabled


def set_clock_enabled(on: bool) -> None:
    """Runtime toggle for the overhead A/B drill (mirrors profiler.stop)."""
    global _clock_enabled
    _clock_enabled = bool(on)
    if not on:
        CLOCK.stop()


# -- the metered_jit hook ------------------------------------------------------


def record_dispatch(fn: str, args: Sequence[Any] = (),
                    kwargs: Optional[Dict[str, Any]] = None,
                    out: Any = None, t0: float = 0.0,
                    t1: Optional[float] = None, compiled: bool = False,
                    compile_s: float = 0.0,
                    built: Sequence[CompileRecord] = ()) -> None:
    """The single entry point `utils/profiling.metered_jit` calls per
    dispatch: updates the jit-cache inventory, books route/tier
    attribution, and hands the output to the device clock. `compile_s`
    is the whole wall of a call that compiled, `built` the compile log's
    records of that call: the phases the wall is made of."""
    t1 = time.perf_counter() if t1 is None else t1
    now = time.time()
    _record_inventory(fn, signature_of(args, kwargs), compiled, compile_s,
                      now, built)
    att = current_attribution()
    if att is not None:
        route, tier = att.route, att.tier
        if att.t_first_dispatch is None:
            att.t_first_dispatch = t0
        att.jit_wall_s += max(0.0, t1 - t0)
        att.dispatches += 1
    else:
        route, tier = UNTRACKED_ROUTE, ""
    if not _clock_enabled:
        return
    # capture the tenant HERE, on the dispatch thread, where the serving
    # plane's contextvar binding is live; the clock's drain thread isn't
    app = tenant.current_app()
    if out is not None and "jax" in sys.modules and _backend() != "cpu":
        if CLOCK.submit(out, t0, t1, fn, route, tier, compiled, app=app):
            return
    # Wall-time fallback: jax-less processes, the CPU backend (execution
    # completes inside the call), or a saturated drain queue.
    _account(route, fn, tier, "cpu", int(max(0.0, t1 - t0) * 1e6), app=app)


# -- /debug/jit.json -----------------------------------------------------------


def jit_payload() -> Tuple[int, Dict]:
    """GET /debug/jit.json — the process-local jit-cache inventory."""
    fns: Dict[str, Dict] = {}
    totals = {"compiles": 0, "dispatches": 0, "retraces": 0, "evicted": 0}
    with _inventory_lock:
        for name, entry in _INVENTORY.items():
            sigs = [
                {"signature": list(sig),
                 "compiles": rec["compiles"],
                 "dispatches": rec["dispatches"],
                 "compile_seconds": round(rec["compile_seconds"], 6),
                 **{key: round(rec[key], 6) for key in _BUILD_FIELDS},
                 "first_seen": rec["first_seen"],
                 "last_used": rec["last_used"]}
                for sig, rec in entry.signatures.items()]
            sigs.sort(key=lambda s: -s["dispatches"])
            fns[name] = {
                "compiles_total": entry.compiles,
                "dispatches_total": entry.dispatches,
                "compile_seconds_total": round(entry.compile_seconds, 6),
                **{key: round(entry.build[key], 6)
                   for key in _BUILD_FIELDS},
                "retraces_total": entry.retraces,
                "evicted_signatures": entry.evicted,
                "signatures": sigs,
                "retrace_blame": list(entry.blames),
            }
            totals["compiles"] += entry.compiles
            totals["dispatches"] += entry.dispatches
            totals["retraces"] += entry.retraces
            totals["evicted"] += entry.evicted
    with _attr_lock:
        attribution_rows = [
            {"route": k[0], "fn": k[1], "tier": k[2], "device": k[3],
             "us": v[0], "dispatches": v[1]}
            for k, v in sorted(_ATTR_TOTALS.items(),
                               key=lambda kv: -kv[1][0])]
    return 200, {
        "fns": fns,
        "totals": totals,
        "device_attribution": attribution_rows,
        "clock": {"enabled": _clock_enabled,
                  "running": CLOCK.is_running(),
                  "queue_depth": CLOCK._queue.qsize(),
                  "backend": _backend()},
    }


# -- /debug/profile/device.json (moved from profiler.py, envelope kept) --------


def memory_payload() -> Tuple[int, Dict]:
    """GET /debug/profile/device.json — jax live-buffer and device-memory
    view. Lazy-import discipline: processes that never loaded jax (event
    server, tests) answer a 503 envelope instead of paying the import."""
    if "jax" not in sys.modules:
        return 503, {"status": 503,
                     "error": "jax not loaded in this process"}
    import jax

    out: Dict = {"backend": None, "device_kind": None, "devices": [],
                 "live_buffers": {}, "top_buffers": [], "memory_stats": {}}
    try:
        devices = jax.devices()
        out["backend"] = jax.default_backend()
        out["device_kind"] = devices[0].device_kind
        out["devices"] = [str(d) for d in devices]
    except Exception:  # noqa: BLE001
        pass
    try:
        per_device: Dict[str, Dict] = {}
        buffers = []
        for arr in jax.live_arrays():
            try:
                dev = str(next(iter(arr.devices())))
                nbytes = int(arr.nbytes)
            except Exception:  # noqa: BLE001
                continue
            slot = per_device.setdefault(dev, {"count": 0, "bytes": 0})
            slot["count"] += 1
            slot["bytes"] += nbytes
            buffers.append((nbytes, str(arr.shape), str(arr.dtype), dev))
        out["live_buffers"] = per_device
        buffers.sort(key=lambda b: -b[0])
        out["top_buffers"] = [
            {"bytes": b, "shape": shape, "dtype": dtype, "device": dev}
            for b, shape, dtype, dev in buffers[:20]]
    except Exception:  # noqa: BLE001
        out["live_buffers_error"] = "live_arrays unavailable"
    try:
        prof = jax.profiler.device_memory_profile()
        out["device_memory_profile_bytes"] = len(prof)
    except Exception:  # noqa: BLE001
        out["device_memory_profile_bytes"] = None
    try:
        for d in jax.local_devices():
            stats = getattr(d, "memory_stats", None)
            if callable(stats):
                s = stats()
                if s:
                    out["memory_stats"][str(d)] = {
                        k: v for k, v in s.items()
                        if isinstance(v, (int, float))}
    except Exception:  # noqa: BLE001
        pass
    return 200, out


# -- device-memory sampler -----------------------------------------------------


class MemorySampler:
    """Periodically folds live-buffer bytes per device into the
    `device_mem_*` gauges (which `telemetry/history.py` then samples into
    queryable series). No-ops cheaply while jax is unloaded."""

    def __init__(self, interval_s: float = 10.0):
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self.high_water: Dict[str, int] = {}

    @classmethod
    def from_env(cls) -> "MemorySampler":
        return cls(interval_s=float(
            os.environ.get("PIO_DEVICE_MEM_INTERVAL_S") or 10.0))

    def is_running(self) -> bool:
        return self._running

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="pio-device-mem", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=2.0)
        self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sample_now()
            except Exception:  # noqa: BLE001 — sampling must never die
                log.debug("device: memory sample failed", exc_info=True)

    def sample_now(self) -> Dict[str, int]:
        """One sample sweep; returns live bytes per device (empty while
        jax is unloaded)."""
        if "jax" not in sys.modules:
            return {}
        import jax

        live: Dict[str, int] = {}
        try:
            for arr in jax.live_arrays():
                try:
                    dev = str(next(iter(arr.devices())))
                    live[dev] = live.get(dev, 0) + int(arr.nbytes)
                except Exception:  # noqa: BLE001
                    continue
        except Exception:  # noqa: BLE001
            return {}
        limits: Dict[str, int] = {}
        try:
            for d in jax.local_devices():
                stats = getattr(d, "memory_stats", None)
                if callable(stats):
                    s = stats() or {}
                    limit = s.get("bytes_limit")
                    if isinstance(limit, (int, float)) and limit > 0:
                        limits[str(d)] = int(limit)
        except Exception:  # noqa: BLE001
            pass
        for dev, nbytes in live.items():
            DEVICE_MEM_LIVE.labels(device=dev).set(nbytes)
            hw = max(self.high_water.get(dev, 0), nbytes)
            self.high_water[dev] = hw
            DEVICE_MEM_HIGH_WATER.labels(device=dev).set(hw)
        for dev, limit in limits.items():
            DEVICE_MEM_LIMIT.labels(device=dev).set(limit)
            used = live.get(dev, 0)
            DEVICE_MEM_HEADROOM.labels(device=dev).set(
                round(max(0.0, (limit - used) / limit), 6))
        return live


SAMPLER: Optional[MemorySampler] = None
_sampler_lock = threading.Lock()


def ensure_started() -> None:
    """Start the drain thread + memory sampler (idempotent); every
    instrumented server calls this at startup, same contract as the
    profiler and history."""
    if _clock_enabled:
        CLOCK.start()
    global SAMPLER
    if not _env_flag("PIO_DEVICE_MEM"):
        return
    with _sampler_lock:
        if SAMPLER is None:
            SAMPLER = MemorySampler.from_env()
        SAMPLER.start()


def stop() -> None:
    CLOCK.stop()
    with _sampler_lock:
        if SAMPLER is not None:
            SAMPLER.stop()


# -- fleet merge (rides PR 9's snapshot channel) -------------------------------


def export_state() -> Dict:
    """The per-worker device block embedded in aggregate
    snapshot_registry() payloads — what the supervisor merges. Times are
    integer microseconds so merged totals are sum-exact."""
    with _attr_lock:
        attribution_rows = [
            [k[0], k[1], k[2], k[3], v[0], v[1]]
            for k, v in _ATTR_TOTALS.items()]
    with _inventory_lock:
        fns = {name: {"compiles": e.compiles, "dispatches": e.dispatches,
                      "retraces": e.retraces}
               for name, e in _INVENTORY.items()}
    return {
        "attribution": attribution_rows,
        "fns": fns,
        "total_us": sum(r[4] for r in attribution_rows),
        "clock_running": CLOCK.is_running(),
    }


def merge_device(parts: Iterable[Tuple[str, Optional[Dict]]]) -> Dict:
    """Merge (worker_label, export_state()) pairs into one fleet device
    view. Microsecond totals are summed exactly — integers, no averaging
    — and the per-worker totals ship *inside the same payload* as the
    fleet total, so exactness is checkable from one fetch:
    ``total_us == sum(workers.values())`` always holds."""
    workers: Dict[str, int] = {}
    attribution: Dict[Tuple[str, str, str, str], List[int]] = {}
    routes: Dict[str, int] = {}
    fns: Dict[str, Dict[str, int]] = {}
    clocks_running = 0
    total_us = 0
    for wlabel, state in parts:
        if state is None:
            workers.setdefault(str(wlabel), 0)
            continue
        part_us = 0
        for row in state.get("attribution", []):
            route, fn, tier, device = (str(row[0]), str(row[1]),
                                       str(row[2]), str(row[3]))
            us, n = int(row[4]), int(row[5])
            slot = attribution.setdefault((route, fn, tier, device), [0, 0])
            slot[0] += us
            slot[1] += n
            routes[route] = routes.get(route, 0) + us
            part_us += us
        workers[str(wlabel)] = workers.get(str(wlabel), 0) + part_us
        total_us += part_us
        for name, counts in state.get("fns", {}).items():
            dst = fns.setdefault(name, {"compiles": 0, "dispatches": 0,
                                        "retraces": 0})
            for key in dst:
                dst[key] += int(counts.get(key, 0))
        if state.get("clock_running"):
            clocks_running += 1
    return {
        "fleet": True,
        "workers": workers,
        "clocks_running": clocks_running,
        "total_us": total_us,
        "total_seconds": round(total_us / 1e6, 6),
        "routes": {r: us for r, us in
                   sorted(routes.items(), key=lambda kv: -kv[1])},
        "attribution": [
            {"route": k[0], "fn": k[1], "tier": k[2], "device": k[3],
             "us": v[0], "dispatches": v[1]}
            for k, v in sorted(attribution.items(),
                               key=lambda kv: -kv[1][0])],
        "fns": fns,
    }


# -- lifecycle -----------------------------------------------------------------


def reset_state() -> None:
    """Zero the inventory and attribution totals (tests, gate drills,
    and the post-fork child — the supervisor merge must never sum a
    parent's history twice)."""
    with _inventory_lock:
        _INVENTORY.clear()
    with _attr_lock:
        _ATTR_TOTALS.clear()


def _reinit_after_fork() -> None:
    global _inventory_lock, _attr_lock, _sampler_lock, _backend_name
    global _listen_lock
    _inventory_lock = threading.Lock()
    _attr_lock = threading.Lock()
    _sampler_lock = threading.Lock()
    _listen_lock = threading.Lock()
    _backend_name = None
    _INVENTORY.clear()
    _ATTR_TOTALS.clear()
    # the parent's first seconds and compiles are not the child's
    COMPILE_LOG._lock = threading.Lock()
    COMPILE_LOG.clear()
    clock_was_running = CLOCK._running
    CLOCK._lock = threading.Lock()
    CLOCK._queue = queue.Queue(maxsize=CLOCK._queue.maxsize)
    CLOCK._thread = None
    CLOCK._running = False
    CLOCK._window = {}
    if clock_was_running and _clock_enabled:
        CLOCK.start()
    sampler = SAMPLER
    if sampler is not None:
        was_running = sampler._running
        sampler._stop = threading.Event()
        sampler._thread = None
        sampler._running = False
        sampler.high_water = {}
        if was_running and _env_flag("PIO_DEVICE_MEM"):
            sampler.start()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reinit_after_fork)
