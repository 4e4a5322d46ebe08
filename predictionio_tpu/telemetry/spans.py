"""Per-request span timelines — the flight recorder's data model.

PR 2 gave every request a trace id and every subsystem aggregate metrics;
what neither can answer is "*which stage* of THIS slow request ate the
time". A `Timeline` is that answer: one bounded list of named spans
(start offset + duration, measured on `time.monotonic()`) hanging off a
contextvar for the request's whole handler run. The HTTP middleware opens
one per request; `spans.span("serving.admission")` blocks record into it
from anywhere downstream; the flight recorder (telemetry/recorder.py)
tail-samples the finished product.

Three recording paths, because more than one thread touches a request:

- `span(name)` — a context manager for work on the *request's own thread*
  (admission, validation, storage calls). When jax is loaded it also
  opens a `jax.profiler.TraceAnnotation`, so the same stage names appear
  on XLA timelines and in the flight recorder.
- `record(name, duration_s, start_s=...)` — for stages measured on
  *another* thread (the micro-batcher's dispatcher, the group-commit
  writer) and stamped onto the pending entry; the handler thread copies
  the stamps into its own timeline after being woken. Contextvars don't
  cross threads, and handing the timeline itself to the dispatcher would
  make one slow request's bookkeeping a shared-state problem.
- `Worker(name, fn, *args)` — for work the request's thread *hands to a
  thread of its own and joins* (the two sides of a bucketize, the
  bucket-cache save behind the train loop). The worker records into a
  timeline of its own, so `span` and `record` inside `fn` behave as on
  the request's thread, annotations included, and `depth` keeps its one
  writer; `join()` copies the finished records into the timeline the
  worker was started under, on the same axis.

Clock discipline: all offsets are `time.monotonic()` relative to the
timeline's `t0`, the same clock the serving/ingest planes already stamp
deadlines and queue waits with — so cross-thread stamps land on the same
axis as same-thread spans without conversion.

Everything here sits on the per-request hot path under the established
≤5% instrumentation budget: __slots__ classes, one contextvar get per
span, a plain list append, and a hard `MAX_SPANS` cap so a pathological
loop cannot grow a timeline without bound.
"""

from __future__ import annotations

import contextvars
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from predictionio_tpu.telemetry import tracing

_sys_modules = sys.modules

# Hard per-timeline span cap: a runaway loop (e.g. a storage op per event
# row) must not turn one request's timeline into a memory leak. Overflow
# is counted on the timeline so truncation is visible, never silent.
MAX_SPANS = 128


# One recorded stage is a plain tuple (name, start_s, duration_s, error,
# nested) — tuple allocation is the cheapest record Python can make, and
# span recording sits inside the ≤5% per-request overhead budget
# (tests/test_telemetry.py). `nested` marks spans recorded inside another
# span (e.g. a storage op inside an inline commit): they refine
# attribution but are excluded from `Timeline.span_sum_s()` so stage
# sums don't double-count.


class Timeline:
    """The per-request flight record: identity + bounded span list.

    Built by the HTTP middleware (or a workflow run) at request start;
    `status`/`duration_s` are stamped by `finish()`; the recorder decides
    afterwards whether the finished timeline is worth keeping."""

    __slots__ = ("trace_id", "server", "route", "method", "start_time",
                 "t0", "spans", "status", "duration_s", "error", "pinned",
                 "dropped_spans", "depth")

    def __init__(self, server: str, route: str, method: str, trace_id: str):
        self.server = server
        self.route = route
        self.method = method
        self.trace_id = trace_id
        # epoch start is derived lazily in to_dict (one fewer clock call
        # on the per-request path); t0 anchors the span-offset axis
        self.start_time = 0.0
        self.t0 = time.monotonic()
        self.spans: List[tuple] = []
        self.status: Optional[int] = None
        self.duration_s = 0.0
        self.error = False
        # force-capture flag (X-PIO-Debug header, workflow runs): the
        # recorder keeps pinned timelines regardless of sampling
        self.pinned = False
        self.dropped_spans = 0
        # live nesting depth of `span` context managers on this thread;
        # spans recorded at depth > 0 are marked nested
        self.depth = 0

    def record(self, name: str, start_s: float, duration_s: float,
               error: bool = False, nested: bool = False) -> None:
        if len(self.spans) >= MAX_SPANS:
            # one writer a timeline: a `Worker`'s thread records into a
            # timeline of its own, which its starter copies over at the
            # join, on the starter's thread
            self.dropped_spans += 1  # pio-lint: disable=race-shared-state
            return
        self.spans.append((name, start_s, duration_s, error, nested))

    def span_sum_s(self) -> float:
        """Sum of top-level stage durations — the acceptance check that
        stage attribution accounts for the measured wall latency compares
        this against `duration_s`. Nested spans are excluded: they refine
        a parent stage, so counting them would double-bill the time."""
        return sum(s[2] for s in self.spans if not s[4])

    def to_dict(self) -> dict:
        if not self.start_time:
            # freeze time: map the monotonic anchor onto the epoch axis
            self.start_time = time.time() - (time.monotonic() - self.t0)
        spans_out = []
        for name, start_s, duration_s, error, nested in self.spans:
            s = {
                "name": name,
                "start_ms": round(start_s * 1e3, 3),
                "duration_ms": round(duration_s * 1e3, 3),
            }
            if error:
                s["error"] = True
            if nested:
                s["nested"] = True
            spans_out.append(s)
        d = {
            "trace_id": self.trace_id,
            "server": self.server,
            "route": self.route,
            "method": self.method,
            "start_time": self.start_time,
            "status": self.status,
            "duration_ms": round(self.duration_s * 1e3, 3),
            "spans": spans_out,
        }
        if self.error:
            d["error"] = True
        if self.dropped_spans:
            d["dropped_spans"] = self.dropped_spans
        return d


_active: contextvars.ContextVar[Optional[Timeline]] = \
    contextvars.ContextVar("pio_timeline", default=None)

# Thread-ident → active Timeline. Contextvars are invisible from other
# threads, but the stack sampler (telemetry/profiler.py) must attribute a
# thread's frames to the request it is serving — so begin/finish mirror
# the active timeline into this plain dict. Dict store/pop on int keys is
# GIL-atomic; no lock on the per-request hot path. The sampler only ever
# *reads* (a racy read sees either the old or new timeline, both fine for
# a statistical profile).
_BY_THREAD: Dict[int, Timeline] = {}


def current() -> Optional[Timeline]:
    return _active.get()


def thread_timeline(ident: int) -> Optional[Timeline]:
    """The timeline active on another thread, by thread ident — the
    profiler's route/trace join point. Best-effort by design."""
    return _BY_THREAD.get(ident)


def begin(server: str, route: str, method: str,
          trace_id: str) -> tuple[Timeline, contextvars.Token]:
    """Open a timeline for the current context; pair with `finish()`."""
    tl = Timeline(server, route, method, trace_id)
    token = _active.set(tl)
    _BY_THREAD[threading.get_ident()] = tl
    return tl, token


def finish(tl: Timeline, token: contextvars.Token, status: Optional[int],
           duration_s: float, error: bool = False) -> Timeline:
    """Stamp the outcome and deactivate. The caller decides what happens
    to the finished timeline (normally: offer it to the flight recorder)."""
    tl.status = status
    tl.duration_s = duration_s
    tl.error = tl.error or error
    _active.reset(token)
    # Restore the outer timeline for nested begins (workflow runs that
    # issue sub-requests on the same thread); drop the entry otherwise so
    # idle pool threads don't pin finished timelines.
    outer = _active.get()
    ident = threading.get_ident()
    if outer is None:
        _BY_THREAD.pop(ident, None)
    else:
        _BY_THREAD[ident] = outer
    return tl


def _reinit_after_fork() -> None:
    # Thread idents are reused and only the forking thread survives into
    # the child — inherited entries would mis-attribute fresh threads.
    _BY_THREAD.clear()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reinit_after_fork)


def record(name: str, duration_s: float,
           start_s: Optional[float] = None,
           error: bool = False,
           nested: Optional[bool] = None) -> None:
    """Record a pre-measured span into the active timeline (no-op without
    one — storage ops triggered by untimed work, committer threads).

    `start_s` is an offset on the timeline's monotonic axis; when omitted
    the span is assumed to have just ended. `nested` defaults to "am I
    inside a live span context"; cross-thread stamps that refine a stage
    recorded the same way (the batcher's dispatch host/device split) pass
    it explicitly, since the stage's span context is long gone by the
    time the waiting thread copies the stamps."""
    tl = _active.get()
    if tl is None:
        return
    if start_s is None:
        start_s = time.monotonic() - tl.t0 - duration_s
    tl.record(name, start_s, duration_s, error,
              nested=tl.depth > 0 if nested is None else nested)


def record_between(name: str, start_monotonic: float,
                   end_monotonic: float,
                   nested: Optional[bool] = None) -> None:
    """Record a span from two absolute `time.monotonic()` stamps — the
    shape cross-thread stages arrive in (enqueued_at / taken_at / done
    stamps on a pending queue entry)."""
    tl = _active.get()
    if tl is None:
        return
    tl.record(name, start_monotonic - tl.t0,
              max(0.0, end_monotonic - start_monotonic),
              nested=tl.depth > 0 if nested is None else nested)


class Worker:
    """`fn(*args)` on a thread of its own, started here; `join()` waits
    for it and returns what `fn` returned, or raises what it raised.

    Under an active timeline the thread records into one of its own
    (same route and trace id, so the stack sampler bills its frames to
    the same request) and `join()` hands the records to the starter's
    timeline with their true intervals; a record is nested where the
    worker nested it or where the join stands inside a live span. The
    thread does not outlive a `join()`, and a `Worker` is joined by the
    thread that made it."""

    __slots__ = ("_thread", "_outer", "_own", "_result", "_error")

    def __init__(self, name: str, fn, *args):
        self._outer = _active.get()
        self._own: Optional[Timeline] = None
        self._result = None
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, args=(fn, args),
                                        name=name)
        self._thread.start()

    def _run(self, fn, args) -> None:
        outer, token = self._outer, None
        if outer is not None:
            self._own, token = begin(outer.server, outer.route, outer.method,
                                     outer.trace_id)
        try:
            self._result = fn(*args)
        except BaseException as e:  # re-raised by join(), in the starter
            self._error = e
        finally:
            if token is not None:
                finish(self._own, token, status=None, duration_s=0.0)

    def wait(self) -> None:
        """Until the thread has ended; nothing is copied or raised."""
        self._thread.join()

    def join(self):
        self._thread.join()
        outer, own, self._own = self._outer, self._own, None
        if own is not None:
            shift = own.t0 - outer.t0
            inside = outer.depth > 0
            for name, start_s, duration_s, error, nested in own.spans:
                outer.record(name, start_s + shift, duration_s, error,
                             nested=nested or inside)
            outer.dropped_spans += own.dropped_spans
        if self._error is not None:
            raise self._error
        return self._result


class span:
    """A named stage: timeline record + XLA trace annotation.

    Drop-in for tracing.span everywhere a stage should show up in the
    flight recorder; on threads without an active timeline only the
    annotation remains (train workers, committer threads). Unlike
    tracing.span it does NOT open a child trace context: stage spans are
    identified by name in the timeline, not by span id, and the context
    push/pop would triple the cost of a stage on the serving hot path
    (the ≤5% overhead bar in tests/test_telemetry.py)."""

    __slots__ = ("name", "_tl", "_t0", "_nested", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        # inline the jax-loaded check: _jax_annotation is a call + dict
        # lookup per stage, and most processes (ingest, tests) never
        # load jax
        if "jax" in _sys_modules:
            ann = self._ann = tracing._jax_annotation(self.name)
            if ann is not None:
                try:
                    ann.__enter__()
                except Exception:
                    self._ann = None
        else:
            self._ann = None
        tl = self._tl = _active.get()
        if tl is not None:
            self._nested = tl.depth > 0
            tl.depth += 1
            self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tl = self._tl
        if tl is not None:
            t1 = time.monotonic()
            tl.depth -= 1
            tl.record(self.name, self._t0 - tl.t0, t1 - self._t0,
                      error=exc_type is not None, nested=self._nested)
        ann = self._ann
        if ann is not None:
            try:
                ann.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        return False
