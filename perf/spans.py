"""Host spans taken from the benchmark's side: wrappers around callables
of the program, installed for the length of one run and only where a
metric needs them.

A target is a string. `module:attr` wraps that attribute (a function, a
class, a method of a module-level object); the wrapper replaces the
attribute on the module, so it sees calls that look the name up at call
time. `jit:<label>` hooks `utils.profiling.metered_jit` and times every
call of the jitted program of that label, closed by a fence on its
outputs: a host clock around device work.

A recorder keeps, for every call of the traffic, the (start, end) pairs
of each span by name. With `annotate=True` each span is also written
into the profiler's trace (`jax.profiler.TraceAnnotation`, named
`perf:<name>`), which puts host spans and device operations on one clock.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import time


class Recorder:
    """Spans of the call in flight; `calls` keeps the finished ones."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.calls: list[dict] = []
        self._open: dict | None = None

    @contextlib.contextmanager
    def call(self):
        """One call of the traffic: the span `call`, and the scope in
        which every other span is kept."""
        self._open = {}
        try:
            with self.span("call"):
                yield
        finally:
            self.calls.append(self._open)
            self._open = None

    @contextlib.contextmanager
    def span(self, name: str):
        if self._open is None:  # outside a timed call: not recorded
            yield
            return
        note = None
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation(f"perf:{name}")
            note.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if note is not None:
                note.__exit__(None, None, None)
            if self._open is not None:
                self._open.setdefault(name, []).append((t0, t1))


def _resolve(target: str):
    mod_name, _, attr_path = target.partition(":")
    owner = importlib.import_module(mod_name)
    *parents, attr = attr_path.split(".")
    for p in parents:
        owner = getattr(owner, p)
    return owner, attr


def wrap(rec: Recorder, name: str, target: str):
    """Record span `name` around every call of `target`. Returns a
    zero-argument function that puts the original back."""
    if target.startswith("jit:"):
        return _wrap_jit(rec, name, target[4:])
    owner, attr = _resolve(target)
    orig = getattr(owner, attr)

    @functools.wraps(orig)
    def wrapper(*args, **kwargs):
        with rec.span(name):
            return orig(*args, **kwargs)

    setattr(owner, attr, wrapper)
    return lambda: setattr(owner, attr, orig)


def _wrap_jit(rec: Recorder, name: str, want: str):
    from predictionio_tpu.utils import profiling

    orig = profiling.metered_jit

    def hook(fn, label=None, **jit_kwargs):
        jitted = orig(fn, label=label, **jit_kwargs)
        if label != want:
            return jitted

        @functools.wraps(jitted)
        def timed(*args, **kwargs):
            import jax

            with rec.span(name):
                return jax.block_until_ready(jitted(*args, **kwargs))

        return timed

    profiling.metered_jit = hook
    return lambda: setattr(profiling, "metered_jit", orig)


def seconds(call: dict, spec) -> float | None:
    """Seconds of one span specification inside one finished call.

    `spec` is a span name (all its occurrences, summed), or a pair
    `[edge_a, edge_b]` where an edge is `"start:<name>"` or `"end:<name>"`
    (first start, last end): the interval between two edges. None when a
    span it names did not occur in the call."""
    if isinstance(spec, str):
        if spec not in call:
            return None
        return sum(t1 - t0 for t0, t1 in call[spec])
    a, b = (_edge(call, e) for e in spec)
    return None if a is None or b is None else b - a


def _edge(call: dict, edge: str):
    which, _, name = edge.partition(":")
    if name not in call:
        return None
    return call[name][0][0] if which == "start" else call[name][-1][1]
