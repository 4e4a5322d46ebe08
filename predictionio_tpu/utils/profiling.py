"""Tracing, per-epoch metrics, and numeric-debug flags.

The reference has no profiling of its own — it inherits the Spark web UI
(stages/tasks at :4040) and log4j verbosity, with
`WorkflowParams.verbose` gating debug materialization (SURVEY.md §5
'Tracing / profiling' [U]). The TPU rebuild's equivalents:

- `maybe_trace(profile_dir)`: a `jax.profiler.trace` capture viewable in
  TensorBoard / Perfetto — the XLA analogue of the Spark stage timeline.
  Enabled by `pio train --profile-dir`.
- `MetricsLogger`: structured per-epoch metric emission (loss/RMSE, step
  time, MAP@10) to stdout logging + a JSON-lines file — the rebuild's
  replacement for eyeballing Spark stage durations.
- `set_debug_flags`: `jax_debug_nans` (SURVEY.md §5 'Race detection':
  functional purity already gives the memory-model story; NaN checking is
  the numeric-sanitizer analogue).
"""

from __future__ import annotations

import contextlib
import functools
import json
import logging
import os
import threading
import time
from typing import Any, Optional, TextIO

from predictionio_tpu.telemetry import device as device_telemetry
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY, capped_label

log = logging.getLogger(__name__)

JIT_COMPILES = REGISTRY.counter(
    "jit_compiles_total",
    "XLA compiles observed per jitted function (a climbing counter at "
    "steady state is a recompile storm — look for unstable shapes)",
    labelnames=("fn",))
# compile-log phase -> the timeline's span inside `jit.compile.<fn>`
_PHASE_SPANS = {"trace": "jit.trace", "lower": "jit.lower",
                "backend_compile": "jit.backend"}
JIT_COMPILE_SECONDS = REGISTRY.histogram(
    "jit_compile_seconds",
    "Wall time of calls that included a trace+compile, per jitted function",
    labelnames=("fn",),
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0, 120.0))


def metered_jit(fn, label: Optional[str] = None, **jit_kwargs):
    """`jax.jit` wrapper surfacing compile activity on /metrics.

    Each call compares the jitted callable's executable-cache size before
    and after: growth means THIS call traced + compiled, so its wall time
    lands in `jit_compile_seconds{fn=label}` and `jit_compiles_total`
    increments. Cache-hit calls pay two cheap cache-size reads — the
    measured overhead is well under the ≤5% instrumentation bar.

    The compile also lands on the calling request's span timeline (when
    one is active) as `jit.compile.<label>` — a latency cliff in the
    flight recorder names its cause instead of looking like a slow
    dispatch.

    What that wall is made of comes from JAX's own events
    (telemetry/device.py::COMPILE_LOG, listening from the first wrap at
    the latest): the wrapper names the call in flight on its thread, so
    every event under it is booked to this label, and a compiled call's
    outermost phases land inside
    `jit.compile.<label>` as `jit.trace.<label>`, `jit.lower.<label>`
    and `jit.backend.<label>` (a cache load or a compile).

    Every dispatch also feeds the device plane
    (telemetry/device.py): the jit-cache inventory behind
    /debug/jit.json (per-signature compile/dispatch counts, retrace
    blame) and the device clock's `device_seconds_total` attribution.
    Labels pass through `capped_label` so a caller minting one label per
    runtime value (the old ranking.score_topk_k{k} bug) cannot grow
    /metrics without bound.

    `wrapper.compile_count()` is `jit_compiles_total{fn=label}` as it
    stands: a caller that times its own dispatch reads it before and
    after to leave a compile out of a steady-state figure (`als_train`'s
    `epoch_times`). A callable, so it stays live on a `functools.wraps`
    copy of the wrapper."""
    import jax

    device_telemetry.listen()
    # the wrapper itself is the metering boundary
    jitted = jax.jit(fn, **jit_kwargs)  # pio-lint: disable=coverage-jit-metering
    name = capped_label("jit_fn", label or getattr(fn, "__name__", "jit"))
    compiles = JIT_COMPILES.labels(fn=name)
    seconds = JIT_COMPILE_SECONDS.labels(fn=name)
    cache_size = jitted._cache_size
    span_name = f"jit.compile.{name}"
    in_flight = device_telemetry.IN_FLIGHT

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        before = cache_size()
        # the label the compile log books this thread's events to; an
        # enclosing call's label (this one traced under it) comes back
        outer = getattr(in_flight, "fn", None)
        in_flight.fn = name
        t0 = time.perf_counter()
        try:
            out = jitted(*args, **kwargs)
        finally:
            t1 = time.perf_counter()
            in_flight.fn = outer
        compiled = cache_size() > before
        elapsed = t1 - t0
        built = ()
        if compiled:
            compiles.inc()
            seconds.observe(elapsed)
            spans.record(span_name, elapsed)
            built = device_telemetry.COMPILE_LOG.records(
                t0, t1, thread=threading.get_ident())
            tl = spans.current()
            if tl is not None:
                # the log's stamps are perf_counter's, the timeline's
                # offsets monotonic's from its t0
                shift = time.monotonic() - tl.t0 - time.perf_counter()
                for r in built:
                    if r.depth == 0 and r.phase in _PHASE_SPANS:
                        tl.record(f"{_PHASE_SPANS[r.phase]}.{name}",
                                  r.start + shift, r.end - r.start,
                                  nested=True)
            log.info("profiling: %s compiled (cache %d -> %d, %.3fs)",
                     name, before, cache_size(), elapsed)
        try:
            device_telemetry.record_dispatch(
                name, args, kwargs, out=out, t0=t0, t1=t1,
                compiled=compiled, compile_s=elapsed if compiled else 0.0,
                built=built)
        except Exception:  # noqa: BLE001 — telemetry must not fail dispatch
            log.debug("profiling: device record failed for %s", name,
                      exc_info=True)
        return out

    # the underlying jitted callable, for callers that need .lower() /
    # .clear_cache() or want to bypass the metering
    wrapper.jitted = jitted
    wrapper.compile_count = lambda: compiles.value
    return wrapper


@contextlib.contextmanager
def maybe_trace(profile_dir: Optional[str]):
    """Capture a device/host trace into `profile_dir` when set, else no-op.

    The capture is written in TensorBoard's profile layout
    (`plugins/profile/<run>/...`), loadable with `tensorboard --logdir`
    or Perfetto.
    """
    if not profile_dir:
        yield None
        return
    import jax

    os.makedirs(profile_dir, exist_ok=True)
    log.info("profiling: tracing to %s", profile_dir)
    with jax.profiler.trace(profile_dir):
        yield profile_dir


def xplane_device_time_s(profile_dir: str) -> float:
    """Summed on-device execution time (seconds) of every XLA module
    dispatch recorded in `profile_dir`'s xplane capture.

    The device-plane 'XLA Modules' line carries one event per executed
    module with its on-chip duration — wall-clock minus dispatch and
    host time, which vary from run to run. This is what makes committed
    perf records comparable across runs (VERDICT r2 #6).

    Durations sum within a device plane (sequential executions on that
    chip) and take the MAX across planes: SPMD programs run on every
    chip in parallel, so summing planes would inflate an N-chip run N×."""
    import glob

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    per_plane_ps = []
    for path in glob.glob(
            os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True):
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    per_plane_ps.append(
                        sum(e.duration_ps for e in line.events))
    return max(per_plane_ps, default=0) / 1e12


def _xplane_parseable() -> bool:
    """Whether the TensorFlow xplane protos needed by
    `xplane_device_time_s` exist on this image (memoized)."""
    global _XPLANE_OK
    if _XPLANE_OK is None:
        try:
            from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa
            _XPLANE_OK = True
        except ImportError:
            _XPLANE_OK = False
    return _XPLANE_OK


_XPLANE_OK = None


def trace_device_time_s(fn) -> float:
    """Run `fn()` under a fresh profiler trace; return its device time.

    Returns 0.0 WITHOUT running `fn` when the TensorFlow xplane protos are
    absent (capture could never be parsed) — callers treat <=0 as
    "device time unavailable" (bench_north_star emits device_epoch_s=null),
    so skipping the doomed trace saves minutes of profiled reps on a
    TF-less image."""
    import shutil
    import tempfile

    import jax

    if not _xplane_parseable():
        import warnings
        warnings.warn("tensorflow.tsl xplane protos unavailable — device "
                      "time cannot be measured on this image")
        return 0.0
    d = tempfile.mkdtemp(prefix="pio_devtime_")
    try:
        with jax.profiler.trace(d):
            fn()
        return xplane_device_time_s(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def set_debug_flags(nan_check: bool = False,
                    check_asserts: bool = False) -> None:
    """Numeric sanitizers for the train loop. `nan_check` recompiles jitted
    programs with NaN detection (slow; debugging only). `check_asserts`
    arms the `checkify` assert mode (utils/checks.py): float/index/user
    checks *inside* scan-based train loops, which `jax_debug_nans` cannot
    see into."""
    if nan_check:
        import jax

        jax.config.update("jax_debug_nans", True)
        log.info("profiling: jax_debug_nans enabled")
    if check_asserts:
        from predictionio_tpu.utils import checks

        checks.enable(True)


class MetricsLogger:
    """Per-epoch structured metrics → stdout log + optional JSON-lines file.

    One record per `emit` call:
        {"ts": ..., "run": "...", "stage": "train", "step": 3,
         "rmse": 0.81, "epoch_time_s": 0.011}
    """

    def __init__(self, path: Optional[str] = None, run: str = ""):
        self.run = run
        self._path = path
        self._fh: Optional[TextIO] = None
        if path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._fh = open(path, "a", buffering=1)

    def emit(self, stage: str, step: Optional[int] = None,
             **metrics: Any) -> dict:
        record: dict[str, Any] = {"ts": time.time(), "stage": stage}
        if self.run:
            record["run"] = self.run
        if step is not None:
            record["step"] = step
        record.update(metrics)
        pretty = " ".join(
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in metrics.items())
        log.info("metrics[%s]%s %s", stage,
                 f" step={step}" if step is not None else "", pretty)
        if self._fh:
            json.dump(record, self._fh)
            self._fh.write("\n")
        return record

    def close(self) -> None:
        if self._fh:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NullMetricsLogger(MetricsLogger):
    """Emits to the python log only (no file); the default on a context."""

    def __init__(self):
        super().__init__(path=None)
