"""One run of one cell: set-up, warm-up, the measured window, the check.

Driven by data. The cell's entry in `BENCHMARK.json` names a
configuration and a traffic mix; the configuration's file and
`perf/traffic/<traffic>.json` say the rest. The traffic file names its
driver (`perf/drivers/<kind>.py`), each per-layer metric has a file
`perf/layers/<metric>.json` that names its reader
(`perf/readers/<reader>.py`). Nothing here lists cells, configurations
or metrics.

`main` is the command's entry and looks for the chip first. `run_cell`
is the rest of a run and is what the tests drive on the CPU at a tiny
configuration of their own.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# A window closes when its seconds have passed and it holds this many
# calls: a median of two is a mean, and hands one stalled call to the run.
LEAST_CALLS = 3


def say(msg: str) -> None:
    print(f"[perf] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"perf: no {what} named {name!r} in BENCHMARK.json")


class Compiles:
    """Every program JAX builds or loads in this process, by the runtime's
    own event: (seconds since start, duration)."""

    def __init__(self):
        import jax

        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def between(self, t_lo: float, t_hi: float) -> list[float]:
        return [d for t, d in self.events if t_lo <= t <= t_hi]


class Harness:
    """What a driver and the readers see of the run."""

    def __init__(self, config: dict, traffic: dict, peaks: dict | None,
                 trace: bool, t0: float):
        from perf import spans

        self.config, self.traffic, self.peaks, self.t0 = (
            config, traffic, peaks, t0)
        self.recorder = spans.Recorder(annotate=trace)
        self.compiles = Compiles()
        self.setup_spans: dict[str, float] = {}
        self.trace = None  # the reduced trace of a --trace 1 run
        self.window = (0.0, 0.0)
        self.setup_end = 0.0

    @contextlib.contextmanager
    def setup_span(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.setup_spans[name] = (self.setup_spans.get(name, 0.0)
                                      + time.perf_counter() - t)


def load_cell(root: str, bench: dict, workload: str):
    """(configuration, traffic mix) of a cell, from the files its entry in
    `BENCHMARK.json` names."""
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "config")
    return (load_json(root, entry["file"]),
            load_json(root, "perf", "traffic", cell["traffic"] + ".json"))


def prepare_environment(root: str) -> None:
    """Before jax is imported: every cache the program or JAX keeps goes
    to a fixed place inside the checkout."""
    os.environ["PIO_FS_BASEDIR"] = os.path.join(root, ".pio_store", "perf")
    if root not in sys.path:
        sys.path.insert(0, root)
    from predictionio_tpu.utils import compile_cache

    compile_cache.configure()


def require_chips(n: int):
    """The devices of the run, or exit without a result where JAX finds no
    TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        sys.stderr.write(
            f"perf: cell needs {n} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform!r}. No result.\n")
        raise SystemExit(3)
    return devices


def memory_peak_bytes(devices) -> int:
    """Peak of the fullest chip. This runtime books a program's scratch
    (its temporaries) under `reserved`, apart from the buffers `in_use`
    (PERF.md, Findings of PR 23), so the peak is the two together."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)))
    return peak


def run_cell(root: str, bench: dict, workload: str, seed: int,
             seconds: float, trace: bool, t0: float, devices,
             peaks: dict | None = None) -> dict:
    import jax

    from perf import spans, trace as trace_mod

    # small programs persist too: a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    config, traffic = load_cell(root, bench, workload)
    kind = devices[0].device_kind
    if peaks is None:  # the tests bring their own: no CPU is in the table
        table = load_json(root, "perf", "peaks.json")
        if kind not in table:
            raise SystemExit(f"perf: no peaks for device kind {kind!r} in "
                             f"perf/peaks.json")
        peaks = table[kind]
    say(f"device platform={devices[0].platform} device_kind={kind!r} "
        f"count={len(devices)}; cell {workload} seed {seed} "
        f"seconds {seconds} trace {int(trace)}")

    h = Harness(config, traffic, peaks, trace, t0)
    driver_mod = importlib.import_module(f"perf.drivers.{traffic['driver']}")
    driver = driver_mod.Driver(config, traffic, seed, h)

    span_targets = dict(config["spans"]["always"])
    if trace:
        span_targets.update(config["spans"]["traced"])
    undo = [spans.wrap(h.recorder, name, target)
            for name, target in span_targets.items()]
    try:
        driver.setup()
        with h.setup_span("setup.warmup_call_s"):
            driver.call()
        h.recorder.calls.clear()
        h.setup_end = time.perf_counter()
        setup_s = h.setup_end - t0
        say(f"set-up {setup_s:.3f} s: " + ", ".join(
            f"{k} {v:.3f}" for k, v in h.setup_spans.items()))

        trace_dir = os.path.join(os.environ["PIO_FS_BASEDIR"], "trace",
                                 workload)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        w0 = time.perf_counter()
        try:
            with (jax.profiler.TraceAnnotation("perf:window") if trace
                  else contextlib.nullcontext()):
                while True:
                    driver.call()
                    say(f"call {len(h.recorder.calls)}: "
                        + driver.describe_call(h.recorder.calls[-1]))
                    if (time.perf_counter() - w0 >= seconds
                            and len(h.recorder.calls) >= LEAST_CALLS):
                        break
        finally:
            w1 = time.perf_counter()
            if trace:
                jax.profiler.stop_trace()
        h.window = (w0, w1)
    finally:
        for put_back in undo:
            put_back()
    calls = h.recorder.calls
    say(f"window {w1 - w0:.3f} s, {len(calls)} calls")

    window_compiles = h.compiles.between(w0, w1)
    # read before the check, whose own train is not the window's
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(devices)}
    t_check = time.perf_counter()
    numbers = driver.check()
    say(f"the check took {time.perf_counter() - t_check:.3f} s")
    numbers.append({"name": "window_compiles",
                    "value": len(window_compiles), "limit": 0})
    correct = True
    for n in numbers:
        held = n["limit"] is not None
        ok = (not held) or n["value"] <= n["limit"]
        correct = correct and ok
        say(f"check {n['name']}: {n['value']!r} "
            + (f"limit {n['limit']!r} {'ok' if ok else 'FAILED'}" if held
               else "(not held)"))

    metrics: dict = {}
    if not trace:
        values = driver.end_to_end(calls)
        values["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        h.trace = trace_mod.load(trace_mod.find_xplane(trace_dir))
        say(f"trace kept at {os.path.relpath(trace_dir, root)}")
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            spec = load_json(root, "perf", "layers", m["name"] + ".json")
            reader = importlib.import_module(f"perf.readers.{spec['reader']}")
            value = reader.read(spec, h)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": bool(correct), "attempted": len(calls),
              "failed": 0, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_mod.busy_seconds(h.trace)
        device["window_s"] = w1 - w0
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(h.trace),
            "idle_gaps": trace_mod.idle_gaps_by_span(h.trace)}
    return result


def main(argv: list[str], t0: float) -> int:
    ap = argparse.ArgumentParser(prog="perf/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    prepare_environment(ROOT)
    devices = require_chips(int(cell["chips"]))
    result = run_cell(ROOT, bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t0, devices[:int(cell["chips"])])
    print(json.dumps(result), flush=True)
    return 0
