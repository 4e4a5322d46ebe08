"""`DeviceClock` against the trace, and the device's idle time by the
program's own spans: one traced run of a cell, by hand through the chip
tool, not part of the benchmark:

    python3 perf/tests/device_clock.py --workload als64.train10 --seed 2147485333

The program's `device_seconds_total{fn="als.train_steps"}`
(`telemetry/device.py`: the drain thread's block-until-ready delta of
every dispatch of the train loop) is read as the profiler starts and as
it stops, which is the measured window, and set against the trace's
`train.loop_busy_s` x iterations x calls. Then, for the window's first
call, the seconds in which no op ran on the device, booked to the
innermost of the program's spans that covered them
(`perf/program_trace.py`'s events, `perf/trace.py::idle_gaps_by_span`).
The run's result line is printed as `perf/run.py` prints it.
"""

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
FN = "als.train_steps"


def clock_reading() -> tuple[float, float]:
    """(seconds, dispatches) the device clock has booked to FN so far."""
    from predictionio_tpu.telemetry import device

    device.CLOCK.flush()
    time.sleep(0.05)  # the drain thread books after it takes an item
    return tuple(
        sum(value for labels, value in family.collect()
            if dict(zip(family.labelnames, labels))["fn"] == FN)
        for family in (device.DEVICE_SECONDS, device.DEVICE_DISPATCHES))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()

    from perf import harness, program_trace, trace

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find(bench["workloads"], args.workload, "workload")
    harness.prepare_environment(ROOT)
    devices = harness.require_chips(int(cell["chips"]))
    import jax

    readings = []
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def reading_then(fn):
        def hooked(*a, **k):
            readings.append(clock_reading())
            return fn(*a, **k)
        return hooked

    jax.profiler.start_trace = reading_then(start)
    jax.profiler.stop_trace = reading_then(stop)
    try:
        result = harness.run_cell(ROOT, bench, args.workload, args.seed,
                                  args.seconds, True, T0, devices)
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
    print(json.dumps(result), flush=True)

    (s0, n0), (s1, n1) = readings
    _, traffic = harness.load_cell(ROOT, bench, args.workload)
    busy = result["metrics"].get("train.loop_busy_s")
    if busy is None:
        harness.say("no device ops in the trace: nothing to compare")
        return 1
    traced = busy["value"] * int(traffic["iterations"]) * result["attempted"]
    harness.say(f"device clock: device_seconds_total{{fn={FN!r}}} rose by "
                f"{s1 - s0:.6f} s over {n1 - n0:.0f} dispatches in the "
                f"window; the trace's loop_busy_s x iterations x calls is "
                f"{traced:.6f} s; ratio {(s1 - s0) / traced:.6f}")

    view = program_trace.of_run()
    lo, hi = program_trace.calls_of(view["host"])[0]
    first = {"ops": {p: [e[:3] for e in ev]
                     for p, ev in view["ops"].items()},
             "host": [["window", lo, hi - lo]] + [
                 e for e in view["host"] if lo <= e[1] and e[1] + e[2] <= hi]}
    harness.say(f"first call {(hi - lo) / 1e9:.3f} s; device idle by the "
                "program's spans: " + ", ".join(
                    f"{name} {seconds:.3f}" for name, seconds
                    in trace.idle_gaps_by_span(first, 16)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
