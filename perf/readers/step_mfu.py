"""Model FLOP/s utilization of a train step, in percent: the operations
the step's forward and backward passes require
(`perf/ops/<ops>.py::model_flops(config)`, recomputation not counted)
over the device-busy seconds of a step (an execution of the program
that runs them, over the traffic file's `per` steps of it) and the chip's
peak. Nothing where the trace holds no such program."""

import importlib

from perf import trace
from perf.harness import say


def read(spec: dict, h):
    runs = trace.module_intervals(
        h.trace, h.config["trace_names"][spec["module"]])
    if not runs:
        return None
    busy = trace.busy_seconds(h.trace, runs) / (
        len(runs) * int(h.traffic.get(spec.get("per", ""), 1)))
    ops = importlib.import_module(f"perf.ops.{spec['ops']}")
    flops = ops.model_flops(h.config)
    say(f"{spec['ops']}: {flops:.4e} model operations a step, "
        f"{busy:.6f} s busy a step")
    return 100.0 * flops / (busy * h.peaks["flops_per_s"]) if busy else None
