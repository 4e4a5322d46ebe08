"""A kernel's share of its roofline, in percent: the least time the chip
could take for the operations and bytes the kernel's job needs
(perf/ops/<kernel>.py at the peaks of perf/peaks.json) over the summed
device time of the kernel's events inside the executions of one program.

The job is counted from the configuration, not from the kernel's calls:
an ALS iteration solves one system of order `rank` for every row of both
tables, however the program batches, pads or splits them."""

import importlib
import re

from perf import data, trace
from perf.harness import say


def read(spec: dict, h):
    ops = importlib.import_module(f"perf.ops.{spec['ops']}")
    names = h.config["trace_names"]
    runs = trace.union(trace.module_intervals(h.trace, names[spec["module"]]))
    rx = re.compile(names[spec["events"]])
    taken = sum(
        dur for name, start, dur in next(iter(h.trace["ops"].values()), [])
        if rx.search(name) and any(lo <= start < hi for lo, hi in runs)) / 1e9
    if not taken:
        return None
    rows = sum(data.table_heights(h.config["shape"]))
    rows *= len(runs) * int(h.traffic["iterations"])
    least, bound = ops.least_seconds(
        rows, int(h.config["algorithm_params"]["rank"]), h.peaks)
    say(f"{spec['ops']} roofline: least {least:.6f} s of {taken:.6f} s on "
        f"the device, bound by {bound}")
    return 100.0 * least / taken
