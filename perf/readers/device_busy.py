"""Device-busy seconds inside the executions of one program (matched by
the name the trace gives its module), per unit of its work: the traffic
file's `per` key (iterations of a train call) times the executions."""

from perf import trace


def read(spec: dict, h):
    runs = trace.module_intervals(h.trace, h.config["trace_names"][spec["module"]])
    if not runs:
        return None
    units = len(runs) * int(h.traffic.get(spec.get("per", ""), 1))
    return trace.busy_seconds(h.trace, runs) / units
