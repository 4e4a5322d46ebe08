"""Seconds of the program's own spans (`telemetry/spans.py::span`, read
from the trace by `perf/program_trace.py`) inside each `perf:call` of
the window; the median over the calls.

`spans` lists the names (a trailing `*` matches a prefix). Plain: the
summed duration of the matching spans inside the call. With
`"self": true`: the call's wall minus the union of the matching spans,
what no listed span owns. Nothing where a call holds none of them."""

import statistics

from perf import program_trace, trace


def read(spec: dict, h):
    view = program_trace.of_run()
    if view is None:
        return None
    per_call = []
    for lo, hi in program_trace.calls_of(view["host"]):
        inside = program_trace.spans_inside(view["host"], lo, hi,
                                            spec["spans"])
        if not inside:
            return None
        if spec.get("self"):
            covered = sum(e - s for s, e in trace.union(inside))
            per_call.append((hi - lo - covered) / 1e9)
        else:
            per_call.append(sum(e - s for s, e in inside) / 1e9)
    return statistics.median(per_call) if per_call else None
