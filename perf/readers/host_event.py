"""Seconds of host events of the run's trace by name, inside each
`perf:call` of the window; the median over the calls.

`perf/program_trace.py` keeps the host events of the program whose names
start with the prefixes it lists; a span under another prefix
(`sessionrec.pack`) is in the same `.xplane.pb` and is read here, from
the file, once a run. `spans` lists the names (a trailing `*` matches a
prefix). Nothing where there is no trace, or a call holds none of them
(a program without the spans)."""

import functools
import statistics

from perf import program_trace


@functools.lru_cache(maxsize=2)
def _host_events(path: str):
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for plane in data.planes if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name == "perf:call" or "." in e.name]


def read(spec: dict, h):
    path = program_trace.latest()
    if path is None:
        return None
    host = _host_events(path)
    per_call = []
    for lo, hi in program_trace.calls_of(host):
        inside = program_trace.spans_inside(host, lo, hi, spec["spans"])
        if not inside:
            return None
        per_call.append(sum(e - s for s, e in inside) / 1e9)
    return statistics.median(per_call) if per_call else None
