"""Share of the traced calls' wall in which no operation ran on the
device, in percent: 1 - union of device-op intervals / wall of the calls,
over the host spans named `call` in the trace."""

from perf import trace


def read(spec: dict, h):
    calls = [(s, s + d) for n, s, d in h.trace["host"] if n == spec["span"]]
    if not calls or not h.trace["ops"]:
        return None
    return 100.0 * trace.idle_share(h.trace, calls)
