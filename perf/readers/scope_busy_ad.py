"""`scope_busy` for a program that is differentiated: device-busy seconds
of the ops under some of the program's named scopes, inside the
executions of one program, per unit of its work.

Autodiff wraps a scope's name on an op's `op_name` path
(`jvp(enc.mla)`, `transpose(jvp(enc.mla))`) and `scope_busy` looks for
whole path parts, so it would see the recomputed forward pass only. Here
an op belongs to the known scope whose name stands last in its
`op_name`, wrapped or not. `known` lists every scope the program opens
in that module, `scopes` the ones this metric reads. Nothing where no op
of the program carries a known scope."""

import re

from perf import program_trace, trace


def scope_of(op_name: str, known) -> str | None:
    best, at = None, -1
    for name in known:
        for m in re.finditer(r"(?<![\w.])" + re.escape(name) + r"(?![\w.])",
                             op_name):
            if m.start() > at:
                best, at = name, m.start()
    return best


def read(spec: dict, h):
    view = program_trace.of_run()
    runs = trace.module_intervals(h.trace,
                                  h.config["trace_names"][spec["module"]])
    if view is None or not view["ops"] or not runs:
        return None
    runs = trace.union(runs)
    known, want = list(spec["known"]), set(spec["scopes"])
    cache: dict = {}
    inside = []
    for name, s, d, op_name in next(iter(view["ops"].values())):
        if any(lo <= s < hi for lo, hi in runs):
            if op_name not in cache:
                cache[op_name] = scope_of(op_name, known)
            inside.append((name, s, d, cache[op_name]))
    if all(scope is None for *_, scope in inside):
        return None
    units = len(runs) * int(h.traffic.get(spec.get("per", ""), 1))

    mine = [(n, s, d) for n, s, d, scope in inside if scope in want]
    return sum(e - s for s, e in trace.busy_intervals(mine, runs)) / 1e9 / units
