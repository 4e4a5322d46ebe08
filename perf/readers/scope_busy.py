"""Device-busy seconds of the ops under some of the program's named
scopes, inside the executions of one program, per unit of its work: the
same units as `device_busy` (`train.loop_busy_s`), so a set of
`scope_busy` metrics whose `scopes` partition `known` adds up to it.

`known` lists every scope the program opens in that module; an op
belongs to the innermost of them on its `op_name` path
(`perf/program_trace.py::scope_of`). `scopes` lists the ones this metric
reads; `null` among them reads the ops no known scope claims, and says
how much that is and which ops. Nothing where no op of the program
carries a known scope."""

from perf import program_trace, trace
from perf.harness import say


def read(spec: dict, h):
    view = program_trace.of_run()
    runs = trace.module_intervals(h.trace,
                                  h.config["trace_names"][spec["module"]])
    if view is None or not view["ops"] or not runs:
        return None
    runs = trace.union(runs)
    known, want = set(spec["known"]), set(spec["scopes"])
    inside = [(name, s, d, program_trace.scope_of(op_name, known))
              for name, s, d, op_name in next(iter(view["ops"].values()))
              if any(lo <= s < hi for lo, hi in runs)]
    if all(scope is None for *_, scope in inside):
        return None
    units = len(runs) * int(h.traffic.get(spec.get("per", ""), 1))

    def busy(events):
        return sum(e - s for s, e in trace.busy_intervals(events, runs)) / 1e9

    if None in want:
        unclaimed = [(n, s, d) for n, s, d, scope in inside if scope is None]
        sums: dict[str, float] = {}
        for n, _, d in unclaimed:
            if not trace._CONTAINER.match(n):
                sums[n] = sums.get(n, 0.0) + d / 1e9
        top = sorted(sums.items(), key=lambda kv: -kv[1])[:5]
        say(f"scope_busy: ops of {spec['module']} under no known scope "
            f"{busy(unclaimed) / units:.6f} s a unit; largest: "
            + ", ".join(f"{n} {t / units:.6f}" for n, t in top))
    return busy([(n, s, d) for n, s, d, scope in inside
                 if scope in want]) / units
