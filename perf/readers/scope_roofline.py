"""A scope's share of its roofline, in percent: the least time the chip
could take for the operations and bytes that the scope's job needs a
step (`perf/ops/<ops>.py::least_seconds(config, peaks)`, counted from
the configuration) over the device-busy seconds a step of the ops under
the scope (`scope_busy_ad`, which this reader asks). For work that is
plain `jax.numpy` under a named scope and no kernel of a name; the
`roofline` reader sums a kernel's events and counts ALS tables. Nothing
where the trace holds no op under the scope."""

import importlib

from perf.harness import say
from perf.readers import scope_busy_ad


def read(spec: dict, h):
    busy = scope_busy_ad.read(spec, h)
    if not busy:
        return None
    ops = importlib.import_module(f"perf.ops.{spec['ops']}")
    least, bound = ops.least_seconds(h.config, h.peaks)
    say(f"{spec['ops']} roofline: least {least:.6f} s of {busy:.6f} s busy "
        f"a step, bound by {bound}")
    return 100.0 * least / busy
