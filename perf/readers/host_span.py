"""Median over the window's calls of a host span (see perf/spans.py for
what a span specification is). Nothing where a call lacks the span."""

import statistics

from perf import spans


def read(spec: dict, h):
    per_call = [spans.seconds(c, spec["span"]) for c in h.recorder.calls]
    if not per_call or any(v is None for v in per_call):
        return None
    return statistics.median(per_call)
