"""Seconds of one of the harness's set-up spans, by its name."""


def read(spec: dict, h):
    return h.setup_spans.get(spec["span"])
