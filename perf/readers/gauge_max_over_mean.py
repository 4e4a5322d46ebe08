"""The largest value of one of the program's gauges over the mean of its
values, across its label values (`telemetry/registry.py`'s REGISTRY): 1
is an even load. Nothing where the program has no such gauge or it holds
no value above zero."""


def read(spec: dict, h):
    from predictionio_tpu.telemetry.registry import REGISTRY

    family = REGISTRY.get(spec["gauge"])
    if family is None:
        return None
    values = [value for _, value in family.collect()]
    if not values or sum(values) <= 0:
        return None
    return max(values) * len(values) / sum(values)
