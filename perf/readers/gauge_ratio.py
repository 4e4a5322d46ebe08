"""A ratio of two of the program's gauges (`telemetry/registry.py`'s
REGISTRY), each summed over its label values, in percent. Nothing where
the program has no such gauge or the denominator is zero."""


def read(spec: dict, h):
    from predictionio_tpu.telemetry.registry import REGISTRY

    sums = []
    for name in (spec["numerator"], spec["denominator"]):
        family = REGISTRY.get(name)
        if family is None:
            return None
        sums.append(sum(value for _, value in family.collect()))
    return 100.0 * sums[0] / sums[1] if sums[1] else None
