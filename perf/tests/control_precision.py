"""The control of `correct`, on the chip at a cell's own size: the program
with the Gram/RHS products of its normal equations at a matmul precision
below the one the configuration states, and the numbers the check then
reads. Not run by the benchmark; run by hand through the chip tool:

    python3 perf/tests/control_precision.py --workload als64.train10 \
        --precision high --seeds 11 12 13

`--precision highest` is the program as it is (sound readings, many seeds
in one process); `high` is three bf16 passes, the nearest precision below
float32 at `highest` and the step a later PR would be tempted by;
`default` is one bf16 pass. One train call of the cell's traffic per seed,
no measured window: a train's readings need none.

Each line also says what the train loop's program holds on the device:
the bytes in use as it is entered, the scratch, arguments and outputs the
compiler planned for it (`memory_analysis`), and the allocator's peaks
after the call. `memory_peak_bytes` of a run rests on these (PERF.md).
"""

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def at_precision(name: str):
    """Put `ops/als.py::normal_eq_einsum` at the named precision."""
    import functools

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als

    precision = {"highest": jax.lax.Precision.HIGHEST,
                 "high": jax.lax.Precision.HIGH,
                 "default": jax.lax.Precision.DEFAULT}[name]
    als.normal_eq_einsum = lambda compute_dtype: functools.partial(
        jnp.einsum, preferred_element_type=jnp.float32, precision=precision)
    als._get_train_loop.cache_clear()


def _stats() -> dict:
    import jax

    return jax.devices()[0].memory_stats() or {}


class LoopMemory:
    """Hooks `metered_jit`: device bytes in use as the program of `label`
    is entered, and the shapes it was entered with."""

    def __init__(self, want: str):
        from predictionio_tpu.utils import profiling

        self.entered: dict = {}
        orig = profiling.metered_jit

        def hook(fn, label=None, **kw):
            jitted = orig(fn, label=label, **kw)
            if label != want:
                return jitted

            def probed(*args):
                import jax

                self.entered = {
                    "jitted": jitted.jitted,
                    "specs": jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=x.sharding), args),
                    "in_use": _stats().get("bytes_in_use")}
                return jitted(*args)

            probed.jitted = jitted.jitted
            return probed

        profiling.metered_jit = hook

    def read(self) -> dict:
        t = time.perf_counter()
        planned = self.entered["jitted"].lower(
            *self.entered["specs"]).compile().memory_analysis()
        stats = _stats()
        return {"in_use_entering_loop": self.entered["in_use"],
                "loop_temp": planned.temp_size_in_bytes,
                "loop_arguments": planned.argument_size_in_bytes,
                "loop_outputs": planned.output_size_in_bytes,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
                "bytes_reserved": stats.get("bytes_reserved"),
                "bytes_limit": stats.get("bytes_limit"),
                "memory_analysis_took_s": time.perf_counter() - t}


def main() -> int:
    from perf import harness, spans

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", required=True,
                    choices=("highest", "high", "default"))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find(bench["workloads"], args.workload, "workload")
    harness.prepare_environment(ROOT)
    devices = harness.require_chips(int(cell["chips"]))
    config, traffic = harness.load_cell(ROOT, bench, args.workload)
    import importlib

    at_precision(args.precision)
    loop_memory = LoopMemory("als.train_steps")
    driver_mod = importlib.import_module(f"perf.drivers.{traffic['driver']}")
    for seed in args.seeds:
        h = harness.Harness(config, traffic, None, False, T0)
        driver = driver_mod.Driver(config, traffic, seed, h)
        undo = [spans.wrap(h.recorder, n, t)
                for n, t in config["spans"]["always"].items()]
        try:
            driver.setup()
            driver.call()
        finally:
            for put_back in undo:
                put_back()
        memory = loop_memory.read()
        t_check = time.perf_counter()
        numbers = {n["name"]: n["value"] for n in driver.check()}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision,
                          "device": devices[0].device_kind,
                          "call": driver.describe_call(h.recorder.calls[-1]),
                          "check_took_s": time.perf_counter() - t_check,
                          "memory": memory, "numbers": numbers}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
