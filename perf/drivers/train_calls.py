"""Traffic kind `train_calls`: back-to-back `Algorithm.train(ctx, pd)` on
the seed's PreparedData, each against an empty bucket-cache directory (a
retrain on new events always misses that cache).

The traffic file gives `iterations` (put into the algorithm's parameters
under the configuration's `iterations_key`) and nothing else: one kind of
request, closed loop, one caller.
"""

from __future__ import annotations

import importlib
import os
import shutil
import statistics

from perf import data as perf_data
from perf import spans


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, harness):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.harness = harness
        self.iterations = int(traffic["iterations"])
        self.last_model = None

    def setup(self) -> None:
        """Ratings from the seed, the algorithm object and its context."""
        from predictionio_tpu.controller import WorkflowContext
        from predictionio_tpu.controller.params import params_from_dict
        from predictionio_tpu.data.bimap import BiMap

        cfg = self.config
        shape = cfg["shape"]
        with self.harness.setup_span("setup.data_s"):
            u, i, v = perf_data.make_ratings(shape, self.seed)
            n_users, n_items = perf_data.table_heights(shape)
            parts = {
                "user_idx": u, "item_idx": i, "values": v,
                "user_ids": BiMap({f"u{k}": k for k in range(n_users)}),
                "item_ids": BiMap({f"i{k}": k for k in range(n_items)}),
                "empty_dict": {},
            }
        self.data = parts
        engine = importlib.import_module(cfg["template"])
        self.pd = getattr(engine, cfg["prepared_data"])(
            **{field: parts[src]
               for field, src in cfg["prepared_data_fields"].items()})
        algo_cls = getattr(engine, cfg["algorithm"])

        def algorithm(iterations: int):
            params = dict(cfg["algorithm_params"])
            params[cfg["iterations_key"]] = iterations
            return algo_cls(params_from_dict(algo_cls.params_class, params))

        self.algorithm = algorithm
        self.algo = algorithm(self.iterations)
        # the program seeds jax.random.key with it: keep it in 31 bits
        self.ctx = WorkflowContext(seed=self.seed % (2 ** 31 - 1))
        self.cache_dir = os.path.join(os.environ["PIO_FS_BASEDIR"], "cache")

    def call(self):
        shutil.rmtree(self.cache_dir, ignore_errors=True)
        with self.harness.recorder.call():
            self.last_model = self.algo.train(self.ctx, self.pd)

    def end_to_end(self, calls: list[dict]) -> dict:
        """`train_call_s`: the median call of the window, a latency.
        `epoch_s`: a time per step, so all the loops' seconds of the
        window over all their iterations."""
        walls = [spans.seconds(c, "call") for c in calls]
        loops = [spans.seconds(c, "train_loop") for c in calls]
        return {"train_call_s": statistics.median(walls),
                "epoch_s": sum(loops) / (len(calls) * self.iterations)}

    def describe_call(self, call: dict) -> str:
        return (f"wall {spans.seconds(call, 'call'):.3f} s, loop "
                f"{spans.seconds(call, 'train_loop'):.3f} s")

    def check(self) -> list[dict]:
        """The last call's model against the reference. Its user table
        was solved from the item table of the iteration before, which a
        call does not return: one more train from the same seed, one
        iteration short, gives it (the bucket cache of the last call is
        left in place for it)."""
        check = self.config["check"]
        module = importlib.import_module(f"perf.checks.{check['kind']}")
        before = self.algorithm(self.iterations - 1).train(self.ctx, self.pd)
        return module.run(check, self.data, self.last_model,
                          before.item_factors, self.seed)
