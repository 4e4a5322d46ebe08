"""Traffic kind `fit_calls`: back-to-back `Algorithm.train(ctx, pd)` of
the sessionrec template on the seed's PreparedData: user histories from
`perf/sequences.py`, packed by the program into the configuration's
sequences, trained for the configured epochs, then the model build
(parameters to the host, every user's session vector).

The traffic file gives `steps`, the optimizer steps of one call, which
the driver checks against what the configuration's shape and step size
give, and `iterations`, the steps one execution of the step program
holds (one), which the readers divide an execution's device seconds by.
The program dispatches a call's steps back to back and waits once
(`engine._run_steps`, the span `train_loop`). One kind of request,
closed loop, one caller.
"""

from __future__ import annotations

import importlib
import os
import statistics

from perf import sequences, spans
from perf.harness import ROOT


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, harness):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.harness = harness
        self.steps = int(traffic["steps"])
        self.last_model = None

    def setup(self) -> None:
        """Histories from the seed, the algorithm object and its context."""
        try:  # before any work: a program from before the encoder
            importlib.import_module("predictionio_tpu.models.encoder")
        except ImportError as e:
            raise SystemExit(f"perf: this program has no config-driven "
                             f"encoder, the cell cannot run ({e})")
        import numpy as np

        from predictionio_tpu.controller import WorkflowContext
        from predictionio_tpu.controller.params import params_from_dict
        from predictionio_tpu.data.bimap import BiMap

        cfg = self.config
        shape = cfg["shape"]
        steps = (int(shape["n_sequences"]) // int(cfg["train"]["seqs_per_step"])
                 * int(cfg["algorithm_params"]["epochs"]))
        if steps != self.steps:
            raise SystemExit(f"perf: the traffic states {self.steps} "
                             f"steps a call, the configuration gives {steps}")
        with self.harness.setup_span("setup.data_s"):
            histories = sequences.make_histories(shape, self.seed)
            item_ids = BiMap({f"i{k}": k
                              for k in range(int(shape["n_items"]))})
            user_seqs = {f"u{k}": np.asarray(h, np.int32)
                         for k, h in enumerate(histories)}
        engine = importlib.import_module(cfg["template"])
        self.pd = engine.PreparedData(item_ids=item_ids, user_seqs=user_seqs)
        algo_cls = getattr(engine, cfg["algorithm"])
        params = dict(cfg["algorithm_params"])
        if not os.path.isabs(params["encoderConfig"]):
            params["encoderConfig"] = os.path.join(ROOT,
                                                   params["encoderConfig"])
        self.algo = algo_cls(params_from_dict(algo_cls.params_class, params))
        # the program seeds jax.random.key with it: keep it in 31 bits
        self.ctx = WorkflowContext(seed=self.seed % (2 ** 31 - 1))

    def call(self):
        with self.harness.recorder.call():
            self.last_model = self.algo.train(self.ctx, self.pd)

    def end_to_end(self, calls: list[dict]) -> dict:
        """`train_call_s`: the median call of the window, a latency.
        `epoch_s`: a time per step, so all the seconds of the window
        between a call's first step dispatched and its last one done,
        over all its steps."""
        walls = [spans.seconds(c, "call") for c in calls]
        loops = [spans.seconds(c, "train_loop") for c in calls]
        return {"train_call_s": statistics.median(walls),
                "epoch_s": sum(loops) / (len(calls) * self.steps)}

    def describe_call(self, call: dict) -> str:
        return (f"wall {spans.seconds(call, 'call'):.3f} s, steps "
                f"{spans.seconds(call, 'train_loop'):.3f} s")

    def check(self) -> list[dict]:
        """What the last call's first step reported, against the
        reference at the same weights and batch."""
        check = self.config["check"]
        module = importlib.import_module(f"perf.checks.{check['kind']}")
        return module.run(check, self.config, self.last_model, self.ctx.seed)
