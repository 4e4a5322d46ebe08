"""The controls of the encoder cell's `correct`, on the chip at the cell's
own size, run through `harness.run_cell` like the cell itself:

    python3 perf/tests/control_encoder.py --control bfloat16_reference --seed 7 --seconds 5

`--control bfloat16_reference` runs the cell from a configuration written
anew under `.pio_store/` with one key added, `check.control`: the check
then prints the program's own numbers and returns the reference's,
computed in bfloat16 throughout (the nearest precision below the
configuration's bfloat16 operands with float32 sums, router, softmax,
norms and loss) against the float32 reference. `--control half_batch`
breaks the timed path instead: the step program masks the second half of
every sequence, a fault the losses and the gradients have to show.
`--control unchanged` trains with a step size of zero: the state is left
as it was and `update_sign_max_wrong_share` reads 1. Each has to come
out as not correct. No CPU mode (`perf/tests/test_encoder_cell.py` holds
the three at the tiny size)."""

import argparse
import copy
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def half_batch(real):
    """`encoder.train_step` with the second half of every sequence
    masked as padding."""
    def train_step(cfg, lr):
        step = real(cfg, lr)

        def sessionrec_train_step(state, tokens, seg, pos):
            return step(state, tokens,
                        seg.at[:, seg.shape[1] // 2:].set(0), pos)

        return sessionrec_train_step

    return train_step


def main() -> int:
    from perf import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True, choices=(
        "bfloat16_reference", "half_batch", "unchanged"))
    ap.add_argument("--workload", default="joyai.fit8_pack8k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    bench = copy.deepcopy(harness.load_json(ROOT, "BENCHMARK.json"))
    cell = harness.find(bench["workloads"], args.workload, "workload")
    entry = harness.find(bench["configs"], cell["config"], "config")
    config = harness.load_json(ROOT, entry["file"])
    if args.control == "bfloat16_reference":
        config["check"]["control"] = args.control
    elif args.control == "unchanged":
        config["algorithm_params"]["stepSize"] = 0.0
    else:
        from predictionio_tpu.models import encoder

        encoder.train_step = half_batch(encoder.train_step)
    rel = os.path.join(".pio_store", "perf", "control",
                       f"{entry['name']}.json")
    config["algorithm_params"]["encoderConfig"] = rel
    os.makedirs(os.path.dirname(os.path.join(ROOT, rel)), exist_ok=True)
    with open(os.path.join(ROOT, rel), "w") as f:
        json.dump(config, f)
    entry["file"] = rel
    harness.prepare_environment(ROOT)
    devices = harness.require_chips(int(cell["chips"]))
    result = harness.run_cell(ROOT, bench, args.workload, args.seed,
                              args.seconds, False, T0, devices[:1])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
