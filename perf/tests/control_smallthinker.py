"""The controls of `smallthinker.fit8_pack8k`'s `correct`, on the chip at
the cell's own size, run through `harness.run_cell` like the cell itself:

    python3 perf/tests/control_smallthinker.py --control bfloat16_reference,silu_gate --seed 7 --seconds 5

The cell runs from a configuration written anew under `.pio_store/` with
one key changed. The reference's controls add `check.control`, one name
or several with commas between (one set-up and one window for all of
them): the check then prints the program's own numbers and, for each
control, those of a reference that is wrong on purpose against the
sound one, with its verdict: `bfloat16_reference` (computed in bfloat16
throughout: the nearest precision below the configuration's),
`sigmoid_scores` (the picked logits through a sigmoid, normalised, in
place of the softmax over the picked), `silu_gate` (SiLU in place of
ReLU), `router_after_attention` (the router fed the normed
post-attention stream the experts read), `no_window_reference` (no
window in the windowed layers), `rotate_full` (the full layer rotated
too). `--control unchanged` trains with a step size of zero: the state
is left as it was and `update_sign_max_wrong_share` reads 1. Each has to
come out as not correct; of several the run returns the one that came
nearest to passing. No CPU mode (`perf/tests/test_smallthinker_cell.py`
holds them at the tiny size)."""

import argparse
import copy
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

REFERENCE_CONTROLS = ("bfloat16_reference", "sigmoid_scores", "silu_gate",
                      "router_after_attention", "no_window_reference",
                      "rotate_full")


def controlled(config: dict, control: str) -> dict:
    """The configuration with the control's one key changed."""
    config = copy.deepcopy(config)
    if control == "unchanged":
        config["algorithm_params"]["stepSize"] = 0.0
    else:
        unknown = set(control.split(",")) - set(REFERENCE_CONTROLS)
        if unknown:
            raise SystemExit(f"no control named {sorted(unknown)}: "
                             f"{REFERENCE_CONTROLS + ('unchanged',)}")
        config["check"]["control"] = control
    return config


def main() -> int:
    from perf import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True)
    ap.add_argument("--workload", default="smallthinker.fit8_pack8k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    bench = copy.deepcopy(harness.load_json(ROOT, "BENCHMARK.json"))
    cell = harness.find(bench["workloads"], args.workload, "workload")
    entry = harness.find(bench["configs"], cell["config"], "config")
    config = controlled(harness.load_json(ROOT, entry["file"]), args.control)
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
