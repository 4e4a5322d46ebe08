"""The controls of `lfm2.fit8_pack8k`'s `correct`, on the chip at the
cell's own size, run through `harness.run_cell` like the cell itself:

    python3 perf/tests/control_lfm2.py --control bfloat16_reference,no_in_gate --seed 7 --seconds 5

The cell runs from a configuration written anew under `.pio_store/` with
one key changed. The reference's controls add `check.control`, one name
or several with commas between (one set-up and one window for all of
them): the check then prints the program's own numbers and, for each
control, those of a reference that is wrong on purpose against the
sound one, with its verdict: `bfloat16_reference` (computed in bfloat16
throughout: the nearest precision below the configuration's),
`no_reset_reference` (the taps read across history boundaries),
`no_in_gate` (conv(x) for conv(B * x)), `no_out_gate` (conv(..) for C *
conv(..)), `gates_swapped` (C before the convolution, B after it),
`silu_on_taps` (SiLU of the convolution's sum, as every recurrent
mixer's short convolution has it), `no_qk_norm` (q and k rotated as
projected), `norm_after_rotation` (the two norms after RoPE),
`interleaved_pairs` (RoPE over (x[2i], x[2i+1]) for (x[i], x[i + 32])).
`--control unchanged` trains with a step size of zero: the state is left
as it was and `update_sign_max_wrong_share` reads 1. Each has to come
out as not correct; of several the run returns the one that came
nearest to passing. No CPU mode (`perf/tests/test_lfm2_cell.py` holds
them at the tiny size)."""

import argparse
import copy
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

REFERENCE_CONTROLS = ("bfloat16_reference", "no_reset_reference", "no_in_gate",
                      "no_out_gate", "gates_swapped", "silu_on_taps",
                      "no_qk_norm", "norm_after_rotation",
                      "interleaved_pairs")


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
    ap.add_argument("--workload", default="lfm2.fit8_pack8k")
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
