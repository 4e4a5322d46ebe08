"""The benchmark's command:

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell once on the TPU this machine holds and prints, as the last
line of its standard output, one JSON object with the run's result. It
exits non-zero, with no result, where JAX finds no TPU: there is no CPU
mode. See perf/README.md.
"""

import time

T0 = time.perf_counter()  # set-up counts from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from perf import harness

    sys.exit(harness.main(sys.argv[1:], T0))
