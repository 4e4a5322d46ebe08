"""The benchmark's own tests: `python -m pytest perf/tests`, CPU only."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def bench():
    return load("BENCHMARK.json")


@pytest.fixture(scope="session")
def tiny_shape():
    return load("perf", "tests", "tiny", "als64_ml20m.json")["shape"]
