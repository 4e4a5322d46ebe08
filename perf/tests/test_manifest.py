"""BENCHMARK.json against the contract's limits, and every file a cell
needs found by the names in it."""

import importlib
import os
import re

import pytest

from perf.tests.conftest import ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["perf"] and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_every_cells_files_are_found(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        entry = configs[w["config"]]
        used.add(w["config"])
        assert entry["file"].startswith("perf/")
        config = load(entry["file"])
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        assert "assumed" in config
        traffic = load("perf", "traffic", w["traffic"] + ".json")
        importlib.import_module(f"perf.drivers.{traffic['driver']}")
        importlib.import_module(f"perf.checks.{config['check']['kind']}")
    assert used == set(configs)


def test_per_layer_metrics_have_a_file_a_reader_and_a_metric_to_move(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = load("perf", "layers", m["name"] + ".json")
        importlib.import_module(f"perf.readers.{spec['reader']}")
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
        assert m["moves"] in e2e and len(m["layer"]) <= 200


@pytest.mark.parametrize("kernel", ["gj_solve"])
def test_roofline_metrics_name_an_ops_file(bench, kernel):
    importlib.import_module(f"perf.ops.{kernel}")
    names = [m["name"] for m in bench["per_layer"] if "roofline" in m["name"]]
    assert names and all(m.endswith("_roofline") for m in names)
