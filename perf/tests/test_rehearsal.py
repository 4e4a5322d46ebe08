"""The rest of a run, driven on the CPU past the harness's look for a chip,
at tiny configurations of the tests' own (perf/tests/tiny/): the drivers,
the `correct` checks, their control and a timed path broken underneath.
No number of these runs is a measurement."""

import copy
import dataclasses
import time

import numpy as np
import pytest

from perf import harness
from perf.tests.conftest import ROOT

PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


@pytest.fixture()
def run(bench, tmp_path, monkeypatch):
    tiny = copy.deepcopy(bench)
    for c in tiny["configs"]:
        c["file"] = f"perf/tests/tiny/{c['name']}.json"
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    # the program keeps its train loops by configuration; one kept from an
    # earlier test would carry that test's span wrapper
    from predictionio_tpu.ops import als

    als._get_train_loop.cache_clear()

    def run_cell(workload, trace=False, seed=2 ** 31 + 17):
        import jax

        return harness.run_cell(ROOT, tiny, workload, seed, 0.3, trace,
                                time.perf_counter(), jax.devices()[:1],
                                peaks=PEAKS)

    return run_cell


def numbers_of(capsys) -> dict:
    out = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("[perf] check "):
            name, rest = line[len("[perf] check "):].split(": ", 1)
            out[name] = rest
    return out


@pytest.mark.parametrize("workload", ["als64.train10", "als128i.train10"])
def test_a_sound_run_is_correct_and_reports_its_end_to_end_metrics(
        run, bench, workload):
    result = run(workload)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= harness.LEAST_CALLS
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_a_traced_run_reports_the_host_spans_and_the_counters(run):
    result = run("als64.train10", trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    for name in ("train.bucketize_s", "train.seen_items_s",
                 "train.device_put_s", "train.readback_s", "setup.data_s",
                 "setup.warmup_call_s", "setup.compile_s"):
        assert got[name]["value"] > 0, name
    assert got["setup.window_compiles"]["value"] == 0
    # no device plane in a CPU trace: the device readers find nothing to
    # read and their metrics are left out of the line
    assert "train.device_idle" not in got and "train.gj_roofline" not in got
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_pallas_solver_in_interpret_mode_is_correct(run, monkeypatch):
    from predictionio_tpu.ops import als

    monkeypatch.setattr(
        als, "resolve_solver",
        lambda cfg: dataclasses.replace(cfg, solver="gj", pallas="interpret"))
    assert run("als64.train10")["correct"] is True


def test_the_lower_precision_control_is_not_correct(run, monkeypatch, capsys):
    """The program's own lower-precision path (bf16 Gram/RHS operands),
    switched on underneath: on a CPU a matmul precision changes nothing,
    so this is the control a test run can hold; on the chip it is
    perf/tests/control_precision.py."""
    from predictionio_tpu.ops import als

    resolve = als.resolve_solver
    monkeypatch.setattr(
        als, "resolve_solver",
        lambda cfg: resolve(dataclasses.replace(cfg,
                                                compute_dtype="bfloat16")))
    result = run("als64.train10")
    assert result["correct"] is False
    assert "FAILED" in numbers_of(capsys)["item_rows_median_rel_err"]


def _unchanged_state(real):
    def als_train(user_idx, item_idx, ratings, n_users, n_items, cfg, **kw):
        out = real(user_idx, item_idx, ratings, n_users, n_items,
                   dataclasses.replace(cfg, iterations=1), **kw)
        rng = np.random.default_rng(0)
        out.user_factors = np.zeros_like(out.user_factors)
        out.item_factors = rng.standard_normal(
            out.item_factors.shape).astype(np.float32)
        out.epoch_times = out.epoch_times * cfg.iterations
        return out
    return als_train


def _part_of_the_batch_left_out(real):
    def als_train(user_idx, item_idx, ratings, *args, **kw):
        keep = len(ratings) * 4 // 5
        return real(user_idx[:keep], item_idx[:keep], ratings[:keep],
                    *args, **kw)
    return als_train


@pytest.mark.parametrize("broken", [_unchanged_state,
                                    _part_of_the_batch_left_out])
def test_a_broken_timed_path_is_not_correct(run, monkeypatch, broken):
    from predictionio_tpu.templates.recommendation import engine

    monkeypatch.setattr(engine, "als_train", broken(engine.als_train))
    assert run("als64.train10")["correct"] is False
