"""The encoder cell on the CPU at `perf/tests/tiny/`: a rehearsal of a
sound run, the per-cell manifest test (what the driver will expect of a
traced run's last line, for every cell), the lower-precision control and
broken timed paths, the sequence generator, the operations count and the
readers this cell brought. No number of these runs is a measurement."""

import copy
import importlib
import json
import time

import numpy as np
import pytest

from perf import harness, sequences
from perf.ops import encoder_step
from perf.readers import gauge_max_over_mean, scope_busy_ad
from perf.tests import control_encoder
from perf.tests.conftest import ROOT, load

PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
CELL = "joyai.fit8_pack8k"


@pytest.fixture()
def run(bench, tmp_path, monkeypatch):
    tiny = copy.deepcopy(bench)
    for c in tiny["configs"]:
        c["file"] = f"perf/tests/tiny/{c['name']}.json"
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    # programs kept from an earlier test would carry its span wrapper
    from predictionio_tpu.ops import als
    from predictionio_tpu.templates.sessionrec import engine

    als._get_train_loop.cache_clear()
    engine._train_programs.cache_clear()
    engine._encoder_config.cache_clear()

    def run_cell(workload, trace=False, seed=2 ** 31 + 17, bench=tiny):
        import jax

        return harness.run_cell(ROOT, bench, workload, seed, 0.3, trace,
                                time.perf_counter(), jax.devices()[:1],
                                peaks=PEAKS)

    run_cell.bench = tiny
    return run_cell


def expected_per_layer(bench: dict, workload: str) -> list[dict]:
    """The per-layer metrics the driver expects in the last line of a
    traced run of the cell: those that list it, and those without a list
    whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def test_a_sound_run_is_correct_and_reports_its_end_to_end_metrics(
        run, bench):
    result = run(CELL)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= harness.LEAST_CALLS
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      load("BENCHMARK.json")["workloads"]])
def test_a_traced_rehearsal_holds_every_metric_the_driver_will_expect(
        run, bench, workload):
    """The test PR 26 lacked. A CPU trace has no device plane, so a
    metric read from the device's ops cannot be in a rehearsal's line:
    for those the test holds what their readers will look up."""
    result = run(workload, trace=True)
    assert result["correct"] is True
    want = expected_per_layer(bench, workload)
    assert len(want) >= 6
    config, traffic = harness.load_cell(ROOT, run.bench, workload)
    for m in want:
        spec = load("perf", "layers", m["name"] + ".json")
        if m["source"] != "device_trace":
            assert m["name"] in result["metrics"], m["name"]
            continue
        assert m["name"] not in result["metrics"]
        if "module" in spec:
            assert spec["module"] in config["trace_names"], m["name"]
        if "per" in spec:
            assert int(traffic[spec["per"]]) > 0, m["name"]
    assert set(result["metrics"]) <= {m["name"] for m in want}
    assert {"busy_s", "window_s"} <= set(result["device"])


def test_the_new_cell_reports_the_six_shared_metrics_and_its_own(bench):
    names = {m["name"] for m in expected_per_layer(bench, CELL)}
    assert {"train.device_idle", "train.loop_busy_s", "setup.data_s",
            "setup.warmup_call_s", "setup.compile_s",
            "setup.window_compiles"} <= names
    assert {n for n in names if n.startswith("fit.")} == {
        "fit.pack_s", "fit.init_s", "fit.readback_s", "fit.session_vecs_s",
        "fit.mla_s", "fit.moe_s", "fit.mtp_s", "fit.head_loss_s",
        "fit.adam_s", "fit.pack_fill", "fit.expert_load_max_over_mean",
        "fit.step_mfu"}
    assert not any(n.startswith("train.bucket") for n in names)
    for als in ("als64.train10", "als128i.train10"):
        assert len(expected_per_layer(bench, als)) == 21


ALS_CELLS = ["als64.train10", "als128i.train10"]


def test_the_als_metrics_kept_their_entries_and_gained_only_the_list(bench):
    """What `test_program_trace.py`'s manifest test held of PR 24's ten
    metrics, which fails since this PR gave them a `workloads` key and
    put twelve entries after them (PERF.md, Open questions): still in
    the manifest in their order, each with its file, one of the three
    readers and the source that goes with it. Besides: the fifteen
    entries that read ALS spans list the two ALS cells and nothing else
    about them changed; the six shared ones have no list."""
    from perf.tests.test_program_trace import NEW

    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert names[-22:-12] == NEW
    for name in NEW:
        spec = load("perf", "layers", name + ".json")
        assert spec["reader"] in ("program_span", "scope_busy", "gauge_ratio")
        assert entries[name]["source"] == {
            "program_span": "program_span", "scope_busy": "device_trace",
            "gauge_ratio": "program_counter"}[spec["reader"]]
    listed = [m for m in bench["per_layer"][:-12] if "workloads" in m]
    assert len(listed) == 15 and set(NEW) <= {m["name"] for m in listed}
    for m in listed:
        assert m["workloads"] == ALS_CELLS
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert {m["name"] for m in bench["per_layer"][:-12]
            if "workloads" not in m} == {
        "train.device_idle", "train.loop_busy_s", "setup.data_s",
        "setup.warmup_call_s", "setup.compile_s", "setup.window_compiles"}


def test_the_unchanged_state_control_is_not_correct(run, tmp_path, capsys):
    """`perf/tests/control_encoder.py --control unchanged`, as a CPU run
    holds it: a step size of zero leaves every report block where it
    was, and the share of updates with the wrong sign reads 1."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], "joyai_llm_flash_1of16", "config")
    config = load(entry["file"])
    config["algorithm_params"]["stepSize"] = 0.0
    path = tmp_path / "control.json"
    config["algorithm_params"]["encoderConfig"] = str(path)
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    assert run(CELL, bench=bench)["correct"] is False
    out = capsys.readouterr().out
    assert "check update_sign_max_wrong_share: 1.0 limit 0.1 FAILED" in out


def test_the_reference_in_bfloat16_is_not_correct(run, tmp_path, capsys):
    """`perf/tests/control_encoder.py --control bfloat16_reference`, as a
    CPU run holds it: the check returns the numbers of the reference
    computed in bfloat16 against the float32 reference, and prints the
    program's own."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], "joyai_llm_flash_1of16", "config")
    config = load(entry["file"])
    config["check"]["control"] = "bfloat16_reference"
    path = tmp_path / "control.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    assert run(CELL, bench=bench)["correct"] is False
    out = capsys.readouterr().out
    assert "the program's numbers: ce_rel_err" in out
    assert "bfloat16 reference: block router" in out


def test_a_program_without_the_encoder_exits_before_any_work(monkeypatch):
    """What the parent of PR 27 does when asked for this cell."""
    import sys

    from perf.drivers import fit_calls

    monkeypatch.setitem(sys.modules, "predictionio_tpu.models.encoder", None)
    config = load("perf", "tests", "tiny", "joyai_llm_flash_1of16.json")
    driver = fit_calls.Driver(config, load("perf", "traffic",
                                           "fit8_pack8k.json"), 1, None)
    with pytest.raises(SystemExit, match="no config-driven encoder"):
        driver.setup()


def _no_update(real):
    def train_step(cfg, lr):
        return real(cfg, 0.0)
    return train_step


@pytest.mark.parametrize("broken", [_no_update, control_encoder.half_batch])
def test_a_broken_timed_path_is_not_correct(run, monkeypatch, broken):
    from predictionio_tpu.models import encoder

    monkeypatch.setattr(encoder, "train_step", broken(encoder.train_step))
    assert run(CELL)["correct"] is False


# -- the sequence generator -----------------------------------------------------

@pytest.fixture(scope="module")
def shape():
    return load("perf", "configs", "joyai_llm_flash_1of16.json")["shape"]


def test_the_lengths_fill_whole_sequences_whatever_the_seed(shape):
    lengths = sequences.history_lengths(shape)
    assert lengths.sum() == 16 * 8192 and 880 <= len(lengths) <= 920
    assert lengths.min() >= 20 and lengths.max() <= 8192
    assert 60 <= np.median(lengths) <= 75
    room, placed = sequences.first_fit_decreasing(lengths, 8192)
    assert len(room) == 16 and not any(room)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_the_seed_moves_users_and_items_and_no_shape(shape, seed):
    from predictionio_tpu.templates.sessionrec.engine import pack_histories

    a = sequences.make_histories(shape, seed)
    b = sequences.make_histories(shape, seed + 1)
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert [len(h) for h in a] != [len(h) for h in b]
    assert max(h.max() for h in a) < shape["n_items"]
    ta, sa, pa = pack_histories(a, 8192)
    tb, sb, pb = pack_histories(b, 8192)
    assert ta.shape == (16, 8192)
    assert np.array_equal(sa, sb) and np.array_equal(pa, pb)
    assert not np.array_equal(ta, tb) and (sa != 0).all()
    again = sequences.make_histories(shape, seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))


# -- operations, and the readers this cell brought --------------------------------

def test_the_operations_of_a_step():
    c = load("perf", "configs", "joyai_llm_flash_1of16.json")
    per_token = encoder_step.forward_flops_per_token(c)
    # 2 x the parameters a token meets: all of them but the embedding
    # rows, the experts it does not pick and one of the two heads' reuse
    assert 600e6 < per_token < 660e6
    pairs = encoder_step.attention_pairs_per_step(c)
    # attention stays in histories: a step of two sequences
    assert pairs < 0.1 * 2 * 8192 * 8192 / 2
    total = encoder_step.model_flops(c)
    assert total == pytest.approx(
        3 * (per_token * 2 * 8192 + 2 * pairs * 32 * 320 * 6))


@pytest.mark.parametrize("op_name,scope", [
    ("jit(sessionrec_train_step)/jvp(enc.mla)/mul", "enc.mla"),
    ("jit(s)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "enc.mla/dot_general", "enc.mla"),
    ("jit(s)/transpose(jvp(enc.mtp))/jvp(enc.mtp)/checkpoint/mtp.mla/"
     "while/body/add", "enc.mtp"),
    ("jit(s)/jvp(enc.mtp)/enc.moe/moe.experts/while/body/dot", "enc.moe"),
    ("enc.mla/while/body/dynamic_slice", "enc.mla"),
    ("jit(s)/enc.adam/sqrt", "enc.adam"),
    ("jit(s)/jvp()/gather", None),
    ("jit(s)/my_enc.mlab/mul", None),
])
def test_an_op_belongs_to_the_last_known_scope_wrapped_or_not(op_name,
                                                              scope):
    known = load("perf", "layers", "fit.mla_s.json")["known"]
    assert scope_busy_ad.scope_of(op_name, known) == scope


def test_max_over_mean_of_a_gauge():
    from predictionio_tpu.telemetry.registry import REGISTRY

    g = REGISTRY.gauge("perf_test_load", "", labelnames=("layer", "expert"))
    spec = {"gauge": "perf_test_load"}
    assert gauge_max_over_mean.read(spec, None) is None
    for (layer, e), v in {("0", "0"): 10, ("0", "1"): 30, ("1", "0"): 20,
                          ("1", "1"): 20}.items():
        g.labels(layer=layer, expert=e).set(v)
    assert gauge_max_over_mean.read(spec, None) == pytest.approx(1.5)
    assert gauge_max_over_mean.read({"gauge": "perf_test_absent"},
                                    None) is None


def test_the_new_metrics_have_their_files_and_readers(bench):
    mine = [m for m in bench["per_layer"] if m["name"].startswith("fit.")]
    assert len(mine) == 12 and bench["per_layer"][-12:] == mine
    for m in mine:
        spec = load("perf", "layers", m["name"] + ".json")
        importlib.import_module(f"perf.readers.{spec['reader']}")
        assert m["workloads"] == [CELL]
    assert [m["name"] for m in mine if "mfu" in m["name"]] == ["fit.step_mfu"]
