"""`kimi_linear.fit8_pack8k` on the CPU at `perf/tests/tiny/`: a
rehearsal of a sound run and of a traced one (what the driver will
expect of its last line), the three controls, what the parent's program
does when asked for the cell, the operations counts and the reader this
cell brought. No number of these runs is a measurement."""

import copy
import json
import time

import pytest

from perf import harness
from perf.ops import hybrid_step, kda_scan
from perf.readers import scope_busy_ad, scope_roofline
from perf.tests import control_kimi
from perf.tests.conftest import ROOT, load
from perf.tests.test_encoder_cell import expected_per_layer

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CELL = "kimi_linear.fit8_pack8k"
CONFIG = "kimi_linear_48b_1of32"
MINE = ["fit.kda_s", "fit.kda_scan_s", "fit.kda_scan_roofline",
        "fit.hybrid_step_mfu", "fit.kda_boundary_chunk_share"]
SHARED = {"train.device_idle", "train.loop_busy_s", "setup.data_s",
          "setup.warmup_call_s", "setup.compile_s", "setup.window_compiles"}


@pytest.fixture()
def run(bench, tmp_path, monkeypatch):
    tiny = copy.deepcopy(bench)
    for c in tiny["configs"]:
        c["file"] = f"perf/tests/tiny/{c['name']}.json"
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    # programs kept from an earlier test would carry its span wrapper
    from predictionio_tpu.templates.sessionrec import engine

    engine._train_programs.cache_clear()
    engine._encoder_config.cache_clear()

    def run_cell(trace=False, seed=2 ** 31 + 17, bench=tiny):
        import jax

        return harness.run_cell(ROOT, bench, CELL, seed, 0.3, trace,
                                time.perf_counter(), jax.devices()[:1],
                                peaks=PEAKS)

    run_cell.bench = tiny
    return run_cell


def test_a_sound_run_is_correct_and_reports_its_end_to_end_metrics(
        run, bench):
    result = run()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= harness.LEAST_CALLS
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_a_traced_run_will_print_the_six_shared_metrics_and_the_five_new(
        run, bench):
    """Exactly those: the twelve `fit.*` of the JoyAI cell list that cell
    alone. On the CPU the device's readers find nothing; the counter's
    metric is in the line."""
    want = expected_per_layer(bench, CELL)
    assert {m["name"] for m in want} == SHARED | set(MINE)
    assert [m["name"] for m in bench["per_layer"][-5:]] == MINE
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "epoch_s"
    result = run(trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert set(got) == {"setup.data_s", "setup.warmup_call_s",
                        "setup.compile_s", "setup.window_compiles",
                        "fit.kda_boundary_chunk_share"}
    # 8 steps of 2 sequences of 64 in chunks of 16, summed over the steps
    assert 0 < got["fit.kda_boundary_chunk_share"]["value"] <= 100
    config, traffic = harness.load_cell(ROOT, run.bench, CELL)
    for name in MINE[:4]:
        spec = load("perf", "layers", name + ".json")
        assert spec["module"] in config["trace_names"]
        assert int(traffic[spec["per"]]) == 1


def test_the_counter_counts_the_histories_the_steps_held(run):
    from predictionio_tpu.telemetry.registry import REGISTRY

    def total():
        return sum(v for _, v in REGISTRY.get(
            "encoder_kda_resets_total").collect())

    before = total()
    result = run()
    config = load("perf", "tests", "tiny", CONFIG + ".json")
    from perf import sequences

    histories = len(sequences.history_lengths(config["shape"]))
    # a warm-up call, the window's calls and none by the check
    assert total() - before == histories * (result["attempted"] + 1)
    chunks = dict(REGISTRY.get("encoder_kda_chunks").collect())
    assert len(chunks) == 8 and set(chunks.values()) == {2 * 64 // 16}


@pytest.mark.parametrize("control,number", [
    ("bfloat16_reference", "ce_rel_err"),
    ("no_reset_reference", "kda_grad_max_rel_err"),
    ("unchanged", "update_sign_max_wrong_share")])
def test_a_control_is_not_correct(run, tmp_path, capsys, control, number):
    """`perf/tests/control_kimi.py`, as a CPU run holds it."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], CONFIG, "config")
    config = control_kimi.controlled(load(entry["file"]), control)
    path = tmp_path / "control.json"
    config["algorithm_params"]["encoderConfig"] = str(path)
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    failed = [line for line in out.splitlines() if "FAILED" in line]
    assert any(f"check {number}:" in line for line in failed), failed
    if control != "unchanged":
        assert "the program's numbers: ce_rel_err" in out
        assert control.replace("_", " ") + ": block conv_q" in out


def test_the_parents_program_refuses_the_configuration_at_once():
    """What the parent of PR 32 does when asked for this cell: its
    `EncoderConfig` has a required `q_lora_rank`, the file states null,
    `from_dict` drops it and the constructor raises, before any work."""
    import dataclasses

    from predictionio_tpu.models import encoder

    raw = load("perf", "configs", CONFIG + ".json")
    assert raw["q_lora_rank"] is None
    field = {f.name: f for f in dataclasses.fields(encoder.EncoderConfig)}[
        "q_lora_rank"]
    assert field.default == 0  # here; required there
    assert encoder.EncoderConfig.from_dict(raw).q_lora_rank == 0


# -- operations, and the reader this cell brought -----------------------------------

def test_the_scan_is_counted_in_the_recurrent_form():
    one = kda_scan.cost_per_token_head(128, 128)
    # decay 1, k^T S 2, update 2, S^T q 2 passes over the state, and u
    assert one["flops"] == 7 * 128 * 128 + 2 * 128
    assert one["bytes"] == 4 * (3 * 128 + 2 * 128 + 1)
    c = load("perf", "configs", CONFIG + ".json")
    assert kda_scan.layer_kinds(c).count("kda") == 4
    job = kda_scan.cost(c)
    units = 2 * 8192 * 32 * 4
    assert job["flops"] == 3 * one["flops"] * units
    seconds, bound = kda_scan.least_seconds(c, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(
        3 * one["bytes"] * units / 819e9)
    assert 0.015 < seconds < 0.025


def test_the_operations_of_a_step_are_counted_by_layer_kind():
    c = load("perf", "configs", CONFIG + ".json")
    assert kda_scan.layer_kinds(c) == ["kda", "kda", "kda", "mla", "kda"]
    # 2 x the matrix entries a token meets in a mixer
    assert hybrid_step.kda_flops_per_token(c) == pytest.approx(
        2 * (39514272 - 32 - 4096 - 128) + 32 * (7 * 128 * 128 + 256))
    assert hybrid_step.mla_flops_per_token(c) == 2 * (29114880 - 512)
    assert hybrid_step.ffn_flops_per_token(c, True) == 2 * 3 * 2304 * 9216
    # router + shared + 8 picks x 8 held / 256
    assert hybrid_step.ffn_flops_per_token(c, False) == 2 * (
        2304 * 256 + 7077888 * 1.25)
    per_token = hybrid_step.forward_flops_per_token(c)
    assert 680e6 < per_token < 690e6  # the issue's 671.5e6 + the scans
    total = hybrid_step.model_flops(c)
    assert 33e12 < total < 35e12


def test_the_roofline_reader_divides_the_least_time_by_the_scopes_seconds(
        monkeypatch):
    class H:
        config = load("perf", "configs", CONFIG + ".json")
        peaks = PEAKS

    spec = load("perf", "layers", "fit.kda_scan_roofline.json")
    monkeypatch.setattr(scope_busy_ad, "read", lambda spec, h: None)
    assert scope_roofline.read(spec, H) is None  # the parent: no such scope
    monkeypatch.setattr(scope_busy_ad, "read", lambda spec, h: 0.2)
    least, _ = kda_scan.least_seconds(H.config, PEAKS)
    assert scope_roofline.read(spec, H) == pytest.approx(100 * least / 0.2)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(s)/jvp(enc.kda)/enc.kda.proj/dot_general", "enc.kda.proj"),
    ("jit(s)/transpose(jvp(enc.kda))/checkpoint/rematted_computation/"
     "enc.kda.scan/while/body/dot_general", "enc.kda.scan"),
    ("jit(s)/jvp(enc.kda)/while/body/checkpoint/enc.kda.conv/mul",
     "enc.kda.conv"),
    ("jit(s)/jvp(enc.kda)/add", "enc.kda"),
    ("jit(s)/jvp(enc.mla)/mul", "enc.mla"),
])
def test_an_op_belongs_to_the_innermost_kda_scope(op_name, scope):
    known = load("perf", "layers", "fit.kda_s.json")["known"]
    assert scope_busy_ad.scope_of(op_name, known) == scope
    wanted = load("perf", "layers", "fit.kda_s.json")["scopes"]
    assert (scope in wanted) == scope.startswith("enc.kda")
