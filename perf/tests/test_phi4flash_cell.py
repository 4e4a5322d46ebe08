"""`phi4flash.fit8_pack8k` on the CPU at `perf/tests/tiny/`: a rehearsal
of a sound run and of a traced one (what the driver will expect of its
last line), the five controls, what the parent's program does when
asked for the cell, and the operations counts. No number of these runs
is a measurement."""

import copy
import json
import time

import pytest

from perf import harness
from perf.ops import sambay_step, selective_scan
from perf.readers import scope_busy_ad
from perf.tests import control_phi4flash
from perf.tests.conftest import ROOT, load
from perf.tests.test_encoder_cell import expected_per_layer

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CELL = "phi4flash.fit8_pack8k"
CONFIG = "phi4_mini_flash_1of8"
MINE = ["fit.ssm_s", "fit.ssm_scan_s", "fit.ssm_scan_roofline",
        "fit.diff_attn_s", "fit.gmu_s", "fit.sambay_step_mfu",
        "fit.ssm_boundary_chunk_share"]
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


def test_a_traced_run_will_print_the_six_shared_metrics_and_the_seven_new(
        run, bench):
    """Exactly those. On the CPU the device's readers find nothing; the
    counter's metric is in the line."""
    want = expected_per_layer(bench, CELL)
    assert {m["name"] for m in want} == SHARED | set(MINE)
    assert [m["name"] for m in bench["per_layer"][-7:]] == MINE
    for m in bench["per_layer"][-7:]:
        assert m["workloads"] == [CELL] and m["moves"] == "epoch_s"
    result = run(trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert set(got) == {"setup.data_s", "setup.warmup_call_s",
                        "setup.compile_s", "setup.window_compiles",
                        "fit.ssm_boundary_chunk_share"}
    assert 0 < got["fit.ssm_boundary_chunk_share"]["value"] <= 100
    config, traffic = harness.load_cell(ROOT, run.bench, CELL)
    for name in MINE[:6]:
        spec = load("perf", "layers", name + ".json")
        assert spec["module"] in config["trace_names"]
        assert int(traffic[spec["per"]]) == 1


def test_the_counters_count_the_histories_and_the_key_blocks(run):
    from predictionio_tpu.telemetry.registry import REGISTRY

    def total():
        return sum(v for _, v in REGISTRY.get(
            "encoder_ssm_resets_total").collect())

    before = total()
    result = run()
    config = load("perf", "tests", "tiny", CONFIG + ".json")
    from perf import sequences

    histories = len(sequences.history_lengths(config["shape"]))
    # a warm-up call, the window's calls and none by the check
    assert total() - before == histories * (result["attempted"] + 1)
    chunks = dict(REGISTRY.get("encoder_ssm_chunks").collect())
    assert len(chunks) == 8 and set(chunks.values()) == {2 * 64 // 16}
    blocks = dict(REGISTRY.get("encoder_attn_key_blocks").collect())
    # 16 sequences of 4 blocks: causal 16 x 10 for both kinds
    assert blocks[("window", "causal")] == blocks[("full", "causal")] == 160
    assert (blocks[("window", "visited")] <= blocks[("full", "visited")]
            <= 160)


@pytest.mark.parametrize("control,number", [
    ("bfloat16_reference", "ce_rel_err"),
    ("no_reset_reference", "ssm_grad_max_rel_err"),
    ("no_window_reference", "attn_grad_max_rel_err"),
    ("stale_carry_reference", "grad_max_rel_err"),
    ("unchanged", "update_sign_max_wrong_share")])
def test_a_control_is_not_correct(run, tmp_path, capsys, control, number):
    """`perf/tests/control_phi4flash.py`, as a CPU run holds it."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], CONFIG, "config")
    config = control_phi4flash.controlled(load(entry["file"]), control)
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
        assert control.replace("_", " ") + ": block ssm_w_in_x" in out


def test_the_parents_program_refuses_the_configuration_at_once():
    """What the parent of PR 37 does when asked for this cell: its
    `EncoderConfig` has `kv_lora_rank` and the three head widths of MLA
    as required fields, the file states none, and the constructor
    raises a TypeError before any work."""
    import dataclasses

    from predictionio_tpu.models import encoder

    raw = load("perf", "configs", CONFIG + ".json")
    fields = {f.name: f for f in dataclasses.fields(encoder.EncoderConfig)}
    for name in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                 "v_head_dim"):
        assert name not in raw
        assert fields[name].default == 0  # here; required there
    assert encoder.EncoderConfig.from_dict(raw).kinds == (
        "swa", "mamba", "full", "gmu", "cross")


# -- operations --------------------------------------------------------------------

def test_the_scan_is_counted_in_the_recurrent_form():
    one = selective_scan.cost_per_token(5120, 16)
    assert one["flops"] == (7 * 16 + 3) * 5120
    assert one["bytes"] == 4 * (3 * 5120 + 32)
    c = load("perf", "configs", CONFIG + ".json")
    assert selective_scan.layer_kinds(c) == ["swa", "mamba", "full", "gmu",
                                             "cross"]
    job = selective_scan.cost(c)
    assert job["bytes"] == 3 * 16384 * 4 * (3 * 5120 + 32)
    seconds, bound = selective_scan.least_seconds(c, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(job["bytes"] / 819e9)
    assert 0.0036 < seconds < 0.0038


def test_the_benchmarks_kinds_are_the_programs():
    from predictionio_tpu.models import encoder

    for first, held, total in ((15, 5, 32), (0, 8, 8), (0, 32, 32), (3, 5, 8)):
        c = {"num_hidden_layers": held, "mb_per_layer": 2,
             "share": {"layer_first": first, "layers_total": total}}
        assert tuple(selective_scan.layer_kinds(c)) == (
            encoder.hybrid_decoder_kinds(first, held, total, 2))


def test_the_operations_of_a_step_are_counted_by_layer_kind():
    c = load("perf", "configs", CONFIG + ".json")
    # 2 x the matrix entries a token meets in a mixer, biases and norms aside
    assert sambay_step.mixer_flops_per_token(c, "mamba") == (
        2 * (41241600 - 5120 - 5120 - 81920 - 5120)
        + (7 * 16 + 3) * 5120)
    assert sambay_step.mixer_flops_per_token(c, "swa") == 2 * (
        19668864 - 5120 - 256 - 128 - 2560)
    assert sambay_step.mixer_flops_per_token(c, "cross") == 2 * (
        13112704 - 2560 - 256 - 128 - 2560)
    assert sambay_step.mixer_flops_per_token(c, "gmu") == 2 * 26214400
    assert sambay_step.pair_flops(c) == 40 * 2 * (64 + 128)
    full, windowed = (sambay_step.pairs_per_step(c),
                      sambay_step.pairs_per_step(c, 512))
    assert 0 < windowed < full
    # a window longer than every history leaves the causal count
    assert sambay_step.pairs_per_step(c, 8192) == full
    total = sambay_step.model_flops(c)
    # 6 x (parameters, the embedding once) x tokens, then pairs and the scan
    assert 6 * 577e6 * 16384 < total < 1.25 * 6 * 577e6 * 16384


@pytest.mark.parametrize("op_name,scope", [
    ("jit(s)/jvp(enc.mamba)/enc.mamba.proj/dot_general", "enc.mamba.proj"),
    ("jit(s)/transpose(jvp(enc.mamba))/checkpoint/rematted_computation/"
     "enc.mamba.scan/while/body/mul", "enc.mamba.scan"),
    ("jit(s)/jvp(enc.attn)/enc.attn.pairs/while/body/dot_general",
     "enc.attn.pairs"),
    ("jit(s)/transpose(jvp(enc.cross))/enc.cross.pairs/while/body/exp",
     "enc.cross.pairs"),
    ("jit(s)/jvp(enc.cross)/enc.cross.subln/mul", "enc.cross.subln"),
    ("jit(s)/jvp(enc.gmu)/dot_general", "enc.gmu"),
    ("jit(s)/jvp(enc.mamba)/add", "enc.mamba"),
])
def test_an_op_belongs_to_the_innermost_scope(op_name, scope):
    spec = load("perf", "layers", "fit.ssm_s.json")
    assert scope_busy_ad.scope_of(op_name, spec["known"]) == scope
    assert (scope in spec["scopes"]) == scope.startswith("enc.mamba")
    attn = load("perf", "layers", "fit.diff_attn_s.json")
    assert (scope in attn["scopes"]) == scope.startswith(("enc.attn",
                                                          "enc.cross"))
