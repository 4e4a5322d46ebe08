"""`granite4h.fit16_pack8k` on the CPU at `perf/tests/tiny/`: a rehearsal
of a sound run and of a traced one (what the driver will expect of its
last line), the controls, what the parent's program does when asked for
the cell, and the operations counts. No number of these runs is a
measurement."""

import copy
import json
import time

import pytest

from perf import harness
from perf.ops import ssd_scan, ssd_step
from perf.readers import scope_busy_ad
from perf.tests import control_granite
from perf.tests.conftest import ROOT, load
from perf.tests.test_encoder_cell import expected_per_layer

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CELL = "granite4h.fit16_pack8k"
CONFIG = "granite_4_0_h_micro_1of8"
MINE = ["fit.ssd_s", "fit.ssd_scan_s", "fit.ssd_scan_roofline",
        "fit.gqa_attn_s", "fit.ffn_s", "fit.ssd_step_mfu",
        "fit.ssd_boundary_chunk_share"]
SHARED = {"train.device_idle", "train.loop_busy_s", "setup.data_s",
          "setup.warmup_call_s", "setup.compile_s", "setup.window_compiles"}
SCOPES = ["enc.ssd", "enc.ssd.proj", "enc.ssd.conv", "enc.ssd.dt",
          "enc.ssd.scan", "enc.ssd.norm", "enc.ssd.out", "enc.gqa",
          "enc.gqa.proj", "enc.gqa.pairs", "enc.gqa.out", "enc.dense_ffn",
          "enc.head_loss", "enc.adam"]


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


def test_the_entries_are_appended_and_name_the_cell_alone(bench):
    """Found by name, wherever later PRs append theirs."""
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fit16_pack8k", 1)
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert load(entry["file"])["source"] == entry["source"]
    assert load("perf", "traffic", "fit16_pack8k.json")["steps"] == 16
    for name in MINE:
        m = harness.find(bench["per_layer"], name, "metric")
        spec = load("perf", "layers", name + ".json")
        assert m["workloads"] == [CELL] and m["moves"] == "epoch_s"
        assert (m["unit"], m["layer"]) == (spec["unit"], spec["layer"])
        if "known" in spec:  # every scope the program opens, in each list
            assert spec["known"] == SCOPES
            assert set(spec["scopes"]) <= set(SCOPES)
    others = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert others == MINE


def test_a_traced_run_will_print_the_six_shared_metrics_and_the_seven_new(
        run, bench):
    """Exactly those. On the CPU the device's readers find nothing; the
    counter's metric is in the line."""
    want = expected_per_layer(bench, CELL)
    assert {m["name"] for m in want} == SHARED | set(MINE)
    result = run(trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert set(got) == {"setup.data_s", "setup.warmup_call_s",
                        "setup.compile_s", "setup.window_compiles",
                        "fit.ssd_boundary_chunk_share"}
    assert 0 < got["fit.ssd_boundary_chunk_share"]["value"] <= 100
    config, traffic = harness.load_cell(ROOT, run.bench, CELL)
    for name in MINE[:6]:
        spec = load("perf", "layers", name + ".json")
        assert spec["module"] in config["trace_names"]
        assert int(traffic[spec["per"]]) == 1


def test_the_counters_count_the_histories_and_the_chunks(run):
    from predictionio_tpu.telemetry.registry import REGISTRY

    def total():
        return sum(v for _, v in REGISTRY.get(
            "encoder_ssd_resets_total").collect())

    before = total()
    result = run()
    config = load("perf", "tests", "tiny", CONFIG + ".json")
    from perf import sequences

    histories = len(sequences.history_lengths(config["shape"]))
    # a warm-up call, the window's calls and none by the check
    assert total() - before == histories * (result["attempted"] + 1)
    chunks = dict(REGISTRY.get("encoder_ssd_chunks").collect())
    # 16 steps of one sequence of 64 in chunks of 16
    assert len(chunks) == 16 and set(chunks.values()) == {64 // 16}


@pytest.mark.parametrize("control,number", [
    ("bfloat16_reference", "ce_rel_err"),
    ("no_reset_reference", "ssd_grad_max_rel_err"),
    ("embedding_multiplier_1", "grad_max_rel_err"),
    ("residual_multiplier_1", "ssd_grad_max_rel_err"),
    ("attention_multiplier_1", "attn_grad_max_rel_err"),
    ("logits_scaling_1", "ce_rel_err"),
    ("attention_scale_rsqrt_d", "attn_grad_max_rel_err"),
    ("unchanged", "update_sign_max_wrong_share")])
def test_a_control_is_not_correct(run, tmp_path, capsys, control, number):
    """`perf/tests/control_granite.py`, as a CPU run holds it."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], CONFIG, "config")
    config = control_granite.controlled(load(entry["file"]), control)
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
        assert control.replace("_", " ") + ": block ssd0_w_in" in out
        assert f"control {control}: " in out and ": not correct" in out


def test_several_controls_in_one_run_return_the_nearest_to_passing(
        run, tmp_path, capsys):
    """One set-up for all of them; `correct` only if some control was."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], CONFIG, "config")
    config = control_granite.controlled(
        load(entry["file"]), "no_reset_reference,attention_scale_rsqrt_d")
    path = tmp_path / "control.json"
    config["algorithm_params"]["encoderConfig"] = str(path)
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    verdicts = [line for line in out.splitlines() if "] control " in line]
    assert len(verdicts) == 2 and all("not correct" in v for v in verdicts)
    with pytest.raises(SystemExit, match="no_such"):
        control_granite.controlled(load(entry["file"]), "no_such")


def test_the_parents_program_refuses_the_configuration_at_once():
    """What the parent of PR 41 does when asked for this cell: its
    `from_dict` drops the keys it does not know, the `share` group's
    `intermediate_size: null` overrides the published one, a None is
    dropped too, and the constructor raises a TypeError for the missing
    argument before any work. Here the feed-forward's width is the
    shared one."""
    import dataclasses

    from predictionio_tpu.models import encoder

    raw = load("perf", "configs", CONFIG + ".json")
    assert raw["intermediate_size"] == 8192
    assert raw["share"]["intermediate_size"] is None
    fields = {f.name: f for f in dataclasses.fields(encoder.EncoderConfig)}
    assert fields["intermediate_size"].default is dataclasses.MISSING
    flat = {**raw, **raw["share"]}
    with pytest.raises(TypeError, match="intermediate_size"):
        # the parent's constructor call, its known keys and no None
        encoder.EncoderConfig(**{
            k: v for k, v in flat.items()
            if k in ("hidden_size", "intermediate_size", "num_hidden_layers",
                     "num_attention_heads") and v is not None})
    assert encoder.EncoderConfig.from_dict(raw).intermediate_size == 8192


# -- operations --------------------------------------------------------------------

def test_the_scan_is_counted_in_the_recurrent_form():
    one = ssd_scan.cost_per_token(64, 64, 128)
    assert one["flops"] == 64 * (5 * 64 * 128 + 3 * 64 + 2)
    assert one["bytes"] == 4 * (2 * 4096 + 64 + 256)
    c = load("perf", "configs", CONFIG + ".json")
    assert ssd_scan.layer_kinds(c) == ["ssd"] * 5 + ["gqa"] + ["ssd"] * 4
    job = ssd_scan.cost(c)
    assert job["bytes"] == 3 * 9 * 8192 * 4 * (2 * 4096 + 64 + 256)
    seconds, bound = ssd_scan.least_seconds(c, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(job["bytes"] / 819e9)
    assert 0.0091 < seconds < 0.0093


def test_the_benchmarks_kinds_are_the_programs():
    from predictionio_tpu.models import encoder

    for name in ("configs", "tests/tiny"):
        c = load("perf", *name.split("/"), CONFIG + ".json")
        assert tuple(ssd_scan.layer_kinds(c)) == (
            encoder.EncoderConfig.from_dict(c).kinds)


def test_the_operations_of_a_step_are_counted_by_layer_kind():
    c = load("perf", "configs", CONFIG + ".json")
    # 2 x the matrix entries a token meets in a mixer, biases and norms aside
    assert ssd_step.mixer_flops_per_token(c, "ssd") == (
        2 * (25_847_232 - 4352 - 192 - 4096)
        + 64 * (5 * 64 * 128 + 3 * 64 + 2))
    assert ssd_step.mixer_flops_per_token(c, "gqa") == 2 * 10_485_760
    assert ssd_step.pair_flops(c) == 32 * 2 * (64 + 64)
    total = ssd_step.model_flops(c)
    # 6 x (parameters, the embedding once) x tokens, then pairs and the scan
    assert 6 * 772e6 * 8192 < total < 1.05 * 6 * 772e6 * 8192


@pytest.mark.parametrize("op_name,scope", [
    ("jit(s)/jvp(enc.ssd)/enc.ssd.proj/dot_general", "enc.ssd.proj"),
    ("jit(s)/transpose(jvp(enc.ssd))/checkpoint/rematted_computation/"
     "enc.ssd.scan/while/body/mul", "enc.ssd.scan"),
    ("jit(s)/jvp(enc.ssd)/enc.ssd.norm/rsqrt", "enc.ssd.norm"),
    ("jit(s)/jvp(enc.gqa)/enc.gqa.pairs/while/body/dot_general",
     "enc.gqa.pairs"),
    ("jit(s)/transpose(jvp(enc.gqa))/enc.gqa.out/dot_general",
     "enc.gqa.out"),
    ("jit(s)/jvp(enc.ssd)/add", "enc.ssd"),
    ("jit(s)/transpose(jvp())/checkpoint/enc.dense_ffn/while/body/"
     "dot_general", "enc.dense_ffn"),
])
def test_an_op_belongs_to_the_innermost_scope(op_name, scope):
    spec = load("perf", "layers", "fit.ssd_s.json")
    assert scope_busy_ad.scope_of(op_name, spec["known"]) == scope
    assert (scope in spec["scopes"]) == scope.startswith("enc.ssd")
    attn = load("perf", "layers", "fit.gqa_attn_s.json")
    assert (scope in attn["scopes"]) == scope.startswith("enc.gqa")
    ffn = load("perf", "layers", "fit.ffn_s.json")
    assert (scope in ffn["scopes"]) == (scope == "enc.dense_ffn")
