"""`smallthinker.fit8_pack8k` on the CPU at `perf/tests/tiny/`: a rehearsal
of a sound run and of a traced one (what the driver will expect of its
last line), the controls, a broken timed path, what the parent's program
does when asked for the cell, and the operations count. No number of
these runs is a measurement."""

import copy
import json
import time

import pytest

from perf import harness
from perf.ops import smallthinker_step
from perf.readers import scope_busy_ad
from perf.tests import control_smallthinker
from perf.tests.conftest import ROOT, load
from perf.tests.test_encoder_cell import expected_per_layer

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CELL = "smallthinker.fit8_pack8k"
CONFIG = "smallthinker_21b_1of4"
MINE = ["fit.st_router_s", "fit.st_experts_s", "fit.st_attn_swa_s",
        "fit.st_attn_full_s", "fit.st_head_loss_s", "fit.st_adam_s",
        "fit.st_step_mfu", "fit.st_expert_load_max_over_mean",
        "fit.st_moe_block_fill"]
SHARED = {"train.device_idle", "train.loop_busy_s", "setup.data_s",
          "setup.warmup_call_s", "setup.compile_s", "setup.window_compiles"}
SCOPES = ["enc.router", "enc.gqa_swa", "enc.gqa_swa.proj",
          "enc.gqa_swa.pairs", "enc.gqa_swa.out", "enc.gqa_full",
          "enc.gqa_full.proj", "enc.gqa_full.pairs", "enc.gqa_full.out",
          "enc.experts", "enc.experts.plan", "enc.head_loss", "enc.adam"]


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


def controlled_bench(run, tmp_path, control):
    """The tiny benchmark with the cell's configuration written anew as
    `control_smallthinker.controlled` changes it."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], CONFIG, "config")
    config = control_smallthinker.controlled(load(entry["file"]), control)
    path = tmp_path / "control.json"
    config["algorithm_params"]["encoderConfig"] = str(path)
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    return bench


def test_a_sound_run_is_correct_and_reports_its_end_to_end_metrics(
        run, bench, capsys):
    result = run()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= harness.LEAST_CALLS
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # the rows the window cuts have two numbers of their own, both held
    out = capsys.readouterr().out
    assert "check ce_past_window_rel_err:" in out
    assert "check window_off_share:" in out
    assert "of them at or past position 8 of their history" in out


def test_the_entries_are_appended_and_name_the_cell_alone(bench):
    """Found by name, wherever later PRs append theirs."""
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fit8_pack8k", 1)
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == ["num_hidden_layers",
                                "moe_num_primary_experts", "vocab_size"]
    config = load(entry["file"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
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


def test_the_file_keeps_every_published_number_but_the_three_reduced():
    """The catalog's `config` for SmallThinker-21BA3B-Instruct, key by
    key; the two layouts whole."""
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    config = load("perf", "configs", CONFIG + ".json")
    differ = sorted(k for k, v in published.items() if config[k] != v)
    assert differ == sorted(config["reduced"])
    assert {k: config["published"][k] for k in differ} == {
        k: published[k] for k in differ}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 37984)
    assert config["share"] == {
        "experts_total": 64, "expert_first": 0, "chips_per_layer": 4,
        "vocab_ways": 4, "layer_first": 0, "layers_total": 52}
    assert config["shape"]["n_items"] == config["vocab_size"]


def test_a_traced_run_will_print_the_six_shared_metrics_and_the_nine_new(
        run, bench):
    """Exactly those. On the CPU the device's readers find nothing; the
    counters' metrics are in the line."""
    want = expected_per_layer(bench, CELL)
    assert {m["name"] for m in want} == SHARED | set(MINE)
    result = run(trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert set(got) == {"setup.data_s", "setup.warmup_call_s",
                        "setup.compile_s", "setup.window_compiles",
                        "fit.st_expert_load_max_over_mean",
                        "fit.st_moe_block_fill"}
    assert got["fit.st_expert_load_max_over_mean"]["value"] >= 1.0
    assert 0 < got["fit.st_moe_block_fill"]["value"] <= 100
    config, traffic = harness.load_cell(ROOT, run.bench, CELL)
    for name in MINE[:7]:
        spec = load("perf", "layers", name + ".json")
        assert spec["module"] in config["trace_names"]
        assert int(traffic[spec["per"]]) == 1


def test_the_gauges_hold_the_last_steps_rows_and_the_rows_walked(run):
    from predictionio_tpu.telemetry.registry import REGISTRY

    run()
    config = load("perf", "tests", "tiny", CONFIG + ".json")
    tokens = dict(REGISTRY.get("encoder_expert_tokens").collect())
    walked = dict(REGISTRY.get("encoder_expert_block_rows").collect())
    # four layers of two held experts (ids 2 and 3), by (layer, expert);
    # another cell's run in this process leaves its own labels beside them
    mine = {(str(n), e) for n in range(4) for e in "23"}
    assert mine <= set(walked) and mine <= set(tokens)
    block = config["train"]["moe_block_rows"]
    for key in mine:
        assert walked[key] == -(-int(tokens[key]) // block) * block


@pytest.mark.parametrize("control,number", [
    ("bfloat16_reference", "ce_rel_err"),
    ("sigmoid_scores", "router_grad_max_rel_err"),
    ("silu_gate", "ce_rel_err"),
    ("router_after_attention", "expert_picks_moved_share"),
    ("no_window_reference", "window_off_share"),
    ("rotate_full", "attn_grad_max_rel_err"),
    ("unchanged", "update_sign_max_wrong_share")])
def test_a_control_is_not_correct(run, tmp_path, capsys, control, number):
    """`perf/tests/control_smallthinker.py`, as a CPU run holds it."""
    bench = controlled_bench(run, tmp_path, control)
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    failed = [line for line in out.splitlines() if "FAILED" in line]
    assert any(f"check {number}:" in line for line in failed), failed
    if control != "unchanged":
        assert "the program's numbers: ce_rel_err" in out
        assert control.replace("_", " ") + ": block full_w_q" in out
        assert f"control {control}: " in out and ": not correct" in out


def test_several_controls_in_one_run_return_the_nearest_to_passing(
        run, tmp_path, capsys):
    """One set-up for all of them; `correct` only if some control was."""
    bench = controlled_bench(run, tmp_path, "silu_gate,rotate_full")
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    verdicts = [line for line in out.splitlines() if "] control " in line]
    assert len(verdicts) == 2 and all("not correct" in v for v in verdicts)
    with pytest.raises(SystemExit, match="no_such"):
        control_smallthinker.controlled(
            load("perf", "tests", "tiny", CONFIG + ".json"), "no_such")


def _post_attention_router(real):
    """`encoder.block` with the router fed the normed stream after
    attention (JoyAI's place for it) and not the block's input."""
    import dataclasses

    def block(p, bias, cfg, h, seg, pos, scope="", n=0):
        return real(p, bias,
                    dataclasses.replace(cfg, router_on_block_input=False),
                    h, seg, pos, scope, n)

    return block


def test_a_broken_timed_path_is_not_correct(run, monkeypatch, capsys):
    from predictionio_tpu.models import encoder

    monkeypatch.setattr(encoder, "block",
                        _post_attention_router(encoder.block))
    assert run()["correct"] is False
    out = capsys.readouterr().out
    assert any("check expert_picks_moved_share:" in line
               for line in out.splitlines() if "FAILED" in line)


def test_the_parents_program_refuses_the_configuration_at_once():
    """What the parent of PR 43 does when asked for this cell: its
    `from_dict` drops the keys it does not know, the published file has
    no `intermediate_size`, and the constructor raises a TypeError for
    the missing argument before any work. Here the family's keys give
    it (0: no dense feed-forward anywhere)."""
    import dataclasses

    from predictionio_tpu.models import encoder

    raw = load("perf", "configs", CONFIG + ".json")
    assert "intermediate_size" not in raw
    assert "intermediate_size" not in raw["share"]
    fields = {f.name: f for f in dataclasses.fields(encoder.EncoderConfig)}
    assert fields["intermediate_size"].default is dataclasses.MISSING
    with pytest.raises(TypeError, match="intermediate_size"):
        # the parent's constructor call, its known keys
        encoder.EncoderConfig(**{
            k: v for k, v in raw.items()
            if k in ("hidden_size", "intermediate_size", "num_hidden_layers",
                     "num_attention_heads")})
    cfg = encoder.EncoderConfig.from_dict(raw)
    assert (cfg.intermediate_size, cfg.n_dense, cfg.n_moe) == (0, 0, 4)


# -- operations --------------------------------------------------------------------

def test_the_operations_of_a_step_are_counted_layer_by_layer():
    c = load("perf", "configs", CONFIG + ".json")
    assert smallthinker_step.windowed_layers(c) == [False, True, True, True]
    assert smallthinker_step.held_picks_per_token(c) == 1.5
    # 2 x the matrix entries a token meets in a layer: attention whole,
    # the router over 64, 1.5 experts of 3 x 2560 x 768
    assert smallthinker_step.layer_flops_per_token(c) == 2 * (
        20_971_520 + 163_840 + 1.5 * 5_898_240)
    assert smallthinker_step.pair_flops(c) == 28 * 2 * (128 + 128)
    from perf.ops.sambay_step import pairs_per_step

    full, cut = pairs_per_step(c), pairs_per_step(c, 4096)
    # one history of 4385 is longer than the window: 289 positions lose
    # 1 .. 289 keys
    assert full - cut == 289 * 290 / 2 / 8
    assert smallthinker_step.pairs(c) == full + 3 * cut
    total = smallthinker_step.model_flops(c)
    tokens = 2 * 8192
    matrices = 4 * (20_971_520 + 163_840 + 1.5 * 5_898_240) + 2560 * 37984
    assert total == 3 * (2 * matrices * tokens
                         + smallthinker_step.pairs(c) * 28 * 512)


def test_the_benchmarks_windows_are_the_programs():
    from predictionio_tpu.models import encoder

    for name in ("configs", "tests/tiny"):
        c = load("perf", *name.split("/"), CONFIG + ".json")
        cfg = encoder.EncoderConfig.from_dict(c)
        assert tuple(smallthinker_step.windowed_layers(c)) == (
            cfg.layer_windowed)
        assert cfg.sliding_window == c["sliding_window_size"]


@pytest.mark.parametrize("op_name,scope,metric", [
    ("jit(s)/jvp(enc.router)/dot_general", "enc.router", "fit.st_router_s"),
    ("jit(s)/transpose(jvp(enc.router))/top_k", "enc.router",
     "fit.st_router_s"),
    ("jit(s)/jvp(enc.experts)/moe.experts/enc.experts/enc.experts.plan/sort",
     "enc.experts.plan", "fit.st_router_s"),
    ("jit(s)/jvp(enc.experts)/moe.experts/enc.experts/while/body/"
     "dot_general", "enc.experts", "fit.st_experts_s"),
    ("jit(s)/transpose(jvp(enc.experts))/enc.experts/while/body/dot_general",
     "enc.experts", "fit.st_experts_s"),
    ("jit(s)/jvp(enc.gqa_swa)/enc.gqa_swa.proj/cos", "enc.gqa_swa.proj",
     "fit.st_attn_swa_s"),
    ("jit(s)/transpose(jvp(enc.gqa_swa))/enc.gqa_swa.pairs/"
     "segment_attention_bwd", "enc.gqa_swa.pairs", "fit.st_attn_swa_s"),
    ("jit(s)/jvp(enc.gqa_full)/enc.gqa_full.pairs/segment_attention_fwd",
     "enc.gqa_full.pairs", "fit.st_attn_full_s"),
    ("jit(s)/jvp(enc.gqa_full)/enc.gqa_full.out/dot_general",
     "enc.gqa_full.out", "fit.st_attn_full_s"),
    ("jit(s)/jvp(enc.head_loss)/while/body/dot_general", "enc.head_loss",
     "fit.st_head_loss_s"),
    ("jit(s)/enc.adam/sqrt", "enc.adam", "fit.st_adam_s"),
])
def test_an_op_belongs_to_the_innermost_scope(op_name, scope, metric):
    for name in MINE[:6]:
        spec = load("perf", "layers", name + ".json")
        assert scope_busy_ad.scope_of(op_name, spec["known"]) == scope
        assert (scope in spec["scopes"]) == (name == metric)
