"""`lfm2.fit8_pack8k` on the CPU at `perf/tests/tiny/`: a rehearsal of a
sound run and of a traced one (what the driver will expect of its last
line), the controls, a broken timed path, what the parent's program does
when asked for the cell, and the operations counts. No number of these
runs is a measurement."""

import copy
import json
import time

import pytest

from perf import harness
from perf.ops import gated_short_conv, lfm2_step
from perf.readers import scope_busy_ad
from perf.tests import control_lfm2
from perf.tests.conftest import ROOT, load
from perf.tests.test_encoder_cell import expected_per_layer

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CELL = "lfm2.fit8_pack8k"
CONFIG = "lfm2_24b_a2b_1of8"
MINE = ["fit.lfm_sconv_s", "fit.lfm_sconv_gated_s",
        "fit.lfm_sconv_gated_roofline", "fit.lfm_attn_s", "fit.lfm_qk_norm_s",
        "fit.lfm_dense_ffn_s", "fit.lfm_router_s", "fit.lfm_experts_s",
        "fit.lfm_head_loss_s", "fit.lfm_adam_s", "fit.lfm_step_mfu",
        "fit.lfm_expert_load_max_over_mean", "fit.lfm_moe_block_fill"]
SHARED = {"train.device_idle", "train.loop_busy_s", "setup.data_s",
          "setup.warmup_call_s", "setup.compile_s", "setup.window_compiles"}
SCOPES = ["enc.sconv", "enc.sconv.proj", "enc.sconv.gate", "enc.sconv.conv",
          "enc.sconv.out", "enc.gqa_full", "enc.gqa_full.proj",
          "enc.gqa_full.qk_norm", "enc.gqa_full.pairs", "enc.gqa_full.out",
          "enc.dense_ffn", "enc.router", "enc.experts", "enc.experts.plan",
          "enc.head_loss", "enc.adam"]
NUMBERS = ("ce_rel_err", "expert_picks_moved_share", "sconv_grad_max_rel_err",
           "attn_grad_max_rel_err", "qk_norm_grad_max_rel_err",
           "router_grad_max_rel_err", "expert_grad_max_rel_err",
           "grad_max_rel_err", "update_sign_max_wrong_share",
           "nonfinite_entries")


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
    `control_lfm2.controlled` changes it."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], CONFIG, "config")
    config = control_lfm2.controlled(load(entry["file"]), control)
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
    out = capsys.readouterr().out
    for number in NUMBERS:
        assert f"check {number}:" in out


def test_the_entries_are_appended_and_name_the_cell_alone(bench):
    """Found by name, wherever later PRs append theirs."""
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fit8_pack8k", 1)
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
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
    """The catalog's `config` for LFM2-24B-A2B, key by key; `layer_types`
    whole."""
    types = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
             + ["full_attention", "conv"])
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": types,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    config = load("perf", "configs", CONFIG + ".json")
    differ = sorted(k for k, v in published.items() if config[k] != v)
    assert differ == sorted(config["reduced"])
    assert {k: config["published"][k] for k in differ} == {
        k: published[k] for k in differ}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (7, 8, 8192)
    assert len(types) == 40 and types.count("conv") == 30
    assert types[1:8] == ["conv", "full_attention", "conv", "conv", "conv",
                          "full_attention", "conv"]
    assert config["share"] == {
        "experts_total": 64, "expert_first": 0, "chips_per_layer": 8,
        "vocab_ways": 8, "layer_first": 1, "layers_total": 40}
    assert config["shape"]["n_items"] == config["vocab_size"]
    assert config["tie_word_embeddings"] is True
    assert "tie_word_embeddings" in config["assumed"]


def test_a_traced_run_will_print_the_six_shared_metrics_and_the_thirteen_new(
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
                        "fit.lfm_expert_load_max_over_mean",
                        "fit.lfm_moe_block_fill"}
    assert got["fit.lfm_expert_load_max_over_mean"]["value"] >= 1.0
    assert 0 < got["fit.lfm_moe_block_fill"]["value"] <= 100
    config, traffic = harness.load_cell(ROOT, run.bench, CELL)
    for name in MINE[:11]:
        spec = load("perf", "layers", name + ".json")
        assert spec["module"] in config["trace_names"]
        assert int(traffic[spec["per"]]) == 1


def test_the_gauges_hold_the_last_steps_rows_and_the_rows_walked(run):
    from predictionio_tpu.telemetry.registry import REGISTRY

    run()
    config = load("perf", "tests", "tiny", CONFIG + ".json")
    tokens = dict(REGISTRY.get("encoder_expert_tokens").collect())
    walked = dict(REGISTRY.get("encoder_expert_block_rows").collect())
    # six expert layers by their place among them, two held experts (ids
    # 2 and 3); another cell's run in this process leaves its own labels
    mine = {(n, e) for n in "012345" for e in "23"}
    assert mine <= set(walked) and mine <= set(tokens)
    block = config["train"]["moe_block_rows"]
    for key in mine:
        assert walked[key] == -(-int(tokens[key]) // block) * block


@pytest.mark.parametrize("control,number", [
    ("bfloat16_reference", "ce_rel_err"),
    ("no_reset_reference", "sconv_grad_max_rel_err"),
    ("no_in_gate", "sconv_grad_max_rel_err"),
    ("no_out_gate", "sconv_grad_max_rel_err"),
    ("gates_swapped", "sconv_grad_max_rel_err"),
    ("silu_on_taps", "sconv_grad_max_rel_err"),
    ("no_qk_norm", "attn_grad_max_rel_err"),
    ("norm_after_rotation", "qk_norm_grad_max_rel_err"),
    ("interleaved_pairs", "attn_grad_max_rel_err"),
    ("unchanged", "update_sign_max_wrong_share")])
def test_a_control_is_not_correct(run, tmp_path, capsys, control, number):
    """`perf/tests/control_lfm2.py`, as a CPU run holds it."""
    bench = controlled_bench(run, tmp_path, control)
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    failed = [line for line in out.splitlines() if "FAILED" in line]
    assert any(f"check {number}:" in line for line in failed), failed
    if control != "unchanged":
        assert "the program's numbers: ce_rel_err" in out
        assert control.replace("_", " ") + ": block sconv1_w_in" in out
        assert f"control {control}: " in out and ": not correct" in out


def test_several_controls_in_one_run_return_the_nearest_to_passing(
        run, tmp_path, capsys):
    """One set-up for all of them; `correct` only if some control was."""
    bench = controlled_bench(run, tmp_path, "no_in_gate,no_qk_norm")
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    verdicts = [line for line in out.splitlines() if "] control " in line]
    assert len(verdicts) == 2 and all("not correct" in v for v in verdicts)
    with pytest.raises(SystemExit, match="no_such"):
        control_lfm2.controlled(
            load("perf", "tests", "tiny", CONFIG + ".json"), "no_such")


def test_a_broken_timed_path_is_not_correct(run, monkeypatch, capsys):
    """The timed path with the second gate left out of the mixer."""
    from predictionio_tpu.models import encoder

    real = encoder.short_conv

    def no_out_gate(p, cfg, x, seg, scope="enc.sconv"):
        d = p["w_out"].shape[0]
        # C's columns of W_in zeroed and a one added: y = 1 * c
        ones = p["w_in"].at[:, d:2 * d].set(0.0)
        return real({**p, "w_in": ones}, cfg, x, seg, scope)

    monkeypatch.setattr(encoder, "short_conv", no_out_gate)
    assert run()["correct"] is False
    out = capsys.readouterr().out
    assert any("check sconv_grad_max_rel_err:" in line
               for line in out.splitlines() if "FAILED" in line)


def test_the_parents_program_refuses_the_configuration_at_once():
    """What the parent of PR 51 does when asked for this cell: its
    `from_dict` hands a file with `layer_types` to `_held_layer_types`,
    which knows `mamba` and `attention` alone and raises a ValueError
    that names `conv` and `full_attention` before any work; it builds no
    other model in silence. Here the family's reader takes the file, and
    the older reader still refuses the entries by name."""
    from predictionio_tpu.models import encoder

    raw = load("perf", "configs", CONFIG + ".json")
    flat = {**raw, **raw["share"]}  # the parent's flattening
    with pytest.raises(ValueError, match=r"\['conv', 'full_attention'\] not "
                                         r"known"):
        encoder._held_layer_types(flat)
    cfg = encoder.EncoderConfig.from_dict(raw)
    assert cfg.kinds == ("sconv", "gqa", "sconv", "sconv", "sconv", "gqa",
                         "sconv")
    assert (cfg.n_dense, cfg.n_moe, cfg.qk_norm, cfg.router_norm_eps) == (
        1, 6, True, 1e-6)


# -- operations --------------------------------------------------------------------

def test_the_operations_of_a_step_are_counted_layer_by_layer():
    c = load("perf", "configs", CONFIG + ".json")
    assert gated_short_conv.layer_kinds(c) == [
        "sconv", "gqa", "sconv", "sconv", "sconv", "gqa", "sconv"]
    assert lfm2_step.dense_layers(c) == 1
    assert lfm2_step.held_picks_per_token(c) == 0.5
    # 2 x the matrix entries a token meets: in 2048 -> 6144, out 2048 ->
    # 2048; two gates' products and three taps' multiply-adds a channel
    assert lfm2_step.mixer_flops_per_token(c, "sconv") == (
        2 * (2048 * 6144 + 2048 * 2048) + 2048 * 8)
    assert lfm2_step.mixer_flops_per_token(c, "gqa") == 2 * (
        2 * 2048 * 2048 + 2 * 2048 * 512)
    assert lfm2_step.ffn_flops_per_token(c, True) == 2 * 3 * 2048 * 11776
    # the router over 64 and half an expert of 3 x 2048 x 1536 a token
    assert lfm2_step.ffn_flops_per_token(c, False) == 2 * (
        2048 * 64 + 0.5 * 9_437_184)
    assert lfm2_step.pair_flops(c) == 32 * 2 * (64 + 64)
    by = lfm2_step.forward_flops_by_kind(c)
    total = sum(by.values())
    assert lfm2_step.model_flops(c) == 3 * total
    assert by["head"] == 2 * 2048 * 8192 * 16384
    # the five conv mixers the largest part, the one dense layer a third,
    # the six expert layers an eighth, two attention layers a tenth
    assert 0.36 < by["sconv"] / total < 0.38
    assert 0.31 < by["dense_ffn"] / total < 0.33
    assert 0.12 < by["experts"] / total < 0.14
    assert 0.10 < by["gqa"] / total < 0.11
    assert 0.07 < by["head"] / total < 0.08
    assert 22.0e12 < lfm2_step.model_flops(c) < 22.4e12


def test_the_gated_convolutions_bytes_are_one_pass_each_way():
    c = load("perf", "configs", CONFIG + ".json")
    one = gated_short_conv.cost_per_token(2048, 3)
    # forward: [B | C | x] read, y written; backward: [B | C | x] and dy
    # read, d[B | C | x] written: eleven float32 values a channel
    assert one["bytes"] == 4 * 2048 * 11
    assert one["flops"] == 3 * 2048 * (2 + 2 * 3)
    job = gated_short_conv.cost(c)
    assert job["bytes"] == 5 * 16384 * one["bytes"]
    seconds, bound = gated_short_conv.least_seconds(c, PEAKS)
    assert bound == "bytes" and seconds == job["bytes"] / 819e9


def test_the_benchmarks_layers_are_the_programs():
    from predictionio_tpu.models import encoder

    for name in ("configs", "tests/tiny"):
        c = load("perf", *name.split("/"), CONFIG + ".json")
        cfg = encoder.EncoderConfig.from_dict(c)
        assert tuple(gated_short_conv.layer_kinds(c)) == cfg.kinds
        assert lfm2_step.dense_layers(c) == cfg.n_dense
        assert cfg.sconv_kernel == c["conv_L_cache"]
        assert lfm2_step.held_picks_per_token(c) == (
            cfg.num_experts_per_tok * cfg.n_routed_experts
            / cfg.experts_total)


@pytest.mark.parametrize("op_name,scope,metrics", [
    ("jit(s)/jvp(enc.sconv)/enc.sconv.proj/dot_general", "enc.sconv.proj",
     ["fit.lfm_sconv_s"]),
    ("jit(s)/jvp(enc.sconv)/enc.sconv.gate/mul", "enc.sconv.gate",
     ["fit.lfm_sconv_s", "fit.lfm_sconv_gated_s",
      "fit.lfm_sconv_gated_roofline"]),
    ("jit(s)/transpose(jvp(enc.sconv))/enc.sconv.conv/causal_conv_bwd",
     "enc.sconv.conv", ["fit.lfm_sconv_s", "fit.lfm_sconv_gated_s",
                        "fit.lfm_sconv_gated_roofline"]),
    ("jit(s)/transpose(jvp(enc.sconv))/enc.sconv.out/dot_general",
     "enc.sconv.out", ["fit.lfm_sconv_s"]),
    ("jit(s)/jvp(enc.gqa_full)/enc.gqa_full.proj/dot_general",
     "enc.gqa_full.proj", ["fit.lfm_attn_s"]),
    ("jit(s)/jvp(enc.gqa_full)/enc.gqa_full.proj/enc.gqa_full.qk_norm/rsqrt",
     "enc.gqa_full.qk_norm", ["fit.lfm_attn_s", "fit.lfm_qk_norm_s"]),
    ("jit(s)/jvp(enc.gqa_full)/enc.gqa_full.pairs/segment_attention_fwd",
     "enc.gqa_full.pairs", ["fit.lfm_attn_s"]),
    ("jit(s)/jvp(enc.dense_ffn)/while/body/dot_general", "enc.dense_ffn",
     ["fit.lfm_dense_ffn_s"]),
    ("jit(s)/jvp(enc.router)/dot_general", "enc.router",
     ["fit.lfm_router_s"]),
    ("jit(s)/jvp(enc.experts)/enc.experts.plan/sort", "enc.experts.plan",
     ["fit.lfm_router_s"]),
    ("jit(s)/transpose(jvp(enc.experts))/enc.experts/while/body/dot_general",
     "enc.experts", ["fit.lfm_experts_s"]),
    ("jit(s)/jvp(enc.head_loss)/while/body/dot_general", "enc.head_loss",
     ["fit.lfm_head_loss_s"]),
    ("jit(s)/enc.adam/sqrt", "enc.adam", ["fit.lfm_adam_s"]),
])
def test_an_op_belongs_to_the_innermost_scope(op_name, scope, metrics):
    for name in MINE[:10]:
        spec = load("perf", "layers", name + ".json")
        assert scope_busy_ad.scope_of(op_name, spec["known"]) == scope
        assert (scope in spec["scopes"]) == (name in metrics)
