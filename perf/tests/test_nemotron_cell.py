"""`nemotron3nano.fit16_pack8k` on the CPU at `perf/tests/tiny/`: a
rehearsal of a sound run and of a traced one (what the driver will
expect of its last line), the controls, a broken timed path, what the
parent's program does when asked for the cell, and the operations
counts. No number of these runs is a measurement."""

import copy
import json
import time

import pytest

from perf import harness
from perf.ops import nemotron_h_step, ssd_scan, ssd_scan_grouped
from perf.readers import scope_busy_ad
from perf.tests import control_nemotron_h
from perf.tests.conftest import ROOT, load
from perf.tests.test_encoder_cell import expected_per_layer

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CELL = "nemotron3nano.fit16_pack8k"
CONFIG = "nemotron3_nano_30b_1of16"
MINE = ["fit.nh_ssd_s", "fit.nh_ssd_scan_s", "fit.nh_ssd_scan_roofline",
        "fit.nh_attn_s", "fit.nh_router_s", "fit.nh_experts_s",
        "fit.nh_shared_s", "fit.nh_head_loss_s", "fit.nh_adam_s",
        "fit.nh_step_mfu", "fit.nh_expert_load_max_over_mean",
        "fit.nh_moe_block_fill"]
SHARED = {"train.device_idle", "train.loop_busy_s", "setup.data_s",
          "setup.warmup_call_s", "setup.compile_s", "setup.window_compiles"}
SCOPES = ["enc.ssd", "enc.ssd.proj", "enc.ssd.conv", "enc.ssd.dt",
          "enc.ssd.scan", "enc.ssd.norm", "enc.ssd.out", "enc.gqa",
          "enc.gqa.proj", "enc.gqa.pairs", "enc.gqa.out", "enc.router",
          "enc.experts", "enc.experts.plan", "enc.shared", "enc.head_loss",
          "enc.adam"]


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
    `control_nemotron_h.controlled` changes it."""
    bench = copy.deepcopy(run.bench)
    entry = harness.find(bench["configs"], CONFIG, "config")
    config = control_nemotron_h.controlled(load(entry["file"]), control)
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
    for number in ("ce_rel_err", "expert_picks_moved_share",
                   "ssd_grad_max_rel_err", "attn_grad_max_rel_err",
                   "router_grad_max_rel_err", "expert_grad_max_rel_err",
                   "grad_max_rel_err", "update_sign_max_wrong_share",
                   "nonfinite_entries"):
        assert f"check {number}:" in out


def test_the_entries_are_appended_and_name_the_cell_alone(bench):
    """Found by name, wherever later PRs append theirs."""
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fit16_pack8k", 1)
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
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
    """The catalog's `config` for NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
    key by key; the pattern whole."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    config = load("perf", "configs", CONFIG + ".json")
    differ = sorted(k for k, v in published.items() if config[k] != v)
    assert differ == sorted(config["reduced"])
    assert {k: config["published"][k] for k in differ} == {
        k: published[k] for k in differ}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 8, 16384)
    assert len(published["hybrid_override_pattern"]) == 52
    assert [published["hybrid_override_pattern"].count(c)
            for c in "ME*"] == [23, 23, 6]
    assert published["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert config["share"] == {
        "experts_total": 128, "expert_first": 0, "chips_per_layer": 16,
        "vocab_ways": 8, "layer_first": 0, "layers_total": 52,
        "pipeline_stages": 6, "intermediate_size": None}
    assert config["shape"]["n_items"] == config["vocab_size"]


def test_a_traced_run_will_print_the_six_shared_metrics_and_the_twelve_new(
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
                        "fit.nh_expert_load_max_over_mean",
                        "fit.nh_moe_block_fill"}
    assert got["fit.nh_expert_load_max_over_mean"]["value"] >= 1.0
    assert 0 < got["fit.nh_moe_block_fill"]["value"] <= 100
    config, traffic = harness.load_cell(ROOT, run.bench, CELL)
    for name in MINE[:10]:
        spec = load("perf", "layers", name + ".json")
        assert spec["module"] in config["trace_names"]
        assert int(traffic[spec["per"]]) == 1


def test_the_gauges_hold_the_last_steps_rows_and_the_rows_walked(run):
    from predictionio_tpu.telemetry.registry import REGISTRY

    run()
    config = load("perf", "tests", "tiny", CONFIG + ".json")
    tokens = dict(REGISTRY.get("encoder_expert_tokens").collect())
    walked = dict(REGISTRY.get("encoder_expert_block_rows").collect())
    # the E layers at 1 and 4 of the held five, two held experts (ids 2
    # and 3); another cell's run in this process leaves its own labels
    mine = {(n, e) for n in "14" for e in "23"}
    assert mine <= set(walked) and mine <= set(tokens)
    block = config["train"]["moe_block_rows"]
    for key in mine:
        assert walked[key] == -(-int(tokens[key]) // block) * block


@pytest.mark.parametrize("control,number", [
    ("bfloat16_reference", "ce_rel_err"),
    ("no_reset_reference", "ssd_grad_max_rel_err"),
    ("one_group", "ssd_grad_max_rel_err"),
    ("norm_all_channels", "ssd_grad_max_rel_err"),
    ("norm_before_gate", "ssd_grad_max_rel_err"),
    ("relu_not_squared", "expert_grad_max_rel_err"),
    ("gated_expert", "expert_grad_max_rel_err"),
    ("scale_1", "expert_grad_max_rel_err"),
    ("no_shared", "grad_max_rel_err"),
    ("unchanged", "update_sign_max_wrong_share")])
def test_a_control_is_not_correct(run, tmp_path, capsys, control, number):
    """`perf/tests/control_nemotron_h.py`, as a CPU run holds it."""
    bench = controlled_bench(run, tmp_path, control)
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    failed = [line for line in out.splitlines() if "FAILED" in line]
    assert any(f"check {number}:" in line for line in failed), failed
    if control != "unchanged":
        assert "the program's numbers: ce_rel_err" in out
        assert control.replace("_", " ") + ": block ssd2_w_in" in out
        assert f"control {control}: " in out and ": not correct" in out


def test_several_controls_in_one_run_return_the_nearest_to_passing(
        run, tmp_path, capsys):
    """One set-up for all of them; `correct` only if some control was."""
    bench = controlled_bench(run, tmp_path, "one_group,scale_1")
    assert run(bench=bench)["correct"] is False
    out = capsys.readouterr().out
    verdicts = [line for line in out.splitlines() if "] control " in line]
    assert len(verdicts) == 2 and all("not correct" in v for v in verdicts)
    with pytest.raises(SystemExit, match="no_such"):
        control_nemotron_h.controlled(
            load("perf", "tests", "tiny", CONFIG + ".json"), "no_such")


def test_a_broken_timed_path_is_not_correct(run, monkeypatch, capsys):
    """The timed path with the norm over all channels, not one a group."""
    from predictionio_tpu.ops import ssd

    real = ssd.gated_group_norm
    monkeypatch.setattr(
        ssd, "gated_group_norm",
        lambda y, z, w, groups, eps: real(y, z, w, 1, eps))
    assert run()["correct"] is False
    out = capsys.readouterr().out
    assert any("check ssd_grad_max_rel_err:" in line
               for line in out.splitlines() if "FAILED" in line)


def test_the_parents_program_refuses_the_configuration_at_once():
    """What the parent of PR 48 does when asked for this cell: its
    `from_dict` drops the keys it does not know (the pattern among
    them), the `share` group's null overrides the top level's published
    `intermediate_size` and is dropped, and the constructor raises a
    TypeError for the missing argument before any work, where it would
    otherwise build nine zero-width latent-attention blocks in silence.
    Here the family's keys give the width (0: no dense feed-forward)."""
    import dataclasses

    from predictionio_tpu.models import encoder

    raw = load("perf", "configs", CONFIG + ".json")
    assert raw["intermediate_size"] == 1856
    assert raw["share"]["intermediate_size"] is None
    fields = {f.name: f for f in dataclasses.fields(encoder.EncoderConfig)}
    assert fields["intermediate_size"].default is dataclasses.MISSING
    flat = {**raw, **raw["share"]}  # the parent's flattening
    with pytest.raises(TypeError, match="intermediate_size"):
        # the parent's constructor call, its known keys, nulls dropped
        encoder.EncoderConfig(**{
            k: v for k, v in flat.items() if v is not None
            and k in ("hidden_size", "intermediate_size",
                      "num_hidden_layers", "num_attention_heads")})
    cfg = encoder.EncoderConfig.from_dict(raw)
    assert (cfg.intermediate_size, cfg.n_moe, cfg.single_sublayer) == (
        0, 4, True)


# -- operations --------------------------------------------------------------------

def test_the_operations_of_a_step_are_counted_layer_by_layer():
    c = load("perf", "configs", CONFIG + ".json")
    assert ssd_scan_grouped.layer_kinds(c) == [
        "ssd", "experts", "ssd", "experts", "ssd", "gqa", "experts", "ssd",
        "experts"]
    assert nemotron_h_step.held_picks_per_token(c) == 0.375
    scan = ssd_scan.cost_per_token(64, 64, 128)["flops"]
    # 2 x the matrix entries a token meets: in 2688 -> 10304, out 4096 ->
    # 2688, the taps over 6144 channels; the scan beside them
    assert nemotron_h_step.layer_flops_per_token(c, "ssd") == 2 * (
        2688 * 10304 + 4096 * 2688 + 4 * 6144) + scan
    assert nemotron_h_step.layer_flops_per_token(c, "gqa") == 2 * (
        2 * 2688 * 4096 + 2 * 2688 * 256)
    # the router over 128, 0.375 experts of 2 x 2688 x 1856, the shared
    # expert's 2 x 2688 x 3712
    assert nemotron_h_step.layer_flops_per_token(c, "experts") == 2 * (
        2688 * 128 + 0.375 * 9_977_856 + 19_955_712)
    assert nemotron_h_step.pair_flops(c) == 32 * 2 * (128 + 128)
    by = nemotron_h_step.forward_flops_by_kind(c)
    total = sum(by.values())
    assert nemotron_h_step.model_flops(c) == 3 * total
    assert by["head"] == 2 * 2688 * 16384 * 8192
    # the four M layers about half, the four E layers three tenths (the
    # shared expert most of that), the head an eighth
    assert 0.48 < by["ssd"] / total < 0.50
    assert 0.28 < by["experts"] / total < 0.31
    assert 0.12 < by["head"] / total < 0.14
    shared = 4 * 2 * 19_955_712 * 8192
    assert shared / by["experts"] > 0.8


def test_the_grouped_scans_bytes_count_eight_groups_of_b_and_c():
    c = load("perf", "configs", CONFIG + ".json")
    one = ssd_scan.cost_per_token(64, 64, 128)
    eight = ssd_scan_grouped.cost_per_token(64, 64, 128, 8)
    assert eight["flops"] == one["flops"]
    assert eight["bytes"] - one["bytes"] == 4 * 2 * 7 * 128
    assert ssd_scan_grouped.cost_per_token(64, 64, 128, 1) == one
    job = ssd_scan_grouped.cost(c)
    # four layers, 8192 tokens, forward and twice that backward
    assert job["bytes"] == 3 * 4 * 8192 * 4 * (2 * 4096 + 64 + 2 * 1024)
    seconds, bound = ssd_scan_grouped.least_seconds(c, PEAKS)
    assert bound == "bytes" and seconds == job["bytes"] / 819e9


def test_the_benchmarks_layers_are_the_programs():
    from predictionio_tpu.models import encoder

    for name in ("configs", "tests/tiny"):
        c = load("perf", *name.split("/"), CONFIG + ".json")
        cfg = encoder.EncoderConfig.from_dict(c)
        assert tuple(ssd_scan_grouped.layer_kinds(c)) == cfg.kinds
        assert cfg.mamba_n_groups == c["n_groups"]


@pytest.mark.parametrize("op_name,scope,metric", [
    ("jit(s)/jvp(enc.ssd)/enc.ssd.proj/dot_general", "enc.ssd.proj",
     "fit.nh_ssd_s"),
    ("jit(s)/transpose(jvp(enc.ssd))/enc.ssd.scan/while/body/mul",
     "enc.ssd.scan", "fit.nh_ssd_s"),
    ("jit(s)/jvp(enc.ssd)/enc.ssd.norm/rsqrt", "enc.ssd.norm",
     "fit.nh_ssd_s"),
    ("jit(s)/jvp(enc.gqa)/enc.gqa.pairs/segment_attention_fwd",
     "enc.gqa.pairs", "fit.nh_attn_s"),
    ("jit(s)/transpose(jvp(enc.gqa))/enc.gqa.out/dot_general", "enc.gqa.out",
     "fit.nh_attn_s"),
    ("jit(s)/jvp(enc.router)/dot_general", "enc.router", "fit.nh_router_s"),
    ("jit(s)/transpose(jvp(enc.router))/top_k", "enc.router",
     "fit.nh_router_s"),
    ("jit(s)/jvp(enc.experts)/enc.experts.plan/sort", "enc.experts.plan",
     "fit.nh_router_s"),
    ("jit(s)/jvp(enc.experts)/while/body/dot_general", "enc.experts",
     "fit.nh_experts_s"),
    ("jit(s)/transpose(jvp(enc.experts))/enc.experts/while/body/dot_general",
     "enc.experts", "fit.nh_experts_s"),
    ("jit(s)/jvp(enc.shared)/dot_general", "enc.shared", "fit.nh_shared_s"),
    ("jit(s)/transpose(jvp(enc.shared))/dot_general", "enc.shared",
     "fit.nh_shared_s"),
    ("jit(s)/jvp(enc.head_loss)/while/body/dot_general", "enc.head_loss",
     "fit.nh_head_loss_s"),
    ("jit(s)/enc.adam/sqrt", "enc.adam", "fit.nh_adam_s"),
])
def test_an_op_belongs_to_the_innermost_scope(op_name, scope, metric):
    scoped = [n for n in MINE[:9] if n not in ("fit.nh_ssd_scan_s",
                                               "fit.nh_ssd_scan_roofline")]
    for name in scoped:
        spec = load("perf", "layers", name + ".json")
        assert scope_busy_ad.scope_of(op_name, spec["known"]) == scope
        assert (scope in spec["scopes"]) == (name == metric)
    for name in ("fit.nh_ssd_scan_s", "fit.nh_ssd_scan_roofline"):
        spec = load("perf", "layers", name + ".json")
        assert (scope in spec["scopes"]) == (scope == "enc.ssd.scan")
