"""The sessionrec template on the config-driven encoder: packing, the
batched model build, a configuration file named by the algorithm's
parameters, and `predict` / `batch_predict` against the reference's full
forward pass."""

import json
import os

import numpy as np
import pytest

from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.controller.params import params_from_dict
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.templates.sessionrec import engine as sessionrec
from predictionio_tpu.templates.sessionrec.engine import (
    PreparedData,
    SessionRecAlgorithm,
    pack_histories,
)


class TestPacking:
    def test_first_fit_decreasing_fills_the_sequences(self):
        seqs = [np.arange(n, dtype=np.int32) + 100 * n
                for n in (5, 3, 9, 2, 7, 6)]
        tokens, seg, pos = pack_histories(seqs, 16)
        assert tokens.shape == seg.shape == pos.shape == (2, 16)
        # 9 + 7 | 6 + 5 + 3 + 2: the longest first, each into the first
        # sequence with room
        assert [int((row != 0).sum()) for row in seg] == [16, 16]
        assert list(seg[0]) == [1] * 9 + [2] * 7
        assert list(seg[1]) == [1] * 6 + [2] * 5 + [3] * 3 + [4] * 2
        assert list(pos[0]) == list(range(9)) + list(range(7))
        assert list(tokens[0, :9]) == list(np.arange(9) + 900)

    def test_a_long_history_keeps_its_newest_items_and_padding_is_segment_0(
            self):
        tokens, seg, pos = pack_histories(
            [np.arange(40, dtype=np.int32), np.arange(5, dtype=np.int32)], 16)
        assert list(tokens[0]) == list(range(24, 40))
        assert list(seg[1]) == [1] * 5 + [0] * 11
        assert list(pos[1]) == list(range(5)) + list(range(11))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_every_event_is_placed_once(self, seed):
        rng = np.random.default_rng(seed)
        seqs = [rng.integers(0, 99, rng.integers(2, 30)).astype(np.int32)
                for _ in range(40)]
        tokens, seg, _ = pack_histories(seqs, 32)
        placed = sorted(tuple(tokens[b][seg[b] == s])
                        for b in range(len(tokens))
                        for s in range(1, seg[b].max() + 1))
        assert placed == sorted(tuple(s[-32:]) for s in seqs)


def _prepared(n_users=12, n_items=20, seed=0):
    rng = np.random.default_rng(seed)
    return PreparedData(
        item_ids=BiMap.string_int([f"i{k}" for k in range(n_items)]),
        user_seqs={f"u{k}": rng.integers(0, n_items, rng.integers(2, 14))
                   .astype(np.int32) for k in range(n_users)})


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A model trained from a configuration FILE with expert blocks and
    the MTP module, at small widths."""
    path = tmp_path_factory.mktemp("enc") / "small-moe.json"
    path.write_text(json.dumps({
        "hidden_size": 16, "intermediate_size": 24, "num_hidden_layers": 2,
        "first_k_dense_replace": 1, "num_attention_heads": 2,
        "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "moe_intermediate_size": 8,
        "n_routed_experts": 2, "num_experts_per_tok": 2,
        "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
        "share": {"experts_total": 4, "expert_first": 1},
        "train": {"pack_len": 16, "seqs_per_step": 2, "attention_block": 8,
                  "moe_block_rows": 4, "loss_chunk": 16, "init_std": 0.2,
                  "report_blocks": [{"name": "w_eh", "leaf": "mtp.w_eh"}]}}))
    algo = SessionRecAlgorithm(params_from_dict(
        SessionRecAlgorithm.params_class,
        {"maxSeqLen": 16, "epochs": 2, "stepSize": 0.01,
         "encoderConfig": str(path)}))
    return algo, algo.train(WorkflowContext(seed=5), _prepared())


class TestTheConfiguredEncoder:
    def test_train_reports_its_first_step_and_builds_the_model(self, trained):
        _, model = trained
        assert model.encoder["num_nextn_predict_layers"] == 1
        assert model.n_heads == 2 and model.n_items == 20
        report = model.train_report
        assert report["batch"][0].shape == (2, 16)
        assert report["params"]["w_eh"].shape == (32, 16)
        assert np.abs(report["grads"]["w_eh"]).max() > 0
        assert report["metrics"]["picks"].shape == (1, 32, 2)
        assert set(model.session_vecs) == set(model.user_windows)
        assert model.params["router_bias"].shape == (1, 4)

    def test_the_gauges_say_what_was_packed(self, trained):
        from predictionio_tpu.telemetry.registry import REGISTRY

        tokens = REGISTRY.get("encoder_pack_tokens").value
        cells = REGISTRY.get("encoder_pack_cells").value
        assert 0 < tokens <= cells and cells % 32 == 0
        assert REGISTRY.get("encoder_pack_fill").value == tokens / cells
        experts = dict(REGISTRY.get("encoder_expert_tokens").collect())
        assert {("0", "1"), ("0", "2"), ("mtp", "1"), ("mtp", "2")} <= set(
            experts)
        assert REGISTRY.get("encoder_tokens_total").value >= 2 * tokens

    @pytest.mark.parametrize("history", [["i3", "i7"],
                                         ["i1", "i4", "i9", "i2", "i11"],
                                         [f"i{k}" for k in range(12)]])
    def test_predict_agrees_with_the_references_forward_on_logits(
            self, trained, history):
        from predictionio_tpu.quality import encoder_reference as ref

        algo, model = trained
        single = algo.predict(model, {"items": history, "num": 20})
        batched = algo.batch_predict(
            model, [{"items": ["i5", "i6", "i8"], "num": 3},
                    {"items": history, "num": 20}])[1]
        assert single == batched
        want = np.asarray(ref.score(
            model.params, sessionrec._config_of(model),
            np.asarray(model.window_rows(history), np.int32)))
        got = {s["item"]: s["score"] for s in single["itemScores"]}
        assert len(got) == 20 - len(set(history))
        for item, value in got.items():
            assert abs(value - want[model.item_ids.get(item)]) < 2e-4

    def test_more_items_than_the_vocabulary_is_refused(self, trained, tmp_path):
        path = tmp_path / "v.json"
        path.write_text(json.dumps({
            "hidden_size": 8, "intermediate_size": 8, "num_hidden_layers": 1,
            "num_attention_heads": 2, "q_lora_rank": 8, "kv_lora_rank": 4,
            "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
            "vocab_size": 10}))
        algo = SessionRecAlgorithm(params_from_dict(
            SessionRecAlgorithm.params_class, {"encoderConfig": str(path)}))
        with pytest.raises(ValueError, match="vocabulary"):
            algo.train(WorkflowContext(seed=1), _prepared())


def test_the_shipped_engine_json_names_the_default_encoders_file():
    import os

    from predictionio_tpu.models.encoder import EncoderConfig

    here = os.path.dirname(sessionrec.__file__)
    with open(os.path.join(here, "engine.json")) as f:
        named = json.load(f)["algorithms"][0]["params"]["encoderConfig"]
    assert named == sessionrec.DEFAULT_ENCODER
    cfg = sessionrec._encoder_config(named)
    assert cfg == sessionrec._encoder_config("", 16, 1, 2)
    assert isinstance(cfg, EncoderConfig) and cfg.hidden_size == 16


def test_sizes_beside_a_configuration_file_are_refused():
    algo = SessionRecAlgorithm(params_from_dict(
        SessionRecAlgorithm.params_class,
        {"encoderConfig": sessionrec.DEFAULT_ENCODER, "embedDim": 8}))
    with pytest.raises(ValueError, match="size the default block only"):
        algo.train(WorkflowContext(seed=1), _prepared())


# -- an encoder with KDA layers, through the same route ---------------------------

_HYBRID_SCANS = {}  # what `trained_hybrid`'s train built and left behind


@pytest.fixture(scope="module")
def trained_hybrid(tmp_path_factory):
    """A model trained from a configuration FILE in Kimi Linear's key
    names: KDA, KDA, MLA (no rotation, no low-rank query), experts from
    the second layer, no MTP module."""
    path = tmp_path_factory.mktemp("enc") / "small-hybrid.json"
    path.write_text(json.dumps({
        "hidden_size": 16, "intermediate_size": 24, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 2,
        "q_lora_rank": None, "mla_use_nope": True, "kv_lora_rank": 8,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
        "moe_intermediate_size": 8, "num_experts": 2,
        "num_experts_per_token": 2, "num_shared_experts": 1,
        "routed_scaling_factor": 2.446, "num_nextn_predict_layers": 0,
        "rms_norm_eps": 1e-5,
        "linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [3],
                               "num_heads": 2, "head_dim": 8,
                               "short_conv_kernel_size": 4},
        "share": {"experts_total": 4, "expert_first": 1},
        "train": {"pack_len": 16, "seqs_per_step": 2, "attention_block": 8,
                  "moe_block_rows": 4, "loss_chunk": 16, "init_std": 0.2,
                  "kda_chunk": 16, "kda_head_block": 1,
                  "report_blocks": [
                      {"name": "a_log", "leaf": "dense.0.kda.a_log"},
                      {"name": "conv_k", "leaf": "moe.0.kda.conv_k"},
                      {"name": "w_q", "leaf": "moe.1.attn.w_q"}]}}))
    algo = SessionRecAlgorithm(params_from_dict(
        SessionRecAlgorithm.params_class,
        {"maxSeqLen": 16, "epochs": 2, "stepSize": 0.01,
         "encoderConfig": str(path)}))
    from predictionio_tpu.ops import pallas_kda
    from predictionio_tpu.telemetry import spans
    from predictionio_tpu.telemetry.registry import REGISTRY

    scans = lambda: {path: pallas_kda.SCAN_CALLS.labels(path=path).value  # noqa: E731
                     for path in pallas_kda._PATHS}
    before, scans_before = REGISTRY.get("encoder_kda_resets_total").value, scans()
    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        model = algo.train(WorkflowContext(seed=5), _prepared())
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    _HYBRID_SCANS.update(
        built={path: n - scans_before[path] for path, n in scans().items()},
        spans=[name for name, *_ in tl.spans])
    return algo, model, REGISTRY.get("encoder_kda_resets_total").value - before


class TestTheEncoderWithKdaLayers:
    def test_train_reports_the_new_blocks_and_builds_the_model(
            self, trained_hybrid):
        _, model, _ = trained_hybrid
        assert tuple(model.encoder["layer_kinds"]) == ("kda", "kda", "mla")
        report = model.train_report
        assert report["params"]["a_log"].shape == (2,)
        assert report["params"]["conv_k"].shape == (4, 16)
        assert report["params"]["w_q"].shape == (16, 24)
        assert all(np.abs(g).max() > 0 for g in report["grads"].values())
        assert report["metrics"]["picks"].shape == (2, 32, 2)
        assert "ce_mtp" not in report["metrics"]
        assert set(model.session_vecs) == set(model.user_windows)
        assert isinstance(model.params["moe"], list)
        assert np.isfinite(model.params["moe"][0]["kda"]["w_o"]).all()

    def test_the_gauges_and_the_counter_say_what_the_scan_held(
            self, trained_hybrid):
        from predictionio_tpu.telemetry.registry import REGISTRY

        _, model, resets = trained_hybrid
        # 12 users' histories, each in every one of the two epochs
        assert resets == 2 * 12
        chunks = dict(REGISTRY.get("encoder_kda_chunks").collect())
        boundary = dict(REGISTRY.get("encoder_kda_boundary_chunks").collect())
        assert chunks and set(boundary) == set(chunks)
        for step in chunks:  # a step: 2 sequences of 16 in chunks of 16
            assert chunks[step] == 2 and 1 <= boundary[step] <= 2

    def test_the_counter_and_the_timeline_say_which_scan_was_built(
            self, trained_hybrid):
        """On a CPU `kda_scan` builds the plain `jax.numpy` scan: one a
        KDA layer at least, none by the kernels, and the timeline holds
        a record for each under the path's name."""
        assert trained_hybrid
        built, names = _HYBRID_SCANS["built"], _HYBRID_SCANS["spans"]
        assert built["jnp"] >= 2 and built["kernel"] == 0
        assert names.count("enc.kda.scan.jnp") == built["jnp"]
        assert "enc.kda.scan.kernel" not in names

    @pytest.mark.parametrize("history", [["i3"], ["i3", "i7"],
                                         ["i1", "i4", "i9", "i2", "i11"],
                                         [f"i{k}" for k in range(12)]])
    def test_predict_agrees_with_the_references_forward_on_logits(
            self, trained_hybrid, history):
        """`score()` re-encodes a right-padded window: the last real
        position against the reference's recurrence on the history
        alone."""
        from predictionio_tpu.quality import encoder_reference as ref

        algo, model, _ = trained_hybrid
        single = algo.predict(model, {"items": history, "num": 20})
        batched = algo.batch_predict(
            model, [{"items": ["i5", "i6", "i8"], "num": 3},
                    {"items": history, "num": 20}])[1]
        assert single == batched
        want = np.asarray(ref.score(
            model.params, sessionrec._config_of(model),
            np.asarray(model.window_rows(history), np.int32)))
        got = {s["item"]: s["score"] for s in single["itemScores"]}
        assert len(got) == 20 - len(set(history))
        for item, value in got.items():
            assert abs(value - want[model.item_ids.get(item)]) < 2e-4


@pytest.fixture(scope="module")
def trained_sambay(tmp_path_factory):
    """A model trained from a configuration FILE in Phi-4-mini-flash's
    key names: layers 3..7 of 8 (windowed attention, Mamba, full
    attention, a gated memory unit, cross-attention), LayerNorm, a tied
    head; packed sequences of two attention blocks."""
    path = tmp_path_factory.mktemp("enc") / "small-sambay.json"
    path.write_text(json.dumps({
        "model_type": "phi4flash", "hidden_size": 16,
        "intermediate_size": 24, "num_hidden_layers": 5,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "mb_per_layer": 2, "sliding_window": 6, "layer_norm_eps": 1e-5,
        "tie_word_embeddings": True, "mamba_d_state": 4,
        "share": {"layer_first": 3, "layers_total": 8},
        "train": {"pack_len": 16, "seqs_per_step": 2, "attention_block": 8,
                  "loss_chunk": 16, "init_std": 0.2, "ssm_chunk": 8,
                  "ssm_channels": 16,
                  "report_blocks": [
                      {"name": "a_log", "leaf": "dense.1.mamba.a_log"},
                      {"name": "lambda_q1", "leaf": "dense.4.cross.lambda_q1"},
                      {"name": "emb", "leaf": "emb"}]}}))
    algo = SessionRecAlgorithm(params_from_dict(
        SessionRecAlgorithm.params_class,
        {"maxSeqLen": 16, "epochs": 2, "stepSize": 0.01,
         "encoderConfig": str(path)}))
    from predictionio_tpu.telemetry import spans
    from predictionio_tpu.telemetry.registry import REGISTRY

    before = REGISTRY.get("encoder_ssm_resets_total").value
    tl, token = spans.begin("test", "train", "RUN", "t-2")
    try:
        model = algo.train(WorkflowContext(seed=5), _prepared())
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    return (algo, model,
            REGISTRY.get("encoder_ssm_resets_total").value - before,
            [name for name, *_ in tl.spans])


class TestTheEncoderAsADecoderHybridDecoder:
    def test_train_reports_the_new_blocks_and_builds_the_model(
            self, trained_sambay):
        _, model, _, _ = trained_sambay
        assert tuple(model.encoder["layer_kinds"]) == (
            "swa", "mamba", "full", "gmu", "cross")
        assert "head" not in model.params
        report = model.train_report
        assert report["params"]["a_log"].shape == (32, 4)
        assert report["params"]["lambda_q1"].shape == (4,)
        assert all(np.abs(g).max() > 0 for g in report["grads"].values())
        assert set(model.session_vecs) == set(model.user_windows)
        assert np.isfinite(model.params["dense"][1]["mamba"]["w_out"]).all()

    def test_the_gauges_and_the_counters_say_what_the_step_held(
            self, trained_sambay):
        from predictionio_tpu.telemetry.registry import REGISTRY

        _, _, resets, names = trained_sambay
        assert resets == 2 * 12  # 12 users' histories in each of two epochs
        chunks = dict(REGISTRY.get("encoder_ssm_chunks").collect())
        boundary = dict(REGISTRY.get("encoder_ssm_boundary_chunks").collect())
        assert chunks and set(boundary) == set(chunks)
        for step in chunks:  # a step: 2 sequences of 16 in chunks of 8
            assert chunks[step] == 4 and 1 <= boundary[step] <= 4
        blocks = dict(REGISTRY.get("encoder_attn_key_blocks").collect())
        assert {kind for kind, _ in blocks} == {"window", "full"}
        causal = {v for (_, of), v in blocks.items() if of == "causal"}
        # sequences of two blocks: 3 visits each under a causal mask alone
        assert len(causal) == 1 and causal.pop() % 3 == 0
        assert all(v <= max(blocks.values()) for v in blocks.values())
        assert "enc.ssm.scan.jnp" in names

    @pytest.mark.parametrize("history", [["i3"], ["i3", "i7"],
                                         ["i1", "i4", "i2", "i9", "i5"]])
    def test_queries_equal_the_reference_scorer(self, trained_sambay,
                                                history):
        from predictionio_tpu.quality import encoder_reference as ref

        algo, model, _, _ = trained_sambay
        single = algo.predict(model, {"items": history, "num": 20})
        want = np.asarray(ref.score(
            model.params, sessionrec._config_of(model),
            np.asarray(model.window_rows(history), np.int32)))
        got = {s["item"]: s["score"] for s in single["itemScores"]}
        assert len(got) == 20 - len(set(history))
        for item, value in got.items():
            assert abs(value - want[model.item_ids.get(item)]) < 2e-4


@pytest.mark.parametrize("copy", ["kimi_linear.py", "phi4_flash.py",
                                  "granite_hybrid.py", "smallthinker.py",
                                  "nemotron_h.py"])
def test_the_benchmarks_reference_is_a_copy_of_the_packages(copy):
    """The newest copy (`perf/reference/lfm2_moe.py`) is held equal
    in `tests/test_encoder_lfm2.py`. The Kimi, Phi-4-mini-flash,
    Granite, SmallThinker and Nemotron cells' copies are the package's
    reference as PRs 32, 37, 41, 43 and 48 left it, and the benchmark's
    files: the package's still defines every function they have, with
    the arguments they have, in their order."""
    import ast

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def signatures(*parts):
        with open(os.path.join(root, *parts)) as f:
            return {node.name: [a.arg for a in node.args.args]
                    for node in ast.parse(f.read()).body
                    if isinstance(node, ast.FunctionDef)}

    package = signatures("predictionio_tpu", "quality",
                         "encoder_reference.py")
    for name, args in signatures("perf", "reference", copy).items():
        assert package[name][:len(args)] == args, name
