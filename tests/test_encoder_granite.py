"""The encoder as a Mamba-2 hybrid (`models/encoder.py`: the scalar-decay
state-space layer in its chunked matrix form, plain softmax attention
over grouped heads without positions, a gated norm over all channels,
a tied head, four multipliers) against the plain reference
(`quality/encoder_reference.py`, the recurrence a token at a time) at
small widths on the CPU: hidden 32, four layers from a published-style
`layer_types`, every multiplier off 1 and the attention scale off
1 / sqrt(d). Seeded weights, float32 throughout."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.controller.params import params_from_dict
from predictionio_tpu.models import encoder as enc
from predictionio_tpu.quality import encoder_reference as ref
from predictionio_tpu.templates.sessionrec import engine as sessionrec
from tests.test_encoder import close
from tests.test_encoder import packed as packed_histories
from tests.test_encoder_hybrid import leaves_of
from tests.test_sessionrec_encoder import _prepared

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(ROOT, "perf", "configs",
                         "granite_4_0_h_micro_1of8.json")
VOCAB = 50
RAW = {
    "model_type": "granitemoehybrid", "hidden_size": 32,
    "intermediate_size": 8, "shared_intermediate_size": 48,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_chunk_size": 16, "position_embedding_type": "nope",
    "embedding_multiplier": 3.0, "residual_multiplier": 0.5,
    "attention_multiplier": 0.2, "logits_scaling": 2.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True, "vocab_size": VOCAB,
    "train": {"attention_block": 16, "loss_chunk": 32, "remat": True,
              "init_std": 0.1},
}
CFG = enc.EncoderConfig.from_dict(RAW)
KINDS = ("ssd", "gqa", "ssd", "ssd")
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")
# boundaries at the first (16, 32), a middle (5, 57) and the last (47)
# slot of a chunk and an attention block of 16, a history of one token
LENGTHS = [[5, 11, 16, 15, 1, 9], [32, 25, 7]]


def packed():
    return packed_histories(LENGTHS)


@pytest.fixture(scope="module")
def params():
    """The program's own init, the convolution's bias (zero there)
    stirred so that its place shows."""
    p = jax.jit(lambda k: enc.init_params(CFG, VOCAB, k))(jax.random.key(0))
    rng = np.random.default_rng(1)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + jnp.asarray(
            0.1 * rng.standard_normal(a.shape), jnp.float32)
        if "conv_bias" in str(path[-1]) else a, p)


LEAVES = leaves_of(enc.param_shapes(CFG, VOCAB))


def program_loss(cfg, params):
    tokens, seg, pos = packed()
    return jax.jit(jax.value_and_grad(
        lambda p: enc.losses(p, cfg, tokens, seg, pos)[0]))(params)


def reference_loss(cfg, params, **switches):
    tokens, seg, pos = packed()

    def loss(p):
        with jax.default_matmul_precision("highest"):
            parts = [ref.nll_sums(p, cfg, tokens[b], seg[b], pos[b],
                                  **switches) for b in range(2)]
        return sum(p[0] for p in parts) / sum(p[1] for p in parts)

    return jax.jit(jax.value_and_grad(loss))(params)


# -- the configuration ----------------------------------------------------------

def test_the_published_keys_give_the_layer_kinds_and_the_multipliers():
    assert CFG.kinds == KINDS
    assert (CFG.n_dense, CFG.n_moe) == (4, 0)
    # the feed-forward is the shared one: the experts' width is unused
    assert CFG.intermediate_size == 48
    assert (CFG.embedding_multiplier, CFG.residual_multiplier,
            CFG.attention_multiplier, CFG.logits_scaling) == (3.0, 0.5, 0.2,
                                                              2.0)
    assert CFG.mamba_chunk_size == 16
    shapes = enc.param_shapes(CFG, VOCAB)
    assert "head" not in shapes
    assert shapes["dense"][0]["ssd"]["w_in"] == (32, 64 + 64 + 8 + 8 + 4)
    assert shapes["dense"][0]["ssd"]["conv_w"] == (4, 80)
    assert shapes["dense"][1]["gqa"]["w_k"] == (32, 16)


def test_the_published_files_first_ten_layers_are_one_period():
    """`layer_types[0:10]` of the published 40: nine Mamba-2 layers and
    the attention layer at index 5, every width as published."""
    with open(PUBLISHED) as f:
        raw = json.load(f)
    assert len(raw["layer_types"]) == 40
    cfg = enc.EncoderConfig.from_dict(raw)
    assert cfg.kinds == ("ssd",) * 5 + ("gqa",) + ("ssd",) * 4
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.mamba_n_heads,
            cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.rms_norm_eps) == (2048, 8192, 64, 64, 128, 4, 32, 8, 1e-5)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (12, 0.22,
                                                              0.015625, 8)
    assert cfg.tie_word_embeddings and cfg.seqs_per_step == 1


def test_the_benchmarks_configuration_counts_772_million_parameters():
    cfg = enc.EncoderConfig.from_json(PUBLISHED)
    assert enc.count_parameters(cfg, cfg.vocab_size) == 772_160_448
    shapes = enc.param_shapes(cfg, cfg.vocab_size)
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda s: isinstance(s, tuple)))
    assert count(shapes["dense"][0]) == 76_182_976   # a Mamba-2 layer
    assert count(shapes["dense"][5]) == 60_821_504   # the attention layer


def test_a_held_slice_reads_the_published_list_from_layer_first():
    types = ["mamba", "attention", "mamba", "mamba"] * 3
    cfg = enc.EncoderConfig.from_dict(
        {**RAW, "layer_types": types, "num_hidden_layers": 3,
         "share": {"layer_first": 4, "layers_total": 12}})
    assert cfg.kinds == ("ssd", "gqa", "ssd") and cfg.layer_first == 4


@pytest.mark.parametrize("change,match", [
    ({"layer_types": ["mamba", "attention", "conv", "mamba"]}, "conv"),
    ({"layer_types": ["mamba", "attention"], "num_hidden_layers": 4},
     "4 entries"),
    ({"mamba_n_groups": 2}, "mamba_n_groups"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"num_local_experts": 8}, "num_local_experts")])
def test_what_the_two_kinds_cannot_express_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        enc.EncoderConfig.from_dict({**RAW, **change})


def test_a_kind_no_mixer_has_is_refused_by_the_parameter_tree():
    """It used to fall through to latent attention, here of width zero."""
    cfg = dataclasses.replace(CFG, layer_kinds=("ssd", "gqa", "mamba2", "ssd"))
    with pytest.raises(ValueError, match="mamba2"):
        enc.param_shapes(cfg, VOCAB)


def test_the_init_is_mamba_2s():
    state = enc.init_params(CFG, VOCAB, jax.random.key(3))
    m, a = state["dense"][0]["ssd"], state["dense"][1]["gqa"]
    assert np.allclose(np.exp(m["a_log"]), np.arange(1, 5))
    assert np.all(np.asarray(m["d_skip"]) == 1.0)
    assert np.all(np.asarray(m["norm"]) == 1.0)
    assert np.all(np.asarray(m["conv_bias"]) == 0.0)
    assert np.abs(np.asarray(m["conv_w"])).max() <= 0.5
    step = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert 0.05 < float(np.std(np.asarray(a["w_q"]))) < 0.2


# -- the mixers, each against the reference ------------------------------------

def normed(seed=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)


@pytest.mark.parametrize("chunk", [16, 64, 256])
def test_the_mamba_2_mixer_equals_the_reference(params, chunk):
    _, seg, _ = packed()
    x, p = normed(), params["dense"][2]["ssd"]
    cfg = dataclasses.replace(CFG, mamba_chunk_size=chunk)
    got = jax.jit(lambda p, x: enc.ssd(p, cfg, x, seg))(p, x)
    with jax.default_matmul_precision("highest"):
        want = [ref.ssd(p, CFG, x[b], seg[b], None, lambda fn: fn)
                for b in range(2)]
    close(got, jnp.stack(want))


def test_grouped_attention_equals_the_reference(params):
    _, seg, pos = packed()
    x, p = normed(), params["dense"][1]["gqa"]
    got = jax.jit(lambda p, x: enc.gqa(p, CFG, x, seg, pos))(p, x)
    with jax.default_matmul_precision("highest"):
        want = [ref.gqa(p, CFG, x[b], seg[b], None, lambda fn: fn)
                for b in range(2)]
    close(got, jnp.stack(want))


def test_the_attention_scale_is_the_multiplier_itself(params):
    """0.2 here, not 1 / sqrt(8): the other scale gives another result,
    and no multiplier at all means 1 / sqrt(d)."""
    _, seg, pos = packed()
    x, p = normed(), params["dense"][1]["gqa"]
    run = lambda scale: enc.gqa(  # noqa: E731
        p, dataclasses.replace(CFG, attention_multiplier=scale), x, seg, pos)
    assert np.abs(np.asarray(run(0.2) - run(8 ** -0.5))).max() > 1e-3
    close(run(0.0), run(8 ** -0.5), 1e-6)


def test_a_query_head_reads_its_groups_key_and_value_head(params):
    """Heads 0, 1 read key/value head 0 and heads 2, 3 head 1: moving
    head 1's keys leaves the first two query heads' output alone."""
    _, seg, pos = packed()
    x, p = normed(), dict(params["dense"][1]["gqa"])
    eye = {**p, "w_o": jnp.eye(32)}
    moved = {**eye, "w_k": eye["w_k"].at[:, 8:].add(0.3)}
    a, b = (enc.gqa(q, CFG, x, seg, pos) for q in (eye, moved))
    assert np.abs(np.asarray(a - b))[..., :16].max() == 0.0
    assert np.abs(np.asarray(a - b))[..., 16:].max() > 1e-4


# -- the whole loss and its gradients ------------------------------------------

@pytest.fixture(scope="module")
def gradients(params):
    return program_loss(CFG, params), reference_loss(CFG, params)


def test_the_loss_equals_the_reference(gradients):
    (loss, _), (want, _) = gradients
    close(loss, want, 1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_whole_steps_gradients_equal_the_reference(gradients, leaf):
    """Every leaf: both mixers', the gated norm's, the norms', the tied
    embedding's (which carries the lookup's gradient times the embedding
    multiplier and the head's over the logits' scaling)."""
    (_, got), (_, want) = gradients
    close(enc.leaf_of(got, leaf), enc.leaf_of(want, leaf), 5e-5)


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_multiplier_moves_the_result_as_the_reference_says(params, name):
    """From a configuration with all four at 1 (the attention scale at
    1.0, not its default), one moved: the program's loss and gradients
    follow the reference's, and are not the unmoved ones'."""
    ones = dataclasses.replace(CFG, embedding_multiplier=1.0,
                               residual_multiplier=1.0,
                               attention_multiplier=1.0, logits_scaling=1.0)
    moved = dataclasses.replace(ones, **{name: getattr(CFG, name)})
    base, _ = program_loss(ones, params)
    loss, got = program_loss(moved, params)
    want_loss, want = reference_loss(moved, params)
    close(loss, want_loss, 1e-6)
    assert abs(float(loss) - float(base)) > 1e-4 * abs(float(base))
    for leaf in ("emb", "dense.1.gqa.w_q", "dense.2.ssd.w_in",
                 "dense.3.w2"):
        close(enc.leaf_of(got, leaf), enc.leaf_of(want, leaf), 5e-5)


@pytest.mark.parametrize("switch", [
    {"kda_resets": False}, {"embedding_multiplier": 1.0},
    {"residual_multiplier": 1.0}, {"attention_multiplier": 1.0},
    {"attention_multiplier": 8 ** -0.5}, {"logits_scaling": 1.0}],
    ids=lambda s: "-".join(f"{k}={v:.3g}" for k, v in s.items()))
def test_a_controls_switch_moves_the_reference(params, switch):
    """What the benchmark's controls turn: each gives another loss
    (the attention scale hardly, on random weights) and other gradients
    of the attention layer's queries and of the embedding."""
    if "kda_resets" in switch:
        moved = reference_loss(CFG, params, **switch)
    else:
        moved = reference_loss(dataclasses.replace(CFG, **switch), params)
    sound = reference_loss(CFG, params)
    assert abs(float(moved[0]) - float(sound[0])) > 1e-5 * float(sound[0])
    for leaf in ("emb", "dense.1.gqa.w_q"):
        want = np.asarray(enc.leaf_of(sound[1], leaf))
        gap = np.abs(np.asarray(enc.leaf_of(moved[1], leaf)) - want).max()
        assert gap > 1e-3 * np.abs(want).max(), leaf


def test_a_held_slice_equals_the_same_layers_of_the_whole(params):
    """Layers 1..3 as a configuration of their own (`layer_first` 1 of
    the four), fed what layer 0 hands on."""
    tokens, seg, pos = packed()
    whole, _ = enc.encode(params, CFG, tokens, seg, pos)

    def part(first, held):
        return enc.EncoderConfig.from_dict(
            {**RAW, "num_hidden_layers": held,
             "share": {"layer_first": first, "layers_total": 4}})

    head, tail = part(0, 1), part(1, 3)
    assert head.kinds == KINDS[:1] and tail.kinds == KINDS[1:]
    h, _ = enc.encode({**params, "dense": params["dense"][:1]}, head, tokens,
                      seg, pos)
    got, _ = enc.run_blocks({**params, "dense": params["dense"][1:]}, tail,
                            h, seg, pos)
    close(got, whole, 1e-6)


def test_a_packed_batch_equals_its_histories_run_apart(params):
    """Neither kind reads across a boundary: the scan's state, the
    convolution's taps over x, B and C, the attention's keys."""
    tokens, seg, pos = packed()
    encode = jax.jit(lambda *a: enc.encode(params, CFG, *a)[0])
    h = encode(tokens, seg, pos)
    for row, lens in enumerate(LENGTHS):
        at = 0
        for ln in lens:
            one = jnp.zeros((1, 64), jnp.int32).at[0, :ln].set(
                tokens[row, at:at + ln])
            real = (jnp.arange(64) < ln)[None, :]
            h1 = encode(jnp.tile(one, (2, 1)),
                        jnp.tile(real.astype(jnp.int32), (2, 1)),
                        jnp.tile(jnp.where(real, jnp.arange(64),
                                           jnp.arange(64) - ln), (2, 1)))
            close(h[row, at:at + ln], h1[0, :ln], 1e-4)
            at += ln


@pytest.mark.parametrize("ssm_block", [None, 16])
def test_the_reference_in_runs_of_tokens_is_the_reference(params, ssm_block):
    tokens, seg, pos = packed()
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, CFG, tokens[0], seg[0], pos[0])[0]
        got = ref.forward(params, CFG, tokens[0], seg[0], pos[0],
                          q_block=16, wrap=jax.checkpoint,
                          ssm_block=ssm_block)[0]
    close(got, want, 1e-6)


def test_the_scorer_equals_the_reference_forward(params):
    tokens, _, _ = packed()
    lengths = jnp.asarray([40, 3], jnp.int32)
    got = jax.jit(lambda p, s, n: enc.score(p, CFG, s, n))(
        params, tokens, lengths)
    for row, n in enumerate((40, 3)):
        close(got[row], ref.score(params, CFG, np.asarray(tokens[row, :n])),
              1e-4)


def test_the_step_lowers_the_loss():
    tokens, seg, pos = packed()
    step = jax.jit(enc.train_step(CFG, 1e-2))
    state = jax.jit(lambda k: enc.init_state(CFG, VOCAB, k))(
        jax.random.key(0))
    first = None
    for _ in range(5):
        state, metrics = step(state, tokens, seg, pos)
        first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first
    assert all(np.isfinite(np.asarray(leaf)).all()
               for leaf in jax.tree_util.tree_leaves(state["params"]))


# -- through the template's train ------------------------------------------------

@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A model trained from a configuration FILE in Granite's key names,
    `encoderConfig` naming it and nothing else: packed sequences of two
    attention blocks and two chunks."""
    path = tmp_path_factory.mktemp("enc") / "small-granite.json"
    path.write_text(json.dumps({
        **{k: v for k, v in RAW.items() if k != "vocab_size"},
        "hidden_size": 16, "shared_intermediate_size": 24,
        "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 4,
        "mamba_chunk_size": 8,
        "train": {"pack_len": 16, "seqs_per_step": 1, "attention_block": 8,
                  "loss_chunk": 16, "init_std": 0.2,
                  "report_blocks": [
                      {"name": "a_log", "leaf": "dense.0.ssd.a_log"},
                      {"name": "w_k", "leaf": "dense.1.gqa.w_k"},
                      {"name": "emb", "leaf": "emb"}]}}))
    algo = sessionrec.SessionRecAlgorithm(params_from_dict(
        sessionrec.SessionRecAlgorithm.params_class,
        {"maxSeqLen": 16, "epochs": 2, "stepSize": 0.01,
         "encoderConfig": str(path)}))
    from predictionio_tpu.telemetry import spans
    from predictionio_tpu.telemetry.registry import REGISTRY

    before = REGISTRY.get("encoder_ssd_resets_total").value
    tl, token = spans.begin("test", "train", "RUN", "t-3")
    try:
        model = algo.train(WorkflowContext(seed=5), _prepared())
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    return (algo, model,
            REGISTRY.get("encoder_ssd_resets_total").value - before,
            [name for name, *_ in tl.spans])


def test_train_reports_the_new_blocks_and_builds_the_model(trained):
    _, model, _, _ = trained
    assert tuple(model.encoder["layer_kinds"]) == KINDS
    assert "head" not in model.params
    report = model.train_report
    assert report["params"]["a_log"].shape == (4,)
    assert report["params"]["w_k"].shape == (16, 8)
    assert all(np.abs(g).max() > 0 for g in report["grads"].values())
    assert set(model.session_vecs) == set(model.user_windows)
    assert np.isfinite(model.params["dense"][3]["ssd"]["w_out"]).all()


def test_the_gauges_and_the_counter_say_what_the_steps_held(trained):
    from predictionio_tpu.telemetry.registry import REGISTRY

    _, _, resets, names = trained
    assert resets == 2 * 12  # 12 users' histories in each of two epochs
    chunks = dict(REGISTRY.get("encoder_ssd_chunks").collect())
    boundary = dict(REGISTRY.get("encoder_ssd_boundary_chunks").collect())
    assert chunks and set(boundary) == set(chunks)
    for step in chunks:  # a step: 1 sequence of 16 in chunks of 8
        assert chunks[step] == 2 and 1 <= boundary[step] <= 2
    assert "enc.ssd.scan.jnp" in names


@pytest.mark.parametrize("history", [["i3"], ["i3", "i7"],
                                     ["i1", "i4", "i2", "i9", "i5"]])
def test_queries_equal_the_reference_scorer(trained, history):
    """`score()` through the new kinds: a right-padded window, the last
    real position against the reference's recurrence on the history."""
    algo, model, _, _ = trained
    single = algo.predict(model, {"items": history, "num": 20})
    want = np.asarray(ref.score(
        model.params, sessionrec._config_of(model),
        np.asarray(model.window_rows(history), np.int32)))
    got = {s["item"]: s["score"] for s in single["itemScores"]}
    assert len(got) == 20 - len(set(history))
    for item, value in got.items():
        assert abs(value - want[model.item_ids.get(item)]) < 2e-4
