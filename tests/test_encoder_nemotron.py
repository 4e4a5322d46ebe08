"""The encoder as a model whose layers are one sublayer each
(`models/encoder.py` under Nemotron-H's key names: a Mamba-2 layer with
several B/C groups under a gated norm a group, an expert feed-forward of
ungated squared-ReLU experts beside a wider shared one behind a scaled
sigmoid router, or attention without positions, each alone under its
one norm and residual) against the plain reference
(`quality/encoder_reference.py`) at small widths on the CPU: hidden 32,
8 Mamba-2 heads of 4 over a state of 6 in 4 groups, 8 experts of which 2
are held, top-2, five layers M E M * E from the published index 2.
Seeded weights, float32 throughout."""

import dataclasses
import hashlib
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
                         "nemotron3_nano_30b_1of16.json")
TINY = os.path.join(ROOT, "perf", "tests", "tiny")
VOCAB = 50
RAW = {
    "model_type": "nemotron_h", "hidden_size": 32, "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 5,
    # a published pattern, whole: the held five start at `layer_first`
    "hybrid_override_pattern": "MEMEM*EMEMEM*E", "intermediate_size": 12,
    "moe_intermediate_size": 12, "moe_shared_expert_intermediate_size": 20,
    "n_routed_experts": 2, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "mamba_num_heads": 8, "mamba_head_dim": 4,
    "ssm_state_size": 6, "n_groups": 4, "conv_kernel": 4, "chunk_size": 8,
    "expand": 2, "use_conv_bias": True, "mamba_proj_bias": False,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2", "mlp_bias": False,
    "use_bias": False, "attention_bias": False, "layer_norm_epsilon": 1e-5,
    "norm_eps": 1e-5, "rope_theta": 10000, "partial_rotary_factor": 1,
    "tie_word_embeddings": False, "vocab_size": VOCAB,
    "share": {"experts_total": 8, "expert_first": 2, "layer_first": 2,
              "layers_total": 14, "intermediate_size": None},
    "train": {"attention_block": 16, "moe_block_rows": 4, "loss_chunk": 32,
              "remat": True, "init_std": 0.3},
}
CFG = enc.EncoderConfig.from_dict(RAW)
LENGTHS = [[10, 30, 20], [40, 5, 15]]  # histories of two packed sequences
LEAVES = leaves_of(enc.param_shapes(CFG, VOCAB))
# what a control gets wrong of the model, each told from it below
ABSENCES = {"one_group": {"wrong": ("one_group",)},
            "norm_all_channels": {"wrong": ("norm_all_channels",)},
            "norm_before_gate": {"wrong": ("norm_before_gate",)},
            "relu_not_squared": {"wrong": ("relu_not_squared",)},
            "gated_expert": {"wrong": ("gated_expert",)},
            "scale_1": {"wrong": ("scale_1",)},
            "no_shared": {"wrong": ("no_shared",)},
            "no_resets": {"kda_resets": False}}


def packed():
    return packed_histories(LENGTHS)


@pytest.fixture(scope="module")
def params():
    """Weights and a router bias that picks (the buffer starts at zero)."""
    p = jax.jit(lambda k: enc.init_params(CFG, VOCAB, k))(jax.random.key(0))
    bias = 0.05 * jax.random.normal(jax.random.key(3),
                                    (CFG.n_moe, CFG.experts_total))
    return {**p, "router_bias": bias}


def trainable(params):
    return {k: v for k, v in params.items() if k != "router_bias"}


@pytest.fixture(scope="module")
def program(params):
    """((loss, aux), gradients) of the step's loss on the packed batch."""
    tokens, seg, pos = packed()
    bias = {"router_bias": params["router_bias"]}
    return jax.jit(jax.value_and_grad(
        lambda p: enc.losses({**p, **bias}, CFG, tokens, seg, pos),
        has_aux=True))(trainable(params))


def reference_loss(p, bias, **switches):
    """The reference's loss on the batch with what it routed, a sequence
    at a time; `switches`: a control's."""
    tokens, seg, pos = packed()
    with jax.default_matmul_precision("highest"):
        rows = [ref.nll_rows({**p, "router_bias": bias}, CFG, tokens[b],
                             seg[b], pos[b], **switches)
                for b in range(tokens.shape[0])]
    n = sum(ok.sum() for _, ok, _ in rows)
    picks = jnp.stack([jnp.concatenate([r[2][layer][1] for r in rows])
                       for layer in range(CFG.n_moe)])
    counts = sum(jnp.stack([c for c, _ in r[2]]) for r in rows)
    return (sum(nll.sum() for nll, _, _ in rows) / n,
            {"picks": picks, "counts": counts})


@pytest.fixture(scope="module")
def reference(params):
    return jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(
        trainable(params), params["router_bias"])


# -- the configuration ---------------------------------------------------------------

def test_the_published_keys_build_the_layers():
    assert CFG.single_sublayer
    assert CFG.kinds == ("ssd", "experts", "ssd", "gqa", "experts")
    assert (CFG.n_moe, CFG.expert_layers, CFG.layer_first) == (2, (1, 4), 2)
    assert (CFG.mamba_n_heads, CFG.mamba_d_head, CFG.mamba_d_state,
            CFG.mamba_n_groups, CFG.mamba_d_conv, CFG.mamba_chunk_size) == (
        8, 4, 6, 4, 4, 8)
    assert (CFG.router_scoring, CFG.moe_gate, CFG.routed_scaling_factor,
            CFG.router_biased) == ("sigmoid", "relu2", 2.5, True)
    assert (CFG.n_routed_experts, CFG.experts_total, CFG.expert_first,
            CFG.num_experts_per_tok, CFG.moe_intermediate_size,
            CFG.moe_shared_expert_intermediate_size) == (2, 8, 2, 2, 12, 20)
    assert (CFG.rms_norm_eps, CFG.head_dim, CFG.intermediate_size,
            CFG.tie_word_embeddings) == (1e-5, 8, 0, False)
    # the held slice alone, without its place in the model
    alone = enc.EncoderConfig.from_dict(
        {**RAW, "hybrid_override_pattern": "MEM*E",
         "share": {**RAW["share"], "layer_first": 0}})
    assert alone.kinds == CFG.kinds


@pytest.mark.parametrize("change,named", [
    ({"hybrid_override_pattern": "ME-EM*EMEMEM*E"}, "'-'"),
    ({"hybrid_override_pattern": "MEME"}, "5 letters"),
    ({"n_groups": 3}, "n_groups = 3"),
    ({"n_groups": 0}, "n_groups = 0"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act = 'silu'"),
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_group": 2}, "n_group = 2"),
    ({"topk_group": 2}, "topk_group"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings")])
def test_what_the_reader_cannot_map_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        enc.EncoderConfig.from_dict({**RAW, **change})


def test_the_tree_holds_a_norm_and_one_sublayer_a_layer(params):
    assert "dense" not in params and "moe" not in params
    assert [sorted(layer) for layer in params["layers"]] == [
        ["norm", "ssd"],
        ["experts_w1", "experts_w2", "norm", "shared_w1", "shared_w2", "w_g"],
        ["norm", "ssd"], ["gqa", "norm"],
        ["experts_w1", "experts_w2", "norm", "shared_w1", "shared_w2", "w_g"]]
    ssd, experts = params["layers"][0]["ssd"], params["layers"][1]
    # [z | x | B | C | dt']: 32 + 32 + 4 x 6 + 4 x 6 + 8
    assert ssd["w_in"].shape == (32, 120)
    assert ssd["conv_w"].shape == (4, 80) and ssd["norm"].shape == (32,)
    assert experts["experts_w1"].shape == (2, 32, 12)   # no gate column
    assert experts["experts_w2"].shape == (2, 12, 32)
    assert experts["shared_w1"].shape == (32, 20)       # its own width
    assert experts["w_g"].shape == (32, 8)
    assert params["head"].shape == (32, VOCAB)
    assert enc.init_buffers(CFG)["router_bias"].shape == (2, 8)


def test_the_published_configuration_counts_667_million_parameters():
    cfg = enc.EncoderConfig.from_json(PUBLISHED)
    assert cfg.kinds == ("ssd", "experts", "ssd", "experts", "ssd", "gqa",
                         "experts", "ssd", "experts")
    shapes = enc.param_shapes(cfg, cfg.vocab_size)
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda s: isinstance(s, tuple)))
    assert [count(layer) for layer in shapes["layers"]] == [
        38_744_896, 100_125_312, 38_744_896, 100_125_312, 38_744_896,
        23_399_040, 100_125_312, 38_744_896, 100_125_312]
    assert enc.count_parameters(cfg, cfg.vocab_size) == 666_962_944
    assert (cfg.mamba_n_groups, cfg.moe_block_rows, cfg.seqs_per_step,
            cfg.pack_len, cfg.compute_dtype) == (8, 128, 1, 8192, "bfloat16")


# -- against the plain reference -------------------------------------------------------

@pytest.mark.parametrize("what", ["loss", "ce", "counts", "picks", "load"])
def test_the_loss_the_picks_and_the_loads_equal_the_reference(
        program, reference, what):
    (loss, aux), _ = program
    (want, routed), _ = reference
    if what in ("loss", "ce"):
        close(loss if what == "loss" else aux["ce"], want)
    elif what == "counts":
        assert np.array_equal(aux["counts"], routed["counts"])
        assert aux["counts"].shape == (2, 2)
    elif what == "picks":
        assert np.array_equal(aux["picks"], routed["picks"])
        assert aux["picks"].shape == (2, 2 * 64, 2)
    else:  # every expert of the model, the held two among them
        load = np.stack([np.bincount(np.asarray(p).reshape(-1), minlength=8)
                         for p in routed["picks"]])
        assert np.array_equal(aux["load"], load)
        assert np.array_equal(load[:, 2:4], routed["counts"])


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_equals_the_reference(program, reference, leaf):
    _, grads = program
    _, want = reference
    close(enc.leaf_of(grads, leaf), enc.leaf_of(want, leaf), 1e-4)


@pytest.mark.parametrize("absence", sorted(ABSENCES))
def test_a_mechanism_is_told_from_its_absence(params, reference, absence):
    """One group for four, a norm over all channels, the norm before the
    gate, ReLU for its square, a gated expert, scale 1, the shared
    expert left out, missing resets: each moves the loss and a gradient
    far past what the program is held to."""
    (want, _), want_grads = reference
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: reference_loss(p, params["router_bias"],
                                 **ABSENCES[absence])[0]))(trainable(params))
    assert abs(float(value) - float(want)) > 2e-4 * float(want)
    off = max(float(jnp.linalg.norm(enc.leaf_of(grads, leaf)
                                    - enc.leaf_of(want_grads, leaf))
                    / jnp.linalg.norm(enc.leaf_of(want_grads, leaf)))
              for leaf in LEAVES)
    assert off > 0.05


def test_the_router_bias_picks_and_is_outside_the_gradient(params, program):
    (_, aux), grads = program
    assert "router_bias" not in grads
    tokens, seg, pos = packed()
    unbiased = enc.losses({**params, "router_bias": jnp.zeros((2, 8))}, CFG,
                          tokens, seg, pos)[1]
    assert not np.array_equal(unbiased["picks"], aux["picks"])


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The deployment: 128 experts over 16 chips, top-6, a shared expert
    every chip computes alike. The parts the sixteen shares give for one
    E layer (experts 0-7, 8-15, ..), the shared expert counted once,
    equal the uncut reference layer."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((80, 32)), jnp.float32)
    cut = dataclasses.replace(CFG, experts_total=128, n_routed_experts=8,
                              num_experts_per_tok=6)
    uncut = dataclasses.replace(cut, n_routed_experts=128, expert_first=0)
    p = jax.jit(lambda k: enc.init_params(uncut, VOCAB, k))(
        jax.random.key(7))["layers"][1]
    bias = jnp.asarray(0.05 * rng.standard_normal(128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, whole_counts, _ = jax.jit(lambda p: ref.expert_layer(
            p, bias, uncut, x))(p)
        shared = ref.ungated(x, p["shared_w1"], p["shared_w2"])
    total, seen = 0.0, []
    for first in range(0, 128, 8):
        share = dataclasses.replace(cut, expert_first=first)
        mine = dict(p, experts_w1=p["experts_w1"][first:first + 8],
                    experts_w2=p["experts_w2"][first:first + 8])
        y, routed = jax.jit(lambda m, share=share: enc.ungated_expert_ffn(
            m, bias, share, x))(mine)
        total = total + (y - shared)      # what the share's experts gave
        seen.append(routed["counts"])
    close(total + shared, whole, 1e-4)
    assert np.array_equal(np.concatenate(seen), whole_counts)
    assert int(whole_counts.sum()) == 80 * 6


def test_a_packed_batch_equals_its_histories_run_apart(params):
    """The scan's state, the convolution's taps and attention all stop
    at a history's first token: a history packed behind others gives the
    stream it gives alone."""
    tokens, seg, pos = packed()
    h, _ = jax.jit(lambda p: enc.encode(p, CFG, tokens, seg, pos))(params)
    at, l = 0, tokens.shape[1]
    for length in LENGTHS[0]:  # alone at the head of a padded sequence
        real = jnp.arange(l)[None, :] < length
        alone, _ = enc.encode(
            params, CFG, jnp.where(real, jnp.roll(tokens[:1], -at, 1), 0),
            real.astype(jnp.int32), jnp.arange(l, dtype=jnp.int32)[None, :])
        close(h[0, at:at + length], alone[0, :length], 1e-4)
        at += length


# -- the other configurations run the programs they ran --------------------------------

@pytest.mark.parametrize("name,digest", [
    ("granite_4_0_h_micro_1of8",
     "dd1be61ea86aa598eb38fdc5029205b96a4aa03f85722c7be4b4c3cff71f8517"),
    ("joyai_llm_flash_1of16",
     "067646000b180604c0d687997fb8db4b7abf48a36553445cf6caf7c2ac82c931"),
    # taken anew in PR 50: `causal_conv` gives q and k their unit length
    # itself (the kernels' epilogue on the chip), so the pass norms each
    # where it convolves it and not after all three; the same operations
    # in another order, and the step's outputs the parent's bit for bit
    ("kimi_linear_48b_1of32",
     "162a502c2a097af9d65b597b36623633cd5e61b85010e992fa864a6795bebb50"),
    ("phi4_mini_flash_1of8",
     "53c111155ae096f41c67f9aeb11cc1bec2b98f7a5e912c3850ae3022c7f91e84"),
    ("smallthinker_21b_1of4",
     "448c6e65a8b75bb8d38e86bed52243e1b8faf6ed9888fd589db2c2555e8ef3fb")])
def test_the_old_cells_step_programs_lower_to_the_parents_text(name, digest):
    """The five accepted encoder cells share `ops/ssd.py::ssd_scan` and
    the convolution (Granite), `ops/moe.py`'s router, plan and dispatch
    (JoyAI, SmallThinker), `gqa`, the head and loss and Adam with this
    model. Each tiny configuration's whole step (loss, gradients, Adam),
    lowered without debug info, is the text the parent of PR 48
    (bb7a3a5) lowers, sha256 taken there with this same code (Kimi's at
    PR 50): one B/C group runs the scan it ran, a gated expert the
    dispatch it ran."""
    cfg = enc.EncoderConfig.from_json(os.path.join(TINY, name + ".json"))
    state = jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len),
                                 jnp.int32)
    text = jax.jit(enc.train_step(cfg, 1e-3), donate_argnums=(0,)).lower(
        state, batch, batch, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_the_benchmarks_reference_is_a_copy_of_the_packages():
    """As PR 48 left it: the package's reference has since gained LFM2's
    layers (its newest copy is `perf/reference/lfm2_moe.py`, held equal
    in `tests/test_encoder_lfm2.py`), and still defines every function
    this cell's copy has, with its arguments in order."""
    from tests import test_sessionrec_encoder as held

    held.test_the_benchmarks_reference_is_a_copy_of_the_packages(
        "nemotron_h.py")


# -- through the template's train ------------------------------------------------

@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A model trained from a configuration FILE in Nemotron-H's key
    names, `encoderConfig` naming it and nothing else: packed sequences
    of two chunks of the scan."""
    path = tmp_path_factory.mktemp("enc") / "small-nemotron.json"
    path.write_text(json.dumps({
        **{k: v for k, v in RAW.items() if k != "vocab_size"},
        "hidden_size": 16, "head_dim": 4, "mamba_head_dim": 2,
        "train": {"pack_len": 16, "seqs_per_step": 2, "attention_block": 8,
                  "moe_block_rows": 4, "loss_chunk": 16, "init_std": 0.2,
                  "report_blocks": [
                      {"name": "router", "leaf": "layers.1.w_g"},
                      {"name": "w_in", "leaf": "layers.2.ssd.w_in"},
                      {"name": "up", "leaf": "layers.4.experts_w1",
                       "index": [1]}]}}))
    algo = sessionrec.SessionRecAlgorithm(params_from_dict(
        sessionrec.SessionRecAlgorithm.params_class,
        {"maxSeqLen": 16, "epochs": 2, "stepSize": 0.01,
         "encoderConfig": str(path)}))
    return algo, algo.train(WorkflowContext(seed=5), _prepared())


def test_train_reports_the_blocks_moves_the_bias_and_sets_the_gauges(trained):
    from predictionio_tpu.telemetry.registry import REGISTRY

    _, model = trained
    assert model.encoder["single_sublayer"] is True
    assert tuple(model.encoder["layer_kinds"]) == CFG.kinds
    assert model.params["router_bias"].shape == (2, 8)
    assert np.abs(model.params["router_bias"]).max() > 0  # from the load
    report = model.train_report
    assert report["params"]["router"].shape == (16, 8)
    assert report["params"]["w_in"].shape == (16, 2 * 16 + 2 * 24 + 8)
    assert report["params"]["up"].shape == (16, 12)
    assert all(np.abs(g).max() > 0 for g in report["grads"].values())
    assert report["metrics"]["picks"].shape[0] == 2
    assert list(model.session_vecs) == list(model.user_windows)
    tokens = dict(REGISTRY.get("encoder_expert_tokens").collect())
    walked = dict(REGISTRY.get("encoder_expert_block_rows").collect())
    # the E layers by their place among the held layers: 1 and 4
    mine = {(n, e) for n in "14" for e in "23"}
    assert mine <= set(walked) and mine <= set(tokens)
    for key in mine:  # whole row blocks of 4
        assert walked[key] == -(-int(tokens[key]) // 4) * 4
    chunks = dict(REGISTRY.get("encoder_ssd_chunks").collect())
    assert chunks and all(v == 2 * 2 for v in chunks.values())


@pytest.mark.parametrize("history", [["i3"], ["i3", "i7"],
                                     ["i1", "i4", "i2", "i9", "i5", "i6"]])
def test_queries_equal_the_reference_scorer(trained, history):
    """`score()` through the layers: a right-padded window, the last real
    position against the reference's forward pass on the history."""
    algo, model = trained
    single = algo.predict(model, {"items": history, "num": 20})
    want = np.asarray(ref.score(
        model.params, sessionrec._config_of(model),
        np.asarray(model.window_rows(history), np.int32)))
    got = {s["item"]: s["score"] for s in single["itemScores"]}
    assert len(got) == 20 - len(set(history))
    for item, value in got.items():
        assert abs(value - want[model.item_ids.get(item)]) < 2e-4
