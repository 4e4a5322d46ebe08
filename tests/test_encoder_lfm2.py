"""The encoder as a model of short convolutions and attention
(`models/encoder.py` under LFM2's key names: a doubly gated short
convolution as the mixer three layers in four, rotated grouped-query
attention under a norm a head on q and k in the fourth, a leading dense
SwiGLU layer, then sigmoid-routed SiLU-gated experts picked by a bias
buffer with the published epsilon in the weights' sum and no shared
expert, a tied head) against the plain reference
(`quality/encoder_reference.py`) at small widths on the CPU: hidden 32,
4 query and 2 key/value heads of 8, 8 experts of which 2 are held,
top-2, seven layers from the published index 1. Seeded weights, float32
throughout."""

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
from predictionio_tpu.ops import moe
from predictionio_tpu.quality import encoder_reference as ref
from predictionio_tpu.templates.sessionrec import engine as sessionrec
from tests.test_encoder import close
from tests.test_encoder import packed as packed_histories
from tests.test_encoder_hybrid import leaves_of
from tests.test_sessionrec_encoder import _prepared

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(ROOT, "perf", "configs", "lfm2_24b_a2b_1of8.json")
TINY = os.path.join(ROOT, "perf", "tests", "tiny")
VOCAB = 50
# a published `layer_types`, whole: the held seven start at `layer_first`
TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2 + [
    "full_attention", "conv"]
RAW = {
    "model_type": "lfm2_moe", "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 7, "layer_types": TYPES,
    "conv_L_cache": 3, "conv_bias": False, "intermediate_size": 40,
    "moe_intermediate_size": 12, "num_experts": 2, "num_experts_per_tok": 2,
    "num_dense_layers": 2, "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 128000, "vocab_size": VOCAB,
    "share": {"experts_total": 8, "expert_first": 2, "layer_first": 1,
              "layers_total": 12},
    "train": {"attention_block": 16, "moe_block_rows": 4, "loss_chunk": 32,
              "remat": True, "init_std": 0.3},
}
CFG = enc.EncoderConfig.from_dict(RAW)
LENGTHS = [[10, 30, 20], [40, 5, 15]]  # histories of two packed sequences
LEAVES = leaves_of(enc.param_shapes(CFG, VOCAB))
# what a control gets wrong of the model, each told from it below
ABSENCES = {name: {"wrong": (name,)} for name in (
    "no_in_gate", "no_out_gate", "gates_swapped", "silu_on_taps",
    "no_qk_norm", "norm_after_rotation", "interleaved_pairs",
    "bias_in_weights")}
ABSENCES["a_tap_across_a_boundary"] = {"kda_resets": False}


def packed():
    return packed_histories(LENGTHS)


@pytest.fixture(scope="module")
def params():
    """Weights, norms off one (the two a head too: a norm of ones hides
    where it stands) and a router bias that picks (the buffer starts at
    zero)."""
    p = jax.jit(lambda k: enc.init_params(CFG, VOCAB, k))(jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(9), 64))
    p = jax.tree_util.tree_map_with_path(
        lambda path, a: (a + 0.3 * jax.random.normal(next(keys), a.shape)
                         if "norm" in str(path[-1]) else a), p)
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


def reference_loss(p, bias, cfg=CFG, **switches):
    """The reference's loss on the batch with what it routed, a sequence
    at a time; `switches`: a control's."""
    tokens, seg, pos = packed()
    with jax.default_matmul_precision("highest"):
        rows = [ref.nll_rows({**p, "router_bias": bias}, cfg, tokens[b],
                             seg[b], pos[b], **switches)
                for b in range(tokens.shape[0])]
    n = sum(ok.sum() for _, ok, _ in rows)
    picks = jnp.stack([jnp.concatenate([r[2][layer][1] for r in rows])
                       for layer in range(cfg.n_moe)])
    counts = sum(jnp.stack([c for c, _ in r[2]]) for r in rows)
    return (sum(nll.sum() for nll, _, _ in rows) / n,
            {"picks": picks, "counts": counts})


@pytest.fixture(scope="module")
def reference(params):
    return jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(
        trainable(params), params["router_bias"])


# -- the configuration ---------------------------------------------------------------

def test_the_published_keys_build_the_layers():
    assert not CFG.single_sublayer and not CFG.moe_stacked
    assert CFG.kinds == ("sconv", "gqa", "sconv", "sconv", "sconv", "gqa",
                         "sconv")
    assert (CFG.n_dense, CFG.n_moe, CFG.layer_first) == (1, 6, 1)
    assert CFG.layer_rotated == (False, True, False, False, False, True,
                                 False)
    assert (CFG.rope_interleave, CFG.rope_theta, CFG.qk_norm,
            CFG.layer_windowed) == (False, 1000000, True, ())
    assert (CFG.router_scoring, CFG.moe_gate, CFG.routed_scaling_factor,
            CFG.router_biased, CFG.router_norm_eps) == (
        "sigmoid", "silu", 1, True, 1e-6)
    assert (CFG.n_routed_experts, CFG.experts_total, CFG.expert_first,
            CFG.num_experts_per_tok, CFG.moe_intermediate_size,
            CFG.n_shared_experts, CFG.intermediate_size) == (
        2, 8, 2, 2, 12, 0, 40)
    assert (CFG.rms_norm_eps, CFG.sconv_kernel, CFG.tie_word_embeddings) == (
        1e-5, 3, True)
    # the held slice alone, without its place in the list
    alone = enc.EncoderConfig.from_dict({**RAW, "layer_types": TYPES[1:8]})
    assert (alone.kinds, alone.n_dense) == (CFG.kinds, 1)
    # a stage after the dense layers holds none; an untied head if the
    # file says so
    later = enc.EncoderConfig.from_dict(
        {**RAW, "tie_word_embeddings": False,
         "share": {**RAW["share"], "layer_first": 4}})
    assert (later.n_dense, later.kinds[:3], later.tie_word_embeddings) == (
        0, ("sconv", "sconv", "gqa"), False)


@pytest.mark.parametrize("change,named", [
    ({"layer_types": TYPES[:3] + ["mamba"] + TYPES[4:]}, r"\['mamba'\] not"),
    ({"layer_types": TYPES[:5]}, "7 entries"),
    ({"conv_bias": True}, "conv_bias = True"),
    ({"use_expert_bias": False}, "use_expert_bias = False"),
    ({"norm_topk_prob": False}, "norm_topk_prob = False"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type = 'yarn'"),
    ({"num_experts_per_tok": 9}, "num_experts_per_tok = 9"),
    ({"num_dense_layers": 3}, "full_attention layer before num_dense_layers"),
])
def test_what_the_reader_cannot_map_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        enc.EncoderConfig.from_dict({**RAW, **change})


def test_the_older_layer_types_reader_still_refuses_these_entries():
    """A `layer_types` file without the family's `conv_L_cache` goes to
    the Mamba-2 hybrid's reader, which knows neither entry."""
    raw = {k: v for k, v in RAW.items() if k != "conv_L_cache"}
    with pytest.raises(ValueError, match="'conv', 'full_attention'"):
        enc.EncoderConfig.from_dict(raw)


def test_the_tree_holds_the_dense_layer_and_six_unlike_expert_layers(params):
    assert [sorted(layer) for layer in params["dense"]] == [
        ["norm1", "norm2", "sconv", "w13", "w2"]]
    experts = ["experts_w13", "experts_w2", "norm1", "norm2", "w_g"]
    assert [sorted(layer) for layer in params["moe"]] == [
        sorted(experts + [mixer]) for mixer in (
            "gqa", "sconv", "sconv", "sconv", "gqa", "sconv")]
    sconv, gqa = params["dense"][0]["sconv"], params["moe"][0]["gqa"]
    assert sorted(sconv) == ["taps", "w_in", "w_out"]   # no bias anywhere
    assert sconv["w_in"].shape == (32, 96) and sconv["taps"].shape == (3, 32)
    assert sorted(gqa) == ["k_norm", "q_norm", "w_k", "w_o", "w_q", "w_v"]
    assert gqa["q_norm"].shape == gqa["k_norm"].shape == (8,)
    assert params["dense"][0]["w13"].shape == (32, 80)
    assert params["moe"][1]["experts_w13"].shape == (2, 32, 24)
    assert params["moe"][1]["w_g"].shape == (32, 8)
    assert "head" not in params and params["emb"].shape == (VOCAB, 32)
    assert enc.init_buffers(CFG)["router_bias"].shape == (6, 8)
    # another model's grouped-query block holds no such leaves
    plain = dataclasses.replace(CFG, qk_norm=False)
    assert sorted(enc.param_shapes(plain, VOCAB)["moe"][0]["gqa"]) == [
        "w_k", "w_o", "w_q", "w_v"]


def test_the_taps_start_normal_like_a_matrix_and_the_norms_at_one():
    cfg = dataclasses.replace(CFG, hidden_size=256, num_attention_heads=32)
    p = jax.jit(lambda k: enc.init_params(cfg, VOCAB, k))(jax.random.key(1))
    taps = np.asarray(p["moe"][1]["sconv"]["taps"])
    assert 0.2 < taps.std() < 0.4 and np.abs(taps).max() > 0.6  # no bound
    assert np.all(np.asarray(p["moe"][0]["gqa"]["q_norm"]) == 1.0)
    assert np.all(np.asarray(p["moe"][0]["gqa"]["k_norm"]) == 1.0)


def test_the_published_configuration_counts_648_million_parameters():
    cfg = enc.EncoderConfig.from_json(PUBLISHED)
    assert cfg.kinds == ("sconv", "gqa", "sconv", "sconv", "sconv", "gqa",
                         "sconv")
    shapes = enc.param_shapes(cfg, cfg.vocab_size)
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda s: isinstance(s, tuple)))
    assert count(shapes["dense"][0]["sconv"]) == 16_783_360
    assert count(shapes["moe"][0]["gqa"]) == 10_485_888
    assert [count(layer) for layer in shapes["dense"] + shapes["moe"]] == [
        89_139_200, 86_118_528, 92_416_000, 92_416_000, 92_416_000,
        86_118_528, 92_416_000]
    assert enc.count_parameters(cfg, cfg.vocab_size) == 647_819_520
    assert (cfg.intermediate_size, cfg.moe_block_rows, cfg.seqs_per_step,
            cfg.pack_len, cfg.compute_dtype, cfg.rope_theta) == (
        11776, 256, 2, 8192, "bfloat16", 1000000)


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
        assert aux["counts"].shape == (6, 2)
    elif what == "picks":
        assert np.array_equal(aux["picks"], routed["picks"])
        assert aux["picks"].shape == (6, 2 * 64, 2)
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


def told_apart(params, reference, value, grads, loss=2e-4):
    (want, _), want_grads = reference
    assert abs(float(value) - float(want)) > loss * float(want)
    off = max(float(jnp.linalg.norm(enc.leaf_of(grads, leaf)
                                    - enc.leaf_of(want_grads, leaf))
                    / jnp.linalg.norm(enc.leaf_of(want_grads, leaf)))
              for leaf in LEAVES)
    assert off > 0.05


@pytest.mark.parametrize("absence", sorted(ABSENCES))
def test_a_mechanism_is_told_from_its_absence(params, reference, absence):
    """Either gate left out, B and C swapped, a SiLU on the taps, a tap
    that reads across a history's boundary, the norm on q and k left
    out or applied after the rotation, interleaved pairs for
    half-rotation, the bias added to the weights: each moves the loss
    and a gradient far past what the program is held to (a bias of 0.05
    under the normalisation moves the loss the least, the routers'
    gradient by a quarter)."""
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: reference_loss(p, params["router_bias"],
                                 **ABSENCES[absence])[0]))(trainable(params))
    told_apart(params, reference, value, grads,
               1e-4 if absence == "bias_in_weights" else 2e-4)


def test_four_taps_are_told_from_three(params, reference):
    """A fourth tap, three tokens back, on every convolution."""
    rng = np.random.default_rng(4)

    def wider(p):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: (jnp.concatenate(
                [jnp.asarray(0.3 * rng.standard_normal((1, a.shape[1])),
                             a.dtype), a]) if "taps" in str(path[-1]) else a),
            p)

    value, grads = jax.jit(jax.value_and_grad(
        lambda p: reference_loss(wider(p), params["router_bias"])[0]))(
        trainable(params))
    told_apart(params, reference, value, grads)


def test_an_untied_head_is_told_from_the_tied_one(params, reference):
    head = 0.3 * jax.random.normal(jax.random.key(8), (32, VOCAB))
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: reference_loss({**p, "head": head},
                                 params["router_bias"])[0]))(
        trainable(params))
    told_apart(params, reference, value, grads)
    # the tied head's gradient lands in the embedding: rows no token of
    # the batch reads still move
    _, want = reference
    tokens, _, _ = packed()
    unread = np.setdiff1d(np.arange(VOCAB), np.asarray(tokens))
    assert unread.size and np.abs(np.asarray(want["emb"])[unread]).max() > 0
    assert np.abs(np.asarray(grads["emb"])[unread]).max() == 0


def test_the_dense_layer_given_experts_is_told_from_the_dense_one(
        params, reference):
    """Published layer 1 run as an expert layer (a router and the held
    experts of layer 3 in the place of its SwiGLU)."""
    routed = dataclasses.replace(CFG, first_k_dense_replace=0)
    bias = jnp.concatenate([jnp.zeros((1, 8)), params["router_bias"]])

    def all_experts(p):
        first = {k: v for k, v in p["dense"][0].items()
                 if k not in ("w13", "w2")}
        lent = {k: p["moe"][1][k] for k in ("w_g", "experts_w13",
                                            "experts_w2")}
        return {**p, "dense": [], "moe": [{**first, **lent}, *p["moe"]]}

    value, _ = jax.jit(jax.value_and_grad(
        lambda p: reference_loss(all_experts(p), bias, routed)[0]))(
        trainable(params))
    (want, _), _ = reference
    assert abs(float(value) - float(want)) > 2e-4 * float(want)
    assert "w_g" not in params["dense"][0]


@pytest.mark.parametrize("wrong", ["no_router_eps", "router_eps_large"])
def test_the_routers_epsilon_is_told_from_none_and_from_a_larger_one(wrong):
    """w = s[idx] / (sum(s[idx]) + 1e-6). Where the scores are of the
    order of one the epsilon is under float32's last digit, so the rows
    here score near 1e-4: there 1e-6 is a quarter of a percent of the
    sum, none is told from it, and 1e-2 is told from either."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)
    p = {"w_g": jnp.asarray(0.02 * rng.standard_normal((32, 8)), jnp.float32)
         .at[0].add(-9.0),
         "experts_w13": jnp.asarray(rng.standard_normal((2, 32, 24)),
                                    jnp.float32),
         "experts_w2": jnp.asarray(rng.standard_normal((2, 12, 32)),
                                   jnp.float32)}
    x = x.at[:, 0].set(1.0)  # every logit near -9
    bias = jnp.zeros(8)
    got, routed = jax.jit(lambda p: enc.expert_ffn(
        p, bias, CFG, x, routing=enc._route(p, bias, CFG, x)))(p)
    with jax.default_matmul_precision("highest"):
        want, counts, _ = ref.expert_layer(p, bias, CFG, x)
        other, _, _ = ref.expert_layer(p, bias, CFG, x, wrong=(wrong,))
    assert int(counts.sum()) > 10
    assert np.array_equal(routed["counts"], counts)
    close(got, want, 2e-5)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(other - want).max()) > 1e-3 * scale
    assert float(jnp.abs(got - other).max()) > 1e-3 * scale


def test_the_router_bias_picks_and_is_outside_the_gradient(params, program):
    (_, aux), grads = program
    assert "router_bias" not in grads
    tokens, seg, pos = packed()
    unbiased = enc.losses({**params, "router_bias": jnp.zeros((6, 8))}, CFG,
                          tokens, seg, pos)[1]
    assert not np.array_equal(unbiased["picks"], aux["picks"])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The deployment: 64 experts over 8 chips, top-4, no shared expert.
    The parts the eight shares give for one expert layer (experts 0-7,
    8-15, ..) equal the uncut reference layer; a token none of whose
    picks a share holds gets nothing from it."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((80, 32)), jnp.float32)
    cut = dataclasses.replace(CFG, experts_total=64, n_routed_experts=8,
                              num_experts_per_tok=4)
    uncut = dataclasses.replace(cut, n_routed_experts=64, expert_first=0)
    p = jax.jit(lambda k: enc.init_params(uncut, VOCAB, k))(
        jax.random.key(7))["moe"][1]
    assert "shared_w13" not in p
    bias = jnp.asarray(0.05 * rng.standard_normal(64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, whole_counts, picks = jax.jit(lambda p: ref.expert_layer(
            p, bias, uncut, x))(p)
    total, seen, empty = 0.0, [], 0
    for first in range(0, 64, 8):
        share = dataclasses.replace(cut, expert_first=first)
        mine = dict(p, experts_w13=p["experts_w13"][first:first + 8],
                    experts_w2=p["experts_w2"][first:first + 8])
        y, routed = jax.jit(lambda m, share=share: enc.expert_ffn(
            m, bias, share, x, routing=enc._route(m, bias, share, x)))(mine)
        total = total + y
        seen.append(routed["counts"])
        none_held = ~np.any((np.asarray(picks) >= first)
                            & (np.asarray(picks) < first + 8), axis=-1)
        assert not np.asarray(y)[none_held].any()
        empty += int(none_held.sum())
    close(total, whole, 1e-4)
    assert np.array_equal(np.concatenate(seen), whole_counts)
    assert int(whole_counts.sum()) == 80 * 4 and empty > 80


def test_a_packed_batch_equals_its_histories_run_apart(params):
    """The convolution's taps and attention both stop at a history's
    first token, and a position counts from it: a history packed behind
    others gives the stream it gives alone."""
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


def test_the_mixers_ops_stand_under_their_scopes(params):
    """`enc.sconv` with `.proj`, `.gate`, `.conv`, `.out`; the two norms a
    head under `enc.gqa_full.qk_norm`; the router and the experts apart."""
    tokens, seg, pos = packed()
    text = jax.jit(lambda p: enc.losses(p, CFG, tokens, seg, pos)[0]).lower(
        params).as_text(debug_info=True)
    for scope in ("enc.sconv.proj", "enc.sconv.gate", "enc.sconv.conv",
                  "enc.sconv.out", "enc.gqa_full.proj",
                  "enc.gqa_full.qk_norm", "enc.gqa_full.pairs",
                  "enc.gqa_full.out", "enc.dense_ffn", "enc.router",
                  "enc.experts", "enc.experts.plan", "enc.head_loss"):
        assert scope in text, scope
    assert "enc.moe" not in text and "enc.gqa_swa" not in text


# -- the other cells' programs ------------------------------------------------------

@pytest.mark.parametrize("name,digest", [
    ("joyai_llm_flash_1of16",
     "067646000b180604c0d687997fb8db4b7abf48a36553445cf6caf7c2ac82c931"),
    ("kimi_linear_48b_1of32",
     "162a502c2a097af9d65b597b36623633cd5e61b85010e992fa864a6795bebb50"),
    ("phi4_mini_flash_1of8",
     "53c111155ae096f41c67f9aeb11cc1bec2b98f7a5e912c3850ae3022c7f91e84"),
    ("granite_4_0_h_micro_1of8",
     "dd1be61ea86aa598eb38fdc5029205b96a4aa03f85722c7be4b4c3cff71f8517"),
    ("smallthinker_21b_1of4",
     "448c6e65a8b75bb8d38e86bed52243e1b8faf6ed9888fd589db2c2555e8ef3fb"),
    ("nemotron3_nano_30b_1of16",
     "80e441f901d2d7dce87db236eb4ce7d26d8d7f0c33a251f0c88c245db8c9347b")])
def test_the_old_cells_step_programs_lower_to_the_parents_text(name, digest):
    """The six accepted encoder cells share `gqa` and `rope`
    (SmallThinker, Granite, Nemotron), `ops/moe.py::route` (JoyAI, Kimi,
    Nemotron), `_mix`, `_feed_forward` and `run_blocks`, the convolution,
    the head and loss and Adam with this model. Each tiny configuration's
    whole step (loss, gradients, Adam), lowered without debug info, is the
    text the parent of PR 51 (16c2983) lowers, sha256 taken there with
    this same code: no norm where no block holds one, no epsilon where
    none is stated, the router and the experts traced where they were."""
    from tests import test_encoder_nemotron as older

    older.test_the_old_cells_step_programs_lower_to_the_parents_text(
        name, digest)


def test_the_benchmarks_reference_is_a_copy_of_the_packages():
    with open(os.path.join(ROOT, "predictionio_tpu", "quality",
                           "encoder_reference.py")) as f, \
            open(os.path.join(ROOT, "perf", "reference",
                              "lfm2_moe.py")) as g:
        assert f.read() == g.read()


# -- through the template's train ------------------------------------------------

@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A model trained from a configuration FILE in LFM2's key names,
    `encoderConfig` naming it and nothing else."""
    path = tmp_path_factory.mktemp("enc") / "small-lfm2.json"
    path.write_text(json.dumps({
        **{k: v for k, v in RAW.items() if k != "vocab_size"},
        "hidden_size": 16,
        "train": {"pack_len": 16, "seqs_per_step": 2, "attention_block": 8,
                  "moe_block_rows": 4, "loss_chunk": 16, "init_std": 0.2,
                  "report_blocks": [
                      {"name": "router", "leaf": "moe.0.w_g"},
                      {"name": "taps", "leaf": "moe.1.sconv.taps"},
                      {"name": "q_norm", "leaf": "moe.4.gqa.q_norm"},
                      {"name": "gate", "leaf": "moe.3.experts_w13",
                       "index": [1]}]}}))
    algo = sessionrec.SessionRecAlgorithm(params_from_dict(
        sessionrec.SessionRecAlgorithm.params_class,
        {"maxSeqLen": 16, "epochs": 2, "stepSize": 0.01,
         "encoderConfig": str(path)}))
    return algo, algo.train(WorkflowContext(seed=5), _prepared())


def test_train_reports_the_blocks_moves_the_bias_and_sets_the_gauges(trained):
    from predictionio_tpu.telemetry.registry import REGISTRY

    _, model = trained
    assert tuple(model.encoder["layer_kinds"]) == CFG.kinds
    assert model.encoder["qk_norm"] is True
    assert model.encoder["router_norm_eps"] == 1e-6
    assert model.params["router_bias"].shape == (6, 8)
    assert np.abs(model.params["router_bias"]).max() > 0  # from the load
    assert "head" not in model.params
    report = model.train_report
    assert report["params"]["router"].shape == (16, 8)
    assert report["params"]["taps"].shape == (3, 16)
    assert report["params"]["q_norm"].shape == (4,)
    assert report["params"]["gate"].shape == (16, 24)
    assert all(np.abs(g).max() > 0 for g in report["grads"].values())
    assert report["metrics"]["picks"].shape[0] == 6
    assert list(model.session_vecs) == list(model.user_windows)
    tokens = dict(REGISTRY.get("encoder_expert_tokens").collect())
    walked = dict(REGISTRY.get("encoder_expert_block_rows").collect())
    # the six expert layers by their place among them
    mine = {(n, e) for n in "012345" for e in "23"}
    assert mine <= set(walked) and mine <= set(tokens)
    for key in mine:  # whole row blocks of 4
        assert walked[key] == -(-int(tokens[key]) // 4) * 4


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


def test_the_route_this_model_takes_is_moes_with_the_epsilon():
    """`_route` hands `ops/moe.py::route` the configuration's epsilon."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    p = {"w_g": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)}
    bias = jnp.asarray(0.1 * rng.standard_normal(8), jnp.float32)
    idx, weights, load = enc._route(p, bias, CFG, x)
    want = moe.route(x, p["w_g"], bias, 2, 1, "sigmoid", 1e-6)
    assert np.array_equal(idx, want[0]) and np.array_equal(weights, want[1])
    assert np.array_equal(load, want[2])
    assert float(weights.sum(-1).max()) < 1.0  # the epsilon's share is gone
