"""The encoder as a model that routes before it mixes (`models/encoder.py`
under SmallThinker's key names: a softmax router on the block's input,
ReLU-gated held experts with no shared one and no dense layer before
them, grouped-query attention windowed and rotated layer by layer)
against the plain reference (`quality/encoder_reference.py`) at small
widths on the CPU: hidden 32, 8 experts of which 2 are held, top-2, four
layers (full and unrotated, then three windowed and rotated), a window of
8 under histories of up to 40. Seeded weights, float32 throughout."""

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
from predictionio_tpu.ops import pallas_attention
from predictionio_tpu.quality import encoder_reference as ref
from predictionio_tpu.templates.sessionrec import engine as sessionrec
from tests.test_encoder import close
from tests.test_encoder import packed as packed_histories
from tests.test_encoder_hybrid import leaves_of
from tests.test_sessionrec_encoder import _prepared

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(ROOT, "perf", "configs",
                         "smallthinker_21b_1of4.json")
TINY = os.path.join(ROOT, "perf", "tests", "tiny")
VOCAB = 50
RAW = {
    "model_name": "smallthinker_21b_instruct", "hidden_size": 32,
    "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "moe_ffn_hidden_size": 12,
    "moe_num_primary_experts": 2, "moe_num_active_primary_experts": 2,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    # the published lists, whole: the held four start at `layer_first`
    "sliding_window_layout": [0, 1, 1, 1] * 13,
    "rope_layout": [0, 1, 1, 1] * 13, "sliding_window_size": 8,
    "rope_theta": 1500000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "vocab_size": VOCAB,
    "share": {"experts_total": 8, "expert_first": 2, "layer_first": 0,
              "layers_total": 52},
    "train": {"attention_block": 16, "moe_block_rows": 4, "loss_chunk": 32,
              "remat": True, "init_std": 0.3},
}
CFG = enc.EncoderConfig.from_dict(RAW)
LENGTHS = [[10, 30, 20], [40, 5, 15]]  # histories of two packed sequences
LEAVES = leaves_of(enc.param_shapes(CFG, VOCAB))


def packed():
    return packed_histories(LENGTHS)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: enc.init_params(CFG, VOCAB, k))(
        jax.random.key(0))


@pytest.fixture(scope="module")
def program(params):
    """((loss, aux), gradients) of the step's loss on the packed batch."""
    tokens, seg, pos = packed()
    return jax.jit(jax.value_and_grad(
        lambda p: enc.losses(p, CFG, tokens, seg, pos), has_aux=True))(params)


def reference_loss(params, **switches):
    """The reference's loss on the batch with what it routed, a sequence
    at a time; `switches`: a control's."""
    tokens, seg, pos = packed()
    with jax.default_matmul_precision("highest"):
        rows = [ref.nll_rows(params, CFG, tokens[b], seg[b], pos[b],
                             **switches) for b in range(tokens.shape[0])]
    n = sum(ok.sum() for _, ok, _ in rows)
    picks = jnp.stack([jnp.concatenate([r[2][layer][1] for r in rows])
                       for layer in range(CFG.n_moe)])
    counts = sum(jnp.stack([c for c, _ in r[2]]) for r in rows)
    return (sum(nll.sum() for nll, _, _ in rows) / n,
            {"nll_rows": jnp.stack([nll for nll, _, _ in rows]),
             "picks": picks, "counts": counts})


@pytest.fixture(scope="module")
def reference(params):
    return jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(params)


# -- the configuration ---------------------------------------------------------------

def test_the_published_keys_build_the_block():
    assert CFG.kinds == ("gqa",) * 4 and (CFG.n_dense, CFG.n_moe) == (0, 4)
    assert CFG.layer_windowed == CFG.layer_rotated == (False, True, True,
                                                       True)
    assert (CFG.sliding_window, CFG.rope_interleave, CFG.head_dim) == (
        8, False, 8)
    assert (CFG.router_scoring, CFG.router_on_block_input, CFG.moe_gate) == (
        "softmax", True, "relu")
    assert (CFG.n_routed_experts, CFG.experts_total, CFG.expert_first,
            CFG.num_experts_per_tok, CFG.n_shared_experts) == (2, 8, 2, 2, 0)
    assert not CFG.moe_stacked and not CFG.router_biased
    # the held slice of a later stage: layers 4-7 start a period anew,
    # layers 2-5 do not
    later = enc.EncoderConfig.from_dict(
        {**RAW, "share": {**RAW["share"], "layer_first": 2}})
    assert later.layer_windowed == (True, True, False, True)
    alone = enc.EncoderConfig.from_dict(
        {**RAW, "sliding_window_layout": [1, 0, 0, 1],
         "rope_layout": [0, 0, 1, 1]})
    assert alone.layer_windowed == (True, False, False, True)
    assert alone.layer_rotated == (False, False, True, True)


def test_the_tree_holds_no_shared_expert_no_dense_layer_and_no_bias(params):
    assert params["dense"] == [] and len(params["moe"]) == 4
    assert sorted(params["moe"][0]) == ["experts_w13", "experts_w2", "gqa",
                                        "norm1", "norm2", "w_g"]
    assert all(leaf.size > 0 for leaf in jax.tree_util.tree_leaves(params))
    assert enc.init_buffers(CFG) == {}
    assert params["moe"][1]["gqa"]["w_q"].shape == (32, 4 * 8)
    assert params["moe"][1]["gqa"]["w_k"].shape == (32, 2 * 8)


@pytest.mark.parametrize("key,value,says", [
    ("sliding_window_layout", [0, 1, 2, 1], "sliding_window_layout"),
    ("rope_layout", [0, 1, 1], "rope_layout"),
    ("moe_primary_router_apply_softmax", False, "softmax"),
    ("moe_gate", "gelu", "moe_gate")])
def test_what_the_program_does_not_know_is_refused(key, value, says):
    with pytest.raises(ValueError, match=says):
        enc.EncoderConfig.from_dict({**RAW, key: value})


def test_the_published_configuration_counts_656_million_parameters():
    """ISSUE 43's table: four layers of 115 512 320 (attention 20 971 520,
    router 163 840, two norms, 16 experts of 5 898 240), embedding and
    head of 37 984 x 2560 each, the final norm."""
    with open(PUBLISHED) as f:
        raw = json.load(f)
    cfg = enc.EncoderConfig.from_dict(raw)
    assert enc.count_parameters(cfg, cfg.vocab_size) == 656_529_920
    assert enc.count_parameters(cfg, cfg.vocab_size) == (
        4 * (20_971_520 + 163_840 + 5_120 + 16 * 5_898_240)
        + 2 * 37_984 * 2560 + 2560)
    assert cfg.layer_windowed == (False, True, True, True)
    assert (cfg.sliding_window, cfg.rope_theta, cfg.num_experts_per_tok,
            cfg.experts_total, cfg.head_dim) == (4096, 1500000, 6, 64, 128)
    assert (cfg.pack_len, cfg.seqs_per_step, cfg.compute_dtype) == (
        8192, 2, "bfloat16")
    whole = 52 * (20_971_520 + 163_840 + 5_120 + 64 * 5_898_240) + (
        2 * 151_936 * 2560 + 2560)
    assert 21.4e9 < whole < 21.6e9  # the published 21B, by this layout


# -- the system against the reference ---------------------------------------------

@pytest.mark.parametrize("what", ["loss", "nll_rows", "picks", "counts"])
def test_the_loss_and_the_picks_equal_the_reference(program, reference,
                                                    what):
    (loss, aux), _ = program
    (want, want_aux), _ = reference
    if what == "loss":
        return close(loss, want)
    if what == "nll_rows":  # every row's term, zero where none counts
        assert aux[what].shape == (2, 64)
        # 60 events a sequence, less the last of each of six histories
        assert int((np.asarray(aux[what]) > 0).sum()) == 2 * 60 - 6
        return close(aux[what], want_aux[what])
    assert np.array_equal(aux[what], want_aux[what])
    if what == "counts":
        assert np.asarray(aux["load"]).sum(-1).tolist() == [2 * 128] * 4


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_equals_the_reference(program, reference, leaf):
    got, want = (enc.leaf_of(grads, leaf) for (_, grads) in (program,
                                                             reference))
    assert np.abs(np.asarray(want)).max() > 0
    close(got, want, 1e-4)


def test_the_logits_equal_the_reference(params):
    tokens, seg, pos = packed()
    h, _ = jax.jit(lambda p: enc.encode(p, CFG, tokens, seg, pos))(params)
    got = enc.head_logits(params, CFG, h)
    with jax.default_matmul_precision("highest"):
        want = ref.logits_of(params, CFG, ref.forward(
            params, CFG, tokens[1], seg[1], pos[1])[0])
    close(got[1], want, 1e-4)


@pytest.mark.parametrize("switches", [
    {"windowed": False}, {"wrong": ("rotate_full",)},
    {"wrong": ("router_after_attention",)}, {"wrong": ("sigmoid_scores",)},
    {"wrong": ("silu_gate",)}], ids=lambda s: str(*s.values()))
def test_each_mechanism_is_told_from_its_absence(program, params, switches):
    """The reference with one mechanism taken away (a window that bites,
    the full layer's lack of rotation, the router on the block's input,
    the softmax over the picked, the ReLU gate) is far from the
    program, which agrees with the sound one to 2e-5."""
    (loss, aux), _ = program
    off, off_aux = reference_loss(params, **switches)
    assert abs(float(off) - float(loss)) > 1e-3 * float(loss)
    moved = np.abs(np.asarray(off_aux["nll_rows"] - aux["nll_rows"]))
    if "windowed" in switches:  # only rows at or past the window feel it
        _, _, pos = packed()
        assert moved[np.asarray(pos) < CFG.sliding_window].max() < 1e-4
        assert moved[np.asarray(pos) >= CFG.sliding_window].max() > 1e-2
    if switches.get("wrong") == ("router_after_attention",):
        assert not np.array_equal(off_aux["picks"], aux["picks"])


def test_the_kernels_run_a_rotated_windowed_layer_at_128_wide_heads(
        monkeypatch):
    """`gqa` with a window and RoPE on the Pallas path (interpret mode, a
    TPU pretended, small tiles) against the reference's mask written
    out: output and the gradient of every projection."""
    cfg = dataclasses.replace(CFG, head_dim=128, num_attention_heads=2,
                              num_key_value_heads=1, sliding_window=24,
                              attention_block=16)
    real, taken = pallas_attention.segment_pairs, []

    def spy(*a, **kw):
        taken.append((a[5], a[6]))
        return real(*a, **kw, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_attention, "segment_pairs", spy)
    monkeypatch.setattr(pallas_attention, "TILES",
                        {"fwd": (32, 16), "bwd": (16, 32)})
    _, seg, pos = packed()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    weight = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    p = jax.tree_util.tree_map(
        lambda s: jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32),
        enc._gqa_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))

    def got(p):
        return (enc.gqa(p, cfg, x, seg, pos, "enc.gqa_swa", window=24,
                        rotate=True) * weight).sum()

    def want(p):
        with jax.default_matmul_precision("highest"):
            return sum((ref.gqa(p, cfg, x[b], seg[b], None, lambda f: f,
                                pos[b], 24, True) * weight[b]).sum()
                       for b in range(2))

    value, grads = jax.value_and_grad(got)(p)
    want_value, want_grads = jax.value_and_grad(want)(p)
    assert taken == [("enc.gqa_swa.pairs", 24)]
    close(value, want_value, 1e-4)
    for name in ("w_q", "w_k", "w_v", "w_o"):
        close(grads[name], want_grads[name], 1e-4)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """SmallThinker's deployment: 64 experts over 4 chips, top-6. The
    parts the shares (experts 0-15, 16-31, 32-47, 48-63) give add up to
    the uncut reference layer: there is no shared part to count once."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((80, 32)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((80, 32)), jnp.float32)
    cut = dataclasses.replace(CFG, experts_total=64, n_routed_experts=16,
                              num_experts_per_tok=6)
    uncut = dataclasses.replace(cut, n_routed_experts=64, expert_first=0)
    p = jax.jit(lambda k: enc.init_params(uncut, VOCAB, k))(
        jax.random.key(7))["moe"][0]
    with jax.default_matmul_precision("highest"):
        whole, whole_counts, _ = jax.jit(lambda p: ref.expert_layer(
            p, None, uncut, x, router_input=h))(p)
    total, seen = 0.0, []
    for first in range(0, 64, 16):
        share = dataclasses.replace(cut, expert_first=first)
        mine = dict(p, experts_w13=p["experts_w13"][first:first + 16],
                    experts_w2=p["experts_w2"][first:first + 16])
        y, routed = jax.jit(lambda m, share=share: enc.expert_ffn(
            m, None, share, x, routing=enc._route(m, None, share, h)))(mine)
        total = total + y
        seen.append(routed["counts"])
    close(total, whole)
    assert np.array_equal(np.concatenate(seen), whole_counts)
    assert int(whole_counts.sum()) == 80 * 6


# -- the other configurations run the programs they ran --------------------------------

@pytest.mark.parametrize("name,digest", [
    ("joyai_llm_flash_1of16",
     "067646000b180604c0d687997fb8db4b7abf48a36553445cf6caf7c2ac82c931"),
    # taken anew in PR 50 (`tests/test_encoder_nemotron.py` says why)
    ("kimi_linear_48b_1of32",
     "162a502c2a097af9d65b597b36623633cd5e61b85010e992fa864a6795bebb50"),
    ("phi4_mini_flash_1of8",
     "53c111155ae096f41c67f9aeb11cc1bec2b98f7a5e912c3850ae3022c7f91e84"),
    ("granite_4_0_h_micro_1of8",
     "dd1be61ea86aa598eb38fdc5029205b96a4aa03f85722c7be4b4c3cff71f8517")])
def test_the_old_cells_step_programs_lower_to_the_parents_text(name, digest):
    """The four accepted encoder cells share `EncoderConfig`, `block`,
    `gqa`, `rope`, `ops/moe.py` and the loss with this model. Each tiny
    configuration's whole step (loss, gradients, Adam), lowered without
    debug info, is the text the parent of PR 43 (f5073d5) lowers, sha256
    taken there with this same code (Kimi's at PR 50): the same program, so the same loss
    and gradients bit for bit (the gate and the router rule default to
    what they were)."""
    cfg = enc.EncoderConfig.from_json(os.path.join(TINY, name + ".json"))
    state = jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len),
                                 jnp.int32)
    text = jax.jit(enc.train_step(cfg, 1e-3), donate_argnums=(0,)).lower(
        state, batch, batch, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_the_benchmarks_reference_is_a_copy_of_the_packages():
    """As PR 43 left it: the package's reference has since gained
    Nemotron-H's layers (its newest copy is `perf/reference/nemotron_h.py`,
    held equal in `tests/test_encoder_nemotron.py`), and still defines
    every function this cell's copy has, with its arguments in order."""
    from tests import test_sessionrec_encoder as held

    held.test_the_benchmarks_reference_is_a_copy_of_the_packages(
        "smallthinker.py")


# -- through the template's train ------------------------------------------------

@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A model trained from a configuration FILE in SmallThinker's key
    names, `encoderConfig` naming it and nothing else: packed sequences
    of two attention blocks, a window of 4 under histories of up to 9."""
    path = tmp_path_factory.mktemp("enc") / "small-smallthinker.json"
    path.write_text(json.dumps({
        **{k: v for k, v in RAW.items() if k != "vocab_size"},
        "hidden_size": 16, "head_dim": 4, "sliding_window_size": 4,
        "train": {"pack_len": 16, "seqs_per_step": 2, "attention_block": 8,
                  "moe_block_rows": 4, "loss_chunk": 16, "init_std": 0.2,
                  "report_blocks": [
                      {"name": "router", "leaf": "moe.0.w_g"},
                      {"name": "w_k", "leaf": "moe.1.gqa.w_k"},
                      {"name": "gate", "leaf": "moe.3.experts_w13",
                       "index": [1]}]}}))
    algo = sessionrec.SessionRecAlgorithm(params_from_dict(
        sessionrec.SessionRecAlgorithm.params_class,
        {"maxSeqLen": 16, "epochs": 2, "stepSize": 0.01,
         "encoderConfig": str(path)}))
    return algo, algo.train(WorkflowContext(seed=5), _prepared())


def test_train_reports_the_blocks_and_sets_the_gauges(trained):
    from predictionio_tpu.telemetry.registry import REGISTRY

    _, model = trained
    assert tuple(model.encoder["layer_windowed"]) == (False, True, True, True)
    assert "router_bias" not in model.params and "head" in model.params
    report = model.train_report
    assert report["params"]["router"].shape == (16, 8)
    assert report["params"]["gate"].shape == (16, 24)
    assert all(np.abs(g).max() > 0 for g in report["grads"].values())
    assert report["metrics"]["picks"].shape[0] == 4
    assert report["metrics"]["nll_rows"].shape == (2, 16)
    assert list(model.session_vecs) == list(model.user_windows)
    for user, window in model.user_windows.items():  # the fold's own rule
        assert np.array_equal(model.session_vecs[user],
                              model.session_vec_of(window))
    tokens = dict(REGISTRY.get("encoder_expert_tokens").collect())
    walked = dict(REGISTRY.get("encoder_expert_block_rows").collect())
    mine = {(str(n), e) for n in range(4) for e in "23"}
    assert mine <= set(walked) and mine <= set(tokens)
    for key in mine:  # whole row blocks of 4
        assert walked[key] == -(-int(tokens[key]) // 4) * 4


@pytest.mark.parametrize("history", [["i3"], ["i3", "i7"],
                                     ["i1", "i4", "i2", "i9", "i5", "i6"]])
def test_queries_equal_the_reference_scorer(trained, history):
    """`score()` through the block: a right-padded window, the last real
    position against the reference's forward pass on the history (the
    longest is longer than the window)."""
    algo, model = trained
    single = algo.predict(model, {"items": history, "num": 20})
    want = np.asarray(ref.score(
        model.params, sessionrec._config_of(model),
        np.asarray(model.window_rows(history), np.int32)))
    got = {s["item"]: s["score"] for s in single["itemScores"]}
    assert len(got) == 20 - len(set(history))
    for item, value in got.items():
        assert abs(value - want[model.item_ids.get(item)]) < 2e-4
