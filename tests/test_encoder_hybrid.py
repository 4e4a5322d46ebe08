"""The encoder with two kinds of token mixer (`models/encoder.py`: Kimi
Delta Attention beside latent attention without a low-rank query and
without rotation) against the plain reference
(`quality/encoder_reference.py`, the recurrence a token at a time) at
small widths on the CPU: hidden 32, layers KDA, KDA, KDA, MLA, KDA as
the published pattern has them, 8 experts of which 2 are held. Seeded
weights, float32 throughout."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import encoder as enc
from predictionio_tpu.quality import encoder_reference as ref
from tests.test_encoder import close
from tests.test_encoder import packed as packed_histories

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 50
RAW = {
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 5,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "mla_use_nope": True, "first_k_dense_replace": 1,
    "moe_intermediate_size": 12, "num_experts": 2,
    "num_experts_per_token": 3, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "num_nextn_predict_layers": 0, "vocab_size": VOCAB,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12], "head_dim": 8,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11], "num_heads": 4,
        "short_conv_kernel_size": 4},
    "share": {"experts_total": 8, "expert_first": 2},
    "train": {"kda_chunk": 16, "kda_head_block": 2, "attention_block": 16,
              "moe_block_rows": 4, "loss_chunk": 32, "remat": True,
              "init_std": 0.3},
}
CFG = enc.EncoderConfig.from_dict(RAW)
# boundaries at the first (16, 32), a middle (5, 57) and the last (47)
# slot of a chunk of 16, and a history of one token
LENGTHS = [[5, 11, 16, 15, 1, 9], [32, 25, 7]]


def packed():
    return packed_histories(LENGTHS)


@pytest.fixture(scope="module")
def params():
    state = jax.jit(lambda k: enc.init_state(CFG, VOCAB, k))(
        jax.random.key(0))
    p = {**state["params"], **state["buffers"]}
    p["router_bias"] = jnp.asarray(  # a bias that picks
        np.random.default_rng(1).standard_normal(p["router_bias"].shape)
        * 0.05, jnp.float32)
    return p


def leaves_of(shapes, prefix=""):
    if isinstance(shapes, tuple):
        return [prefix[:-1]]
    items = (shapes.items() if isinstance(shapes, dict)
             else ((str(n), s) for n, s in enumerate(shapes)))
    return [leaf for key, sub in items
            for leaf in leaves_of(sub, f"{prefix}{key}.")]


LEAVES = leaves_of(enc.param_shapes(CFG, VOCAB))


# -- the configuration ----------------------------------------------------------

def test_the_published_keys_give_the_layer_kinds_and_a_direct_query():
    assert CFG.kinds == ("kda", "kda", "kda", "mla", "kda")
    assert (CFG.n_dense, CFG.n_moe, CFG.moe_stacked) == (1, 4, False)
    assert (CFG.q_lora_rank, CFG.mla_use_nope) == (0, True)
    assert (CFG.n_routed_experts, CFG.num_experts_per_tok,
            CFG.n_shared_experts) == (2, 3, 1)
    assert (CFG.kda_num_heads, CFG.kda_head_dim, CFG.kda_conv_size) == (4, 8, 4)
    shapes = enc.param_shapes(CFG, VOCAB)
    assert set(shapes["dense"][0]) == {"kda", "norm1", "norm2", "w13", "w2"}
    assert ["kda" in b for b in shapes["moe"]] == [True, True, False, True]
    assert set(shapes["moe"][2]["attn"]) == {"w_q", "w_kva", "kv_norm",
                                             "w_kvb", "w_o"}
    assert len(LEAVES) == 100


def test_a_layer_in_both_lists_or_in_none_is_refused():
    raw = dict(RAW, linear_attn_config=dict(RAW["linear_attn_config"],
                                            kda_layers=[1, 2, 3]))
    with pytest.raises(ValueError, match="every layer is in one of"):
        enc.EncoderConfig.from_dict(raw)


def test_the_configuration_file_counts_602_million_parameters():
    """Without allocating: `param_shapes` alone. The issue's 602 434 432
    counts the four routers' bias of 256 entries each, which is a buffer
    here (no gradient, no Adam moments), so the parameters are 1024
    fewer."""
    with open(os.path.join(ROOT, "perf", "configs",
                           "kimi_linear_48b_1of32.json")) as f:
        raw = json.load(f)
    cfg = enc.EncoderConfig.from_dict(raw)
    n = enc.count_parameters(cfg, cfg.vocab_size)
    buffers = jax.eval_shape(lambda: enc.init_buffers(cfg))
    bias = sum(int(np.prod(b.shape))
               for b in jax.tree_util.tree_leaves(buffers))
    assert (n, bias, n + bias) == (602433408, 1024, 602434432)
    shapes = enc.param_shapes(cfg, cfg.vocab_size)
    count = lambda t: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            t, is_leaf=lambda s: isinstance(s, tuple)))
    assert count(shapes["dense"][0]["kda"]) == 39514272
    assert count(shapes["moe"][2]["attn"]) == 29114880
    assert count(shapes["dense"][0]) == 103219872
    assert count(shapes["moe"][0]) == 103809952 - 256
    assert count(shapes["moe"][2]) == 93410560 - 256
    assert cfg.kinds == ("kda", "kda", "kda", "mla", "kda")
    # every width, the router, the picks, the convolution and the state
    # as published
    for key, value in {"hidden_size": 2304, "intermediate_size": 9216,
                       "moe_intermediate_size": 1024, "kv_lora_rank": 512,
                       "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                       "v_head_dim": 128, "num_attention_heads": 32,
                       "num_experts_per_token": 8,
                       "routed_scaling_factor": 2.446}.items():
        assert raw[key] == value
    assert raw["q_lora_rank"] is None and raw["mla_use_nope"] is True
    assert (cfg.experts_total, cfg.num_experts_per_tok) == (256, 8)
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size) == (
        32, 128, 4)
    assert [b[0] for b in cfg.report_blocks][:3] == ["kda_w_q", "kda_w_k",
                                                     "kda_w_v"]


# -- the system against the reference, piece by piece --------------------------

def test_kda_equals_the_reference(params):
    _, seg, _ = packed()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 64, 32)),
                    jnp.float32)
    p = params["moe"][1]["kda"]
    got = jax.jit(lambda p, x: enc.kda(p, CFG, x, seg))(p, x)
    one = jax.jit(lambda p, x, seg: ref.kda(p, CFG, x, seg, None,
                                            lambda f: f))
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            close(got[b], one(p, x[b], seg[b]))


@pytest.mark.parametrize("head_block", [0, 1, 4])
def test_kda_is_the_same_however_many_heads_a_pass_takes(params, head_block):
    import dataclasses

    _, seg, _ = packed()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 64, 32)),
                    jnp.float32)
    p = params["dense"][0]["kda"]
    want = jax.jit(lambda p, x: enc.kda(p, CFG, x, seg))(p, x)
    cfg = dataclasses.replace(CFG, kda_head_block=head_block)
    close(jax.jit(lambda p, x: enc.kda(p, cfg, x, seg))(p, x), want)


def test_mla_without_rotation_and_low_rank_query_equals_the_reference(
        params):
    _, seg, pos = packed()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 64, 32)),
                    jnp.float32)
    p = params["moe"][2]["attn"]
    got = jax.jit(lambda p, x: enc.mla(p, CFG, x, seg, pos))(p, x)
    # nothing is rotated: positions only say where a history starts, so
    # the same starts under another theta give the same bits
    import dataclasses
    other = dataclasses.replace(CFG, rope_theta=3.0)
    assert np.array_equal(
        jax.jit(lambda p, x: enc.mla(p, other, x, seg, pos))(p, x), got)
    one = jax.jit(lambda p, x, seg, pos: ref.mla(p, CFG, x, seg, pos, None,
                                                 lambda f: f))
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            close(got[b], one(p, x[b], seg[b], pos[b]))


@pytest.fixture(scope="module")
def both_losses(params):
    batch = packed()
    loss, aux = jax.jit(lambda p: enc.losses(p, CFG, *batch))(params)
    return dict(aux, loss=loss), jax.jit(
        lambda p: ref.losses(p, CFG, *batch))(params)


@pytest.mark.parametrize("what", ["loss", "ce", "counts"])
def test_the_loss_and_the_loads_equal_the_reference(both_losses, what):
    got, (r_loss, r_ce, r_mtp, r_counts, _) = both_losses
    assert r_mtp is None and "ce_mtp" not in got  # no second head
    want = {"loss": r_loss, "ce": r_ce, "counts": r_counts}[what]
    if what == "counts":
        assert got[what].shape == (4, 2)
        assert np.array_equal(got[what], want)
    else:
        close(got[what], want, 1e-6)


@pytest.fixture(scope="module")
def gradients(params):
    batch = packed()
    got = jax.jit(jax.grad(lambda p: enc.losses(p, CFG, *batch)[0]))(params)
    want = jax.jit(jax.grad(lambda p: ref.losses(p, CFG, *batch)[0]))(params)
    return got, want


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_whole_steps_gradients_equal_the_reference(gradients, leaf):
    got, want = gradients
    close(enc.leaf_of(got, leaf), enc.leaf_of(want, leaf), 2e-4)


def test_a_packed_batch_equals_its_histories_run_apart(params):
    tokens, seg, pos = packed()
    h, _ = jax.jit(lambda p: enc.encode(p, CFG, tokens, seg, pos))(params)
    apart = jax.jit(lambda p, t, s, q: enc.encode(p, CFG, t, s, q)[0])
    for row, lens in enumerate(LENGTHS):
        at = 0
        for ln in lens:
            alone = np.zeros((1, 64), np.int32)
            alone[0, :ln] = tokens[row, at:at + ln]
            one_seg = jnp.asarray((np.arange(64) < ln).astype(np.int32))[None]
            one_pos = jnp.asarray(np.where(
                np.arange(64) < ln, np.arange(64),
                np.arange(64) - ln).astype(np.int32))[None]
            h1 = apart(params, jnp.asarray(alone), one_seg, one_pos)
            close(h[row, at:at + ln], h1[0, :ln], 1e-4)
            at += ln


@pytest.mark.parametrize("resets", [True, False])
def test_the_references_control_runs_on_across_boundaries(params, gradients,
                                                          resets):
    """`kda_resets=False` (the benchmark's second control): state and
    convolution run on across history boundaries, and a KDA layer's
    gradient is another one; with the resets it is the system's."""
    batch = packed()

    def loss(p):
        sums = [ref.nll_sums(p, CFG, batch[0][b], batch[1][b], batch[2][b],
                             kda_resets=resets) for b in range(2)]
        return sum(s[0] for s in sums) / sum(s[1] for s in sums)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(loss))(params)
    for leaf in ("moe.0.kda.w_q", "dense.0.kda.conv_k"):
        g, w = (np.asarray(enc.leaf_of(t, leaf)) for t in (gradients[0],
                                                           want))
        off = np.linalg.norm(g - w) / np.linalg.norm(w)
        assert off < 1e-4 if resets else off > 0.05, (leaf, off)


@pytest.mark.parametrize("kda_block", [None, 16])
def test_the_reference_in_runs_of_tokens_is_the_reference(params, kda_block):
    tokens, seg, pos = packed()
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, CFG, tokens[0], seg[0], pos[0])[0]
        got = ref.forward(params, CFG, tokens[0], seg[0], pos[0],
                          wrap=jax.checkpoint, q_block=16,
                          kda_block=kda_block)[0]
    close(got, want, 1e-6)


# -- the train step, its report, the scorer -----------------------------------------

REPORT = (("kda_w_q", "moe.0.kda.w_q", ((0, 8), (0, 16))),
          ("conv_k", "dense.0.kda.conv_k", ()),
          ("a_log", "moe.1.kda.a_log", ()),
          ("dt_bias", "moe.3.kda.dt_bias", ()),
          ("o_norm", "moe.0.kda.o_norm", ()),
          ("mla_w_q", "moe.2.attn.w_q", ((0, 16),)),
          ("router", "moe.2.w_g", ()),
          ("rows", "emb", ((0, 8),)))


def test_the_first_steps_report_holds_the_new_blocks(params):
    import dataclasses

    cfg = dataclasses.replace(CFG, report_blocks=REPORT)
    state = jax.jit(lambda k: enc.init_state(cfg, VOCAB, k))(
        jax.random.key(2))
    batch = packed()
    want = jax.jit(jax.grad(lambda p: enc.losses(
        {**p, **state["buffers"]}, cfg, *batch)[0]))(state["params"])
    before = jax.device_get(enc.report_of(cfg, state["params"]))
    after, metrics = jax.jit(enc.train_step(cfg, 0.01))(state, *batch)
    assert metrics["counts"].shape == (4, 2)
    assert metrics["picks"].shape == (4, 128, 3)
    assert "ce_mtp" not in metrics
    report = jax.jit(enc.first_step_report(cfg))(after)
    assert set(report["grads"]) == {name for name, _, _ in REPORT}
    for name, g in enc.report_of(cfg, want).items():
        close(report["grads"][name], g, 1e-6)
        moved = np.sign(np.asarray(report["params"][name]) - before[name])
        big = np.abs(np.asarray(g)) > 1e-6
        assert np.array_equal(moved[big], -np.sign(np.asarray(g))[big])


def test_the_step_lowers_the_loss():
    state = jax.jit(lambda k: enc.init_state(CFG, VOCAB, k))(
        jax.random.key(2))
    step = jax.jit(enc.train_step(CFG, 0.01))
    batch = packed()
    first = None
    for _ in range(4):
        state, metrics = step(state, *batch)
        first = first if first is not None else metrics
    assert float(metrics["loss"]) < float(first["loss"])
    assert np.any(np.asarray(state["buffers"]["router_bias"]) != 0)


@pytest.mark.parametrize("length", [1, 5, 16, 17, 23])
def test_the_scorer_on_right_padded_histories_equals_the_reference(
        params, length):
    """The last real position's logits: the padding after it is a
    history of its own, and a window that is no whole number of chunks
    is padded inside the scan."""
    tokens, _, _ = packed()
    seq = np.zeros((2, 8 if length <= 8 else 32), np.int32)
    seq[0, :length], seq[1, :3] = tokens[0, :length], tokens[1, :3]
    got = jax.jit(lambda p, s, n: enc.score(p, CFG, s, n))(
        params, jnp.asarray(seq), jnp.asarray([length, 3]))
    one = jax.jit(lambda p, h: ref.score(p, CFG, h))
    close(got[0], one(params, tokens[0, :length]), 1e-4)
    close(got[1], one(params, tokens[1, :3]), 1e-4)


# -- the other configuration runs the program it ran ---------------------------------

def test_the_joyai_step_program_lowers_to_the_parents_text():
    """`joyai.fit8_pack8k` shares `EncoderConfig`, `block`, `mla` and
    `encode` with this configuration. The tiny JoyAI configuration's
    step, lowered without debug info, is the text the parent of PR 32
    (6020916) lowers: sha256 taken there with this same code."""
    cfg = enc.EncoderConfig.from_json(os.path.join(
        ROOT, "perf", "tests", "tiny", "joyai_llm_flash_1of16.json"))
    state = jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len),
                                 jnp.int32)
    text = jax.jit(enc.train_step(cfg, 1e-3), donate_argnums=(0,)).lower(
        state, batch, batch, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "067646000b180604c0d687997fb8db4b7abf48a36553445cf6caf7c2ac82c931")
