"""The encoder as a decoder-hybrid-decoder (`models/encoder.py`: Mamba's
selective scan, windowed and full differential attention over grouped
key and value heads, a gated memory unit and a differential
cross-attention that read what an earlier layer left, LayerNorm, a tied
head) against the plain reference (`quality/encoder_reference.py`) at
small widths on the CPU: hidden 32, 8 layers whose kinds follow the
published rule with L = 8, so every kind and both carries are crossed.
Seeded weights, float32 throughout."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import encoder as enc
from predictionio_tpu.quality import encoder_reference as ref
from tests.test_encoder import close
from tests.test_encoder import packed as packed_histories
from tests.test_encoder_hybrid import leaves_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 50
RAW = {
    "model_type": "phi4flash", "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "mb_per_layer": 2, "sliding_window": 12,
    "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
    "vocab_size": VOCAB, "mamba_d_state": 4,
    "train": {"attention_block": 16, "loss_chunk": 32, "remat": True,
              "init_std": 0.1, "ssm_chunk": 16, "ssm_channels": 32},
}
CFG = enc.EncoderConfig.from_dict(RAW)
KINDS = ("mamba", "swa", "mamba", "swa", "mamba", "full", "gmu", "cross")
# boundaries at the first (16, 32), a middle (5, 57) and the last (47)
# slot of a chunk and an attention block of 16, a history of one token,
# and histories longer than the window of 12
LENGTHS = [[5, 11, 16, 15, 1, 9], [32, 25, 7]]


def packed():
    return packed_histories(LENGTHS)


@pytest.fixture(scope="module")
def params():
    """The program's own init, the biases (zero there) stirred so that
    each one's place shows."""
    p = jax.jit(lambda k: enc.init_params(CFG, VOCAB, k))(jax.random.key(0))
    rng = np.random.default_rng(1)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + jnp.asarray(
            0.1 * rng.standard_normal(a.shape), jnp.float32)
        if "bias" in str(path[-1]) else a, p)


LEAVES = leaves_of(enc.param_shapes(CFG, VOCAB))


# -- the configuration ----------------------------------------------------------

def test_the_published_keys_give_the_layer_kinds():
    assert CFG.kinds == KINDS
    assert (CFG.n_dense, CFG.n_moe) == (8, 0)
    assert (CFG.mamba_channels, CFG.dt_rank) == (64, 2)
    assert "head" not in enc.param_shapes(CFG, VOCAB)
    assert enc.hybrid_decoder_kinds(15, 5, 32, 2) == (
        "swa", "mamba", "full", "gmu", "cross")


@pytest.mark.parametrize("first,held,reads", [(6, 2, "gmu"), (7, 1, "cross"),
                                              (5, 3, "gmu")])
def test_a_slice_that_reads_what_it_does_not_make_is_refused(first, held,
                                                             reads):
    raw = {**RAW, "num_hidden_layers": held,
           "share": {"layer_first": first, "layers_total": 8}}
    with pytest.raises(ValueError, match=reads):
        enc.EncoderConfig.from_dict(raw)


def test_the_benchmarks_configuration_counts_577_million_parameters():
    cfg = enc.EncoderConfig.from_json(os.path.join(
        ROOT, "perf", "configs", "phi4_mini_flash_1of8.json"))
    assert cfg.kinds == ("swa", "mamba", "full", "gmu", "cross")
    assert (cfg.layer_first, cfg.sliding_window, cfg.mamba_channels,
            cfg.mamba_d_state, cfg.dt_rank) == (15, 512, 5120, 16, 160)
    assert enc.count_parameters(cfg, cfg.vocab_size) == 577_199_232


def test_the_init_is_mambas_and_differential_attentions(params):
    state = enc.init_params(CFG, VOCAB, jax.random.key(3))
    m, d = state["dense"][0]["mamba"], state["dense"][1]["diff"]
    assert np.allclose(np.exp(m["a_log"]), np.arange(1, 5)[None, :])
    assert np.all(np.asarray(m["d_skip"]) == 1.0)
    assert np.all(np.asarray(m["conv_bias"]) == 0.0)
    assert np.abs(np.asarray(m["conv_x"])).max() <= 0.5
    step = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert 0.02 < float(np.std(np.asarray(d["lambda_q1"]))) < 0.3
    assert np.all(np.asarray(d["sub_norm"]) == 1.0)
    assert np.all(np.asarray(state["final_norm_bias"]) == 0.0)


# -- the mixers, each against the reference ------------------------------------

def normed(seed=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)


@pytest.mark.parametrize("what", ["out", "memory"])
def test_mamba_equals_the_reference(params, what):
    _, seg, _ = packed()
    x, p = normed(), params["dense"][2]["mamba"]
    got = jax.jit(lambda p, x: enc.mamba(p, CFG, x, seg))(p, x)
    with jax.default_matmul_precision("highest"):
        want = [ref.mamba(p, CFG, x[b], seg[b], None, lambda fn: fn)
                for b in range(2)]
    at = ("out", "memory").index(what)
    close(got[at], jnp.stack([w[at] for w in want]))


def test_the_gated_memory_unit_equals_the_reference(params):
    x, p = normed(), params["dense"][6]
    m = jnp.asarray(np.random.default_rng(4).standard_normal((2, 64, 64)),
                    jnp.float32)
    got = enc.gmu(p["gmu"], CFG, x, m)
    with jax.default_matmul_precision("highest"):
        want = (jax.nn.silu(x @ p["gmu"]["w_g"]) * m) @ p["gmu"]["w_o"]
    close(got, want)


@pytest.mark.parametrize("what", ["out", "k", "v"])
@pytest.mark.parametrize("layer", [1, 3, 5])
def test_differential_attention_equals_the_reference(params, layer, what):
    """Layers 1 and 3 with the window of 12, layer 5 without."""
    _, seg, pos = packed()
    x, p = normed(), params["dense"][layer]["diff"]
    window = CFG.sliding_window if KINDS[layer] == "swa" else None
    out, (k, v) = jax.jit(lambda p, x: enc.diff_attention(
        p, CFG, x, seg, pos, layer, window=window))(p, x)
    with jax.default_matmul_precision("highest"):
        want = [ref.diff_attention(p, CFG, x[b], seg[b], layer, window,
                                   None, None, lambda fn: fn)
                for b in range(2)]
    got = {"out": out, "k": k, "v": v}[what]
    close(got, jnp.stack([{"out": w[0], "k": w[1][0], "v": w[1][1]}[what]
                          for w in want]))


def test_the_window_changes_the_result(params):
    _, seg, pos = packed()
    x, p = normed(), params["dense"][1]["diff"]
    run = lambda w: enc.diff_attention(p, CFG, x, seg, pos, 1,  # noqa: E731
                                       window=w)[0]
    assert np.abs(np.asarray(run(12) - run(None))).max() > 1e-3
    close(run(64), run(None), 1e-6)


def test_cross_attention_reads_the_keys_and_values_given(params):
    _, seg, pos = packed()
    x, p = normed(), params["dense"][7]["cross"]
    rng = np.random.default_rng(6)
    kv = tuple(jnp.asarray(rng.standard_normal((2, 64, 4, 4)), jnp.float32)
               for _ in range(2))
    got, _ = jax.jit(lambda p, x, kv: enc.diff_attention(
        p, CFG, x, seg, pos, 7, kv=kv, scope="enc.cross"))(p, x, kv)
    with jax.default_matmul_precision("highest"):
        want = [ref.diff_attention(p, CFG, x[b], seg[b], 7, None,
                                   (kv[0][b], kv[1][b]), None,
                                   lambda fn: fn)[0] for b in range(2)]
    close(got, jnp.stack(want))


def test_lambda_init_reads_the_published_index():
    assert enc.lambda_init(0) == pytest.approx(0.2)
    assert enc.lambda_init(15) == pytest.approx(0.8 - 0.6 * np.exp(-4.5))


# -- the whole loss and its gradients ------------------------------------------

@pytest.fixture(scope="module")
def gradients(params):
    tokens, seg, pos = packed()
    (loss, aux), got = jax.jit(jax.value_and_grad(
        lambda p: enc.losses(p, CFG, tokens, seg, pos), has_aux=True))(
        params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.losses(p, CFG, tokens, seg, pos)[0]))(params)
    return (loss, got), (want_loss, want)


def test_the_loss_equals_the_reference(gradients):
    (loss, _), (want, _) = gradients
    close(loss, want, 1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_whole_steps_gradients_equal_the_reference(gradients, leaf):
    """Every leaf: each mixer's, the norms', the tied embedding's (which
    carries the head's gradient too), across both carries."""
    (_, got), (_, want) = gradients
    close(enc.leaf_of(got, leaf), enc.leaf_of(want, leaf), 5e-5)


def test_the_tied_embedding_gets_the_heads_gradient_too(params, gradients):
    """The gradient of `emb` is the lookup's plus the head's: with the
    head cut off the graph it is the lookup's alone, and rows of items
    the batch never holds then get none."""
    tokens, seg, pos = packed()
    (_, got), _ = gradients

    def lookup_only(emb):
        p = {**params, "emb": emb}
        h, _ = enc.encode(p, CFG, tokens, seg, pos)
        frozen = {**params, "emb": jax.lax.stop_gradient(emb)}
        b, l = tokens.shape
        ahead = jnp.roll(seg, -1, axis=1)
        ok = (seg != 0) & (ahead == seg) & (jnp.arange(l) < l - 1)[None, :]
        return enc.cross_entropy_sum(
            frozen, CFG, h.reshape(b * l, -1),
            jnp.roll(tokens, -1, axis=1).reshape(-1),
            ok.reshape(-1))[0] / jnp.sum(ok)

    lookup = jax.jit(jax.grad(lookup_only))(params["emb"])
    unseen = np.setdiff1d(np.arange(VOCAB), np.asarray(tokens))
    assert unseen.size
    assert np.all(np.asarray(lookup)[unseen] == 0.0)
    assert np.abs(np.asarray(got["emb"])[unseen]).min() > 0.0


def test_a_held_slice_equals_the_same_layers_of_the_whole(params):
    """Layers 3..7 as a configuration of their own (`layer_first` 3 of
    8), fed what layers 0..2 hand on: the whole model's result, so the
    kinds and lam0 follow the published index, not the held one."""
    tokens, seg, pos = packed()
    whole, _ = enc.encode(params, CFG, tokens, seg, pos)

    def part(first, held):
        return enc.EncoderConfig.from_dict(
            {**RAW, "num_hidden_layers": held,
             "share": {"layer_first": first, "layers_total": 8}})

    head, tail = part(0, 3), part(3, 5)
    assert head.kinds == KINDS[:3] and tail.kinds == KINDS[3:]
    h, _ = enc.encode({**params, "dense": params["dense"][:3]}, head, tokens,
                      seg, pos)
    got, _ = enc.run_blocks({**params, "dense": params["dense"][3:]}, tail,
                            h, seg, pos)
    close(got, whole, 1e-6)


def test_a_packed_batch_equals_its_histories_run_apart(params):
    """No kind reads across a boundary: the scan's state, the
    convolution's taps, the window, the carried memory and K, V."""
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


@pytest.mark.parametrize("switch", [{"kda_resets": False},
                                    {"windowed": False},
                                    {"stale_carry": True}])
def test_a_controls_switch_moves_the_reference(params, switch):
    """What the benchmark's controls turn: each gives another loss."""
    tokens, seg, pos = packed()
    with jax.default_matmul_precision("highest"):
        sound = sum(ref.nll_sums(params, CFG, tokens[b], seg[b], pos[b])[0]
                    for b in range(2))
        moved = sum(ref.nll_sums(params, CFG, tokens[b], seg[b], pos[b],
                                 **switch)[0] for b in range(2))
    assert abs(float(moved) - float(sound)) > 1e-4 * abs(float(sound))


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
