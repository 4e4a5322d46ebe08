"""The config-driven next-item encoder (`models/encoder.py`,
`ops/attention.py::segment_attention`, `ops/moe.py`) against its plain
reference (`quality/encoder_reference.py`) at small widths on the CPU:
hidden 32, 8 experts of which 2 are held, one dense block, two expert
blocks and the MTP module. Seeded weights, float32 throughout."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import encoder as enc
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.attention import dense_attention, segment_attention
from predictionio_tpu.quality import encoder_reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 50
CFG = enc.EncoderConfig(
    hidden_size=32, intermediate_size=48, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    first_k_dense_replace=1, moe_intermediate_size=12, n_routed_experts=2,
    experts_total=8, expert_first=2, num_experts_per_tok=3,
    routed_scaling_factor=2.5, num_nextn_predict_layers=1, vocab_size=VOCAB,
    attention_block=16, moe_block_rows=4, loss_chunk=32, remat=True,
    init_std=0.3)
LENGTHS = [[10, 30, 20], [40, 5, 15]]  # histories of two packed sequences


def packed(lengths=LENGTHS, l=64, seed=0):
    rng = np.random.default_rng(seed)
    b = len(lengths)
    tokens = rng.integers(0, VOCAB, (b, l)).astype(np.int32)
    seg = np.zeros((b, l), np.int32)
    pos = np.zeros((b, l), np.int32)
    for row, lens in enumerate(lengths):
        at = 0
        for n, ln in enumerate(lens):
            seg[row, at:at + ln] = n + 1
            pos[row, at:at + ln] = np.arange(ln)
            at += ln
        pos[row, at:] = np.arange(l - at)
    return jnp.asarray(tokens), jnp.asarray(seg), jnp.asarray(pos)


@pytest.fixture(scope="module")
def params():
    state = jax.jit(lambda k: enc.init_state(CFG, VOCAB, k))(
        jax.random.key(0))
    rng = np.random.default_rng(1)
    p = {**state["params"], **state["buffers"]}
    for name in ("router_bias", "mtp_router_bias"):  # a bias that picks
        p[name] = jnp.asarray(rng.standard_normal(p[name].shape) * 0.05,
                              jnp.float32)
    return p


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-3)


# -- attention ---------------------------------------------------------------

@pytest.mark.parametrize("block", [8, 16, 32])
def test_blockwise_attention_equals_dense_attention(block):
    rng = np.random.default_rng(block)
    q, k = (jnp.asarray(rng.standard_normal((2, 3, 64, 12)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((2, 3, 64, 8)), jnp.float32)
    _, seg, pos = packed()

    def blockwise(q, k, v):
        return segment_attention(q, k, v, seg, pos, block=block)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=True, segment_ids=seg)

    close(blockwise(q, k, v), dense(q, k, v))
    got = jax.grad(lambda *a: (blockwise(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        close(g, w)


def test_a_sequence_one_block_holds_takes_the_dense_path():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 2, 64, 6)), jnp.float32)
    _, seg, pos = packed()
    close(segment_attention(q, q, q, seg, pos, block=64),
          segment_attention(q, q, q, seg, pos, block=16))
    with pytest.raises(ValueError):
        segment_attention(q, q, q, seg, pos, block=48)


def windowed_dense(q, k, v, seg, window):
    """Dense attention with the mask written out: s <= t, the same
    history, t - s < window."""
    t = jnp.arange(q.shape[2])
    mask = ((t[None, :] <= t[:, None])[None]
            & (seg[:, :, None] == seg[:, None, :]))
    if window is not None:
        mask = mask & (t[:, None] - t[None, :] < window)[None]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# against blocks of 16: no window, smaller than a block, a block, several
@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
@pytest.mark.parametrize("window", [None, 5, 16, 40])
def test_windowed_blockwise_attention_equals_the_mask_written_out(window,
                                                                  what):
    rng = np.random.default_rng(7)
    q, k = (jnp.asarray(rng.standard_normal((2, 3, 64, 12)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((2, 3, 64, 8)), jnp.float32)
    _, seg, pos = packed()

    def blockwise(q, k, v):
        return segment_attention(q, k, v, seg, pos, block=16, window=window)

    def dense(q, k, v):
        return windowed_dense(q, k, v, seg, window)

    if what == "forward":
        close(blockwise(q, k, v), dense(q, k, v))
        # one block holds the sequence: `dense_attention`'s own window
        return close(segment_attention(q, k, v, seg, pos, block=64,
                                       window=window), dense(q, k, v))
    at = ("dq", "dk", "dv").index(what)
    weight = jnp.asarray(rng.standard_normal((2, 3, 64, 8)), jnp.float32)
    close(jax.grad(lambda *a: (blockwise(*a) * weight).sum(), at)(q, k, v),
          jax.grad(lambda *a: (dense(*a) * weight).sum(), at)(q, k, v))


def test_a_window_skips_the_key_blocks_it_leaves_out():
    from predictionio_tpu.ops.attention import first_key_blocks

    pos = np.arange(128)[None, :]                  # one history, 8 blocks
    assert first_key_blocks(pos, 16, None, np).tolist() == [[0] * 8]
    assert first_key_blocks(pos, 16, 16, np).tolist() == [
        [0, 0, 1, 2, 3, 4, 5, 6]]
    assert first_key_blocks(pos, 16, 1, np).tolist() == [list(range(8))]
    assert first_key_blocks(pos, 16, 40, np).tolist() == [
        [0, 0, 0, 0, 1, 2, 3, 4]]
    # a history that starts at token 70 holds the window's block back
    pos = np.concatenate([np.arange(70), np.arange(58)])[None, :]
    assert first_key_blocks(pos, 16, 40, np).tolist() == [
        [0, 0, 0, 0, 1, 4, 4, 4]]


def test_without_a_window_the_traced_program_is_what_it_was():
    """The jaxpr of forward and backward at `window=None`, as the parent
    of the PR that brought the window traced it (sha256 of its text;
    jax 0.9.0): the two accepted encoder cells run this function."""
    import hashlib

    q = jax.ShapeDtypeStruct((2, 3, 64, 8), jnp.float32)
    v = jax.ShapeDtypeStruct((2, 3, 64, 4), jnp.float32)
    s = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def loss(q, k, v, seg, pos):
        return jnp.sum(segment_attention(q, k, v, seg, pos, block=16,
                                         scope="x") ** 2)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        q, q, v, s, s))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "98165cdb154b310e6c595de8ac29747726d32e67242424248b74b448629af488")


# -- the system against the reference, piece by piece --------------------------

def test_mla_equals_the_reference(params):
    _, seg, pos = packed()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 64, 32)),
                    jnp.float32)
    p = params["dense"][0]["attn"]
    got = jax.jit(lambda p, x: enc.mla(p, CFG, x, seg, pos))(p, x)
    one = jax.jit(lambda p, x, seg, pos: ref.mla(p, CFG, x, seg, pos, None,
                                                 lambda f: f))
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            close(got[b], one(p, x[b], seg[b], pos[b]))


def test_the_expert_layer_equals_the_reference(params):
    x = jnp.asarray(np.random.default_rng(5).standard_normal((96, 32)),
                    jnp.float32)
    p = jax.tree_util.tree_map(lambda a: a[1], params["moe"])
    bias = params["router_bias"][1]
    got, routed = jax.jit(
        lambda p, x: enc.expert_ffn(p, bias, CFG, x))(p, x)
    counts, load = routed["counts"], routed["load"]
    with jax.default_matmul_precision("highest"):
        want, want_counts, want_picks = jax.jit(
            lambda p, x: ref.expert_layer(p, bias, CFG, x))(p, x)
    close(got, want)
    assert np.array_equal(counts, want_counts)
    assert np.array_equal(np.sort(routed["picks"]), np.sort(want_picks))
    assert int(load.sum()) == 96 * CFG.num_experts_per_tok
    assert np.array_equal(load[CFG.expert_first:CFG.expert_first + 2], counts)


@pytest.fixture(scope="module")
def both_losses(params):
    batch = packed()
    loss, aux = jax.jit(lambda p: enc.losses(p, CFG, *batch))(params)
    return dict(aux, loss=loss), jax.jit(
        lambda p: ref.losses(p, CFG, *batch))(params)


@pytest.mark.parametrize("what", ["loss", "ce", "ce_mtp", "counts",
                                  "mtp_counts"])
def test_the_losses_and_the_loads_equal_the_reference(both_losses, what):
    got, (r_loss, r_ce, r_mtp, r_counts, r_mtp_counts) = both_losses
    got = got[what]
    want = {"loss": r_loss, "ce": r_ce, "ce_mtp": r_mtp, "counts": r_counts,
            "mtp_counts": r_mtp_counts}[what]
    if "counts" in what:
        assert np.array_equal(got, want)
    else:
        close(got, want, 1e-6)


@pytest.fixture(scope="module")
def gradients(params):
    batch = packed()
    got = jax.jit(jax.grad(lambda p: enc.losses(p, CFG, *batch)[0]))(params)
    want = jax.jit(jax.grad(lambda p: ref.losses(p, CFG, *batch)[0]))(params)
    return got, want


@pytest.mark.parametrize("leaf", [
    "emb", "head", "final_norm", "dense.0.w13", "dense.0.attn.w_qb",
    "dense.0.attn.w_kva", "moe.attn.w_o", "moe.attn.kv_norm", "moe.w_g",
    "moe.shared_w13", "moe.experts_w13", "moe.experts_w2", "mtp.w_eh",
    "mtp.norm_e", "mtp.block.experts_w2", "mtp.block.w_g"])
def test_the_whole_steps_gradients_equal_the_reference(gradients, leaf):
    got, want = gradients
    close(enc.leaf_of(got, leaf), enc.leaf_of(want, leaf), 1e-4)


def test_the_router_bias_is_outside_the_gradient(gradients):
    got, _ = gradients
    assert not np.any(got["router_bias"]) and not np.any(
        got["mtp_router_bias"])


def test_a_packed_batch_equals_its_histories_run_apart(params):
    tokens, seg, pos = packed()
    h, _ = jax.jit(lambda p: enc.encode(p, CFG, tokens, seg, pos))(params)
    apart = jax.jit(lambda p, t, s, q: enc.encode(p, CFG, t, s, q)[0])
    at = 0
    for ln in LENGTHS[0]:
        alone = np.zeros((1, 64), np.int32)
        alone[0, :ln] = tokens[0, at:at + ln]
        one_seg = jnp.asarray((np.arange(64) < ln).astype(np.int32))[None]
        one_pos = jnp.asarray(np.where(np.arange(64) < ln, np.arange(64),
                                       np.arange(64) - ln).astype(np.int32))[None]
        h1 = apart(params, jnp.asarray(alone), one_seg, one_pos)
        close(h[0, at:at + ln], h1[0, :ln], 1e-4)
        at += ln


# -- the share of a deployment ---------------------------------------------------

# (experts, held a chip, picks a token, scale, hidden, expert width): the
# JoyAI tiny configuration's, and Kimi Linear's pattern at a small size
# (8 held a chip, top-8, scaled 2.446, one shared expert of the experts'
# own width)
SHARES = {"joyai": (8, 2, 3, 2.5, 32, 12),
          "kimi_linear": (32, 8, 8, 2.446, 36, 16)}


@pytest.mark.parametrize("which", sorted(SHARES))
def test_the_shares_add_up_to_the_uncut_layer(which):
    """The chips of a deployment hold `held` experts each. The parts
    their shares give, the shared expert counted once, are the uncut
    reference layer."""
    total_experts, held, picks, scale, d, f = SHARES[which]
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((80, d)), jnp.float32)
    cut = dataclasses.replace(
        CFG, hidden_size=d, moe_intermediate_size=f,
        experts_total=total_experts,
        n_routed_experts=held, num_experts_per_tok=picks,
        routed_scaling_factor=scale)
    uncut = dataclasses.replace(cut, n_routed_experts=total_experts,
                                expert_first=0)
    p = jax.tree_util.tree_map(lambda a: a[0], jax.jit(
        lambda k: enc.init_params(uncut, VOCAB, k))(jax.random.key(7))["moe"])
    bias = jnp.asarray(rng.standard_normal(total_experts) * 0.05, jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, whole_counts, _ = jax.jit(
            lambda p: ref.expert_layer(p, bias, uncut, x))(p)
    shared = enc.swiglu(uncut, x, p["shared_w13"], p["shared_w2"])
    total, seen = shared, []
    for first in range(0, total_experts, held):
        share = dataclasses.replace(cut, expert_first=first)
        mine = dict(p, experts_w13=p["experts_w13"][first:first + held],
                    experts_w2=p["experts_w2"][first:first + held])
        y, routed = jax.jit(
            lambda m, share=share: enc.expert_ffn(m, bias, share, x))(mine)
        total = total + (y - shared)  # what every chip computes alike: once
        seen.append(routed["counts"])
    close(total, whole)
    assert np.array_equal(np.concatenate(seen), whole_counts)
    assert int(whole_counts.sum()) == 80 * uncut.num_experts_per_tok


@pytest.mark.parametrize("block", [1, 4, 64])
def test_the_dispatch_drops_no_token(block):
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((40, 8)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(6)[:3] for _ in range(40)]),
                      jnp.int32)
    w = jnp.asarray(rng.random((40, 3)), jnp.float32)
    w13 = jnp.asarray(rng.standard_normal((3, 8, 10)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((3, 5, 8)), jnp.float32)
    y, counts = jax.jit(lambda x: moe.held_experts(
        x, w, idx, w13, w2, first=1, block=block))(x)
    want = 0.0
    for e in range(3):
        h = x @ w13[e]
        want = want + ((w * (idx == 1 + e)).sum(-1)[:, None]
                       * ((jax.nn.silu(h[:, :5]) * h[:, 5:]) @ w2[e]))
    close(y, want)
    assert np.array_equal(counts, [(np.asarray(idx) == 1 + e).sum()
                                   for e in range(3)])


# -- the second router rule and the second gate (`ops/moe.py`) ----------------------

@pytest.mark.parametrize("what", ["picks", "weights", "load", "gradient",
                                  "refused"])
def test_the_softmax_router_picks_by_the_logits_and_weighs_the_picked(what):
    """`route(..., scoring="softmax")`: the top-k of the logits, a
    softmax over the picked (the same as a softmax over all, picked and
    renormalised); no bias, no scale."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((40, 8)), jnp.float32)
    w_g = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)
    if what == "refused":
        for bias, scale, scoring in ((jnp.zeros(6), 1.0, "softmax"),
                                     (None, 2.5, "softmax"),
                                     (None, 1.0, "tanh")):
            with pytest.raises(ValueError):
                moe.route(x, w_g, bias, 3, scale, scoring)
        return
    idx, weights, load = moe.route(x, w_g, None, 3, 1.0, "softmax")
    logits = np.asarray(x, np.float64) @ np.asarray(w_g, np.float64)
    order = np.argsort(-logits, axis=-1)[:, :3]
    if what == "picks":
        assert np.array_equal(idx, order) and idx.dtype == jnp.int32
    elif what == "weights":
        over_all = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        picked = np.take_along_axis(over_all, order, axis=-1)
        close(weights, picked / picked.sum(-1, keepdims=True))
        close(weights.sum(-1), np.ones(40))
    elif what == "load":
        assert np.array_equal(load, np.bincount(order.reshape(-1),
                                                minlength=6))
    else:  # the weights carry the router's gradient, the picks none
        target = jnp.asarray(rng.standard_normal((40, 3)), jnp.float32)
        got = jax.grad(lambda w: (moe.route(x, w, None, 3, 1.0, "softmax")[1]
                                  * target).sum())(w_g)
        want = jax.grad(lambda w: (jax.nn.softmax(jnp.take_along_axis(
            x @ w, jnp.asarray(order), axis=-1), axis=-1) * target).sum())(
            w_g)
        close(got, want)


def plain_experts(x, w, idx, w13, w2, first, gate):
    """`held_experts` in its plain form: every held expert on every
    token, weighted by the token's weight for it (zero where it did not
    pick the expert), under autodiff. "relu2": no gate, w13 the up
    matrix alone."""
    f = w2.shape[1]
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}.get(gate)
    out = 0.0
    for e in range(w13.shape[0]):
        h = x @ w13[e]
        a = (jax.nn.relu(h) ** 2 if gate == "relu2"
             else act(h[:, :f]) * h[:, f:])
        out = out + (w * (idx == first + e)).sum(-1)[:, None] * (a @ w2[e])
    return out


@functools.lru_cache(maxsize=None)
def both_gradients(gate):
    """(value, every argument's gradient) of `held_experts` under
    `gate`, and of the plain form under autodiff."""
    rng = np.random.default_rng(12)
    args = {"x": jnp.asarray(rng.standard_normal((40, 8)), jnp.float32),
            "weights": jnp.asarray(rng.random((40, 3)), jnp.float32),
            "w13": jnp.asarray(rng.standard_normal(
                (3, 8, 5 if gate == "relu2" else 10)), jnp.float32),
            "w2": jnp.asarray(rng.standard_normal((3, 5, 8)), jnp.float32)}
    idx = jnp.asarray(np.stack([rng.permutation(6)[:3] for _ in range(40)]),
                      jnp.int32)
    target = jnp.asarray(rng.standard_normal((40, 8)), jnp.float32)
    def held(a):
        return (moe.held_experts(a["x"], a["weights"], idx, a["w13"],
                                 a["w2"], first=1, block=4, gate=gate)[0]
                * target).sum()

    def plain(a):
        return (plain_experts(a["x"], a["weights"], idx, a["w13"], a["w2"],
                              1, gate) * target).sum()

    return (jax.jit(jax.value_and_grad(held))(args),
            jax.jit(jax.value_and_grad(plain))(args))


@pytest.mark.parametrize("leaf", ["x", "weights", "w13", "w2"])
@pytest.mark.parametrize("gate", ["silu", "relu", "relu2"])
def test_a_gates_backward_pass_equals_autodiff_of_the_plain_form(gate, leaf):
    """The hand-written backward pass with the expert's form as its
    parameter: SiLU as it was, ReLU with dg = da * u * (g > 0), and the
    ungated W_down relu(W_up x)^2 (two matrices an expert, through the
    same plan and dispatch) with d pre = d a * 2 relu(pre)."""
    (value, grads), (want_value, want_grads) = both_gradients(gate)
    close(value, want_value)
    close(grads[leaf], want_grads[leaf])
    x = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="gelu"):
        moe.held_experts(x, jnp.ones((4, 1)), jnp.zeros((4, 1), jnp.int32),
                         jnp.zeros((1, 8, 4)), jnp.zeros((1, 2, 8)), first=0,
                         block=4, gate="gelu")


# -- an expert's form and the scaled sigmoid router (`ops/moe.py`) -----------------

def test_an_expert_form_and_its_matrices_have_to_agree():
    x, w, idx = (jnp.zeros((4, 8)), jnp.ones((4, 1)),
                 jnp.zeros((4, 1), jnp.int32))
    with pytest.raises(ValueError, match="relu2"):  # a fused gate | up
        moe.held_experts(x, w, idx, jnp.zeros((1, 8, 4)),
                         jnp.zeros((1, 2, 8)), first=0, block=4, gate="relu2")
    with pytest.raises(ValueError, match="silu"):   # an up matrix alone
        moe.held_experts(x, w, idx, jnp.zeros((1, 8, 2)),
                         jnp.zeros((1, 2, 8)), first=0, block=4, gate="silu")


@pytest.mark.parametrize("what", ["picks", "weights", "sum", "gradient"])
def test_the_sigmoid_router_at_scale_2_5(what):
    """Nemotron-H's published rule: the top-k of sigmoid(x w_g) + bias,
    the bias in the pick alone, the picked scores normalised and times
    `routed_scaling_factor` 2.5."""
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.standard_normal((40, 8)), jnp.float32)
    w_g = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(6), jnp.float32)
    idx, weights, _ = moe.route(x, w_g, bias, 3, 2.5, "sigmoid")
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w_g, np.float64))))
    order = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1)[:, :3]
    picked = np.take_along_axis(s, order, axis=-1)
    if what == "picks":
        assert np.array_equal(idx, order)
        assert not np.array_equal(order, np.argsort(-s, axis=-1)[:, :3])
    elif what == "weights":
        close(weights, 2.5 * picked / picked.sum(-1, keepdims=True))
    elif what == "sum":
        close(weights.sum(-1), np.full(40, 2.5))
    else:  # the bias carries no gradient, the weights do
        target = jnp.asarray(rng.standard_normal((40, 3)), jnp.float32)
        d_bias = jax.grad(lambda b: (moe.route(x, w_g, b, 3, 2.5)[1]
                                     * target).sum())(bias)
        assert not np.asarray(d_bias).any()
        got = jax.grad(lambda w: (moe.route(x, w, bias, 3, 2.5)[1]
                                  * target).sum())(w_g)

        def plain(w):
            p = jnp.take_along_axis(jax.nn.sigmoid(x @ w),
                                    jnp.asarray(order), axis=-1)
            return (2.5 * p / p.sum(-1, keepdims=True) * target).sum()

        close(got, jax.grad(plain)(w_g))


@pytest.mark.parametrize("what", ["zero", "weights", "sum", "softmax"])
def test_the_sigmoid_routers_epsilon(what):
    """LFM2's published rule: w = s[idx] / (sum(s[idx]) + 1e-6). An
    epsilon of 0, every other model's, adds no op: the weights are the
    bare quotient's bit for bit and the traced program is what it was."""
    rng = np.random.default_rng(15)
    x = jnp.asarray(rng.standard_normal((40, 8)), jnp.float32)
    # a row of scores near 1e-4, where 1e-6 is no rounding error
    x = x.at[:20].set(jnp.asarray([9.0] + [0.0] * 7))
    w_g = jnp.asarray(0.05 * rng.standard_normal((8, 6)), jnp.float32
                      ).at[0].add(-1.0)
    bias = jnp.asarray(0.3 * rng.standard_normal(6), jnp.float32)
    bare = moe.route(x, w_g, bias, 3, 2.5, "sigmoid")
    if what == "zero":
        same = moe.route(x, w_g, bias, 3, 2.5, "sigmoid", 0.0)
        assert all(np.array_equal(a, b) for a, b in zip(bare, same))
        assert str(jax.make_jaxpr(lambda x: moe.route(
            x, w_g, bias, 3, 2.5))(x)) == str(jax.make_jaxpr(
                lambda x: moe.route(x, w_g, bias, 3, 2.5, "sigmoid", 0.0))(x))
        return
    if what == "softmax":
        with pytest.raises(ValueError, match="no epsilon"):
            moe.route(x, w_g, None, 3, 1.0, "softmax", 1e-6)
        return
    idx, weights, _ = moe.route(x, w_g, bias, 3, 2.5, "sigmoid", 1e-6)
    assert np.array_equal(idx, bare[0])  # the pick does not know it
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w_g, np.float64))))
    picked = np.take_along_axis(s, np.asarray(idx), axis=-1)
    total = picked.sum(-1, keepdims=True)
    if what == "weights":
        close(weights, 2.5 * picked / (total + 1e-6), 1e-6)
    else:  # the small rows lose a share of their sum that is no rounding
        lost = 1.0 - np.asarray(weights).sum(-1) / 2.5
        close(lost[:20], (1e-6 / (total + 1e-6))[:20, 0], 1e-3)
        assert lost[:20].min() > 1e-3 > 1e-5 > np.abs(lost[20:]).max()
        assert np.abs(np.asarray(bare[1]) - np.asarray(weights))[:20].max() \
            > 1e-3 * np.asarray(weights)[:20].max()


@pytest.mark.parametrize("what", ["normed", "before_rotation", "unnormed"])
def test_grouped_query_attention_with_and_without_the_norm_a_head(what):
    """`gqa` norms q and k a head where the block holds `q_norm` and
    `k_norm` (LFM2's), before the rotation; a block without them traces
    the program it traced."""
    from predictionio_tpu.quality import encoder_reference as plain

    cfg = dataclasses.replace(
        CFG, num_key_value_heads=1, head_dim=8, rope_interleave=False,
        rms_norm_eps=1e-5, attention_block=16)
    rng = np.random.default_rng(16)
    d, h, dh = cfg.hidden_size, cfg.num_attention_heads, 8
    x = jnp.asarray(rng.standard_normal((2, 64, d)), jnp.float32)
    p = {k: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
         for k, s in (("w_q", (d, h * dh)), ("w_k", (d, dh)),
                      ("w_v", (d, dh)), ("w_o", (h * dh, d)))}
    norms = {k: jnp.asarray(1.0 + 0.3 * rng.standard_normal(dh), jnp.float32)
             for k in ("q_norm", "k_norm")}
    _, seg, pos = packed()
    got = enc.gqa({**p, **norms}, cfg, x, seg, pos, rotate=True)

    def reference(tree, **wrong):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([plain.gqa(tree, cfg, x[b], seg[b], None,
                                        lambda f: f, pos[b], None, True,
                                        **wrong) for b in range(2)])

    if what == "normed":
        close(got, reference({**p, **norms}), 1e-4)
    elif what == "before_rotation":
        late = reference({**p, **norms}, wrong=("norm_after_rotation",))
        assert float(jnp.abs(got - late).max()) > 1e-2 * float(
            jnp.abs(got).max())
    else:
        bare = enc.gqa(p, cfg, x, seg, pos, rotate=True)
        close(bare, reference(p), 1e-4)
        assert float(jnp.abs(got - bare).max()) > 1e-2 * float(
            jnp.abs(got).max())
        text = jax.jit(lambda p: enc.gqa(p, cfg, x, seg, pos, "enc.gqa",
                                         rotate=True)).lower(p).as_text(
                                             debug_info=True)
        assert "qk_norm" not in text and "enc.gqa.proj" in text
        normed = jax.jit(lambda p: enc.gqa(p, cfg, x, seg, pos, "enc.gqa",
                                           rotate=True)).lower(
            {**p, **norms}).as_text(debug_info=True)
        assert "enc.gqa.qk_norm" in normed


# -- the train step, the scorer, the configuration file -----------------------------

def test_the_step_lowers_the_loss_and_moves_the_bias():
    cfg = dataclasses.replace(CFG, report_blocks=(
        ("w_eh", "mtp.w_eh", ()), ("rows", "emb", ((0, 8),))))
    state = jax.jit(lambda k: enc.init_state(cfg, VOCAB, k))(
        jax.random.key(2))
    step = jax.jit(enc.train_step(cfg, 0.01))
    batch = packed()
    first = None
    for _ in range(4):
        state, metrics = step(state, *batch)
        first = first if first is not None else metrics
    assert float(metrics["loss"]) < float(first["loss"])
    assert first["counts"].shape == (2, 2) and first["mtp_counts"].shape == (2,)
    assert first["picks"].shape == (2, 128, 3)
    assert first["mtp_picks"].shape == (128, 3)
    assert np.any(np.asarray(state["buffers"]["router_bias"]) != 0)
    assert enc.report_of(cfg, state["params"])["rows"].shape == (8, 32)


def test_the_first_steps_report_holds_the_gradient_it_used(params):
    cfg = dataclasses.replace(CFG, report_blocks=(
        ("w_eh", "mtp.w_eh", ()), ("rows", "emb", ((0, 8),))))
    state = jax.jit(lambda k: enc.init_state(cfg, VOCAB, k))(
        jax.random.key(2))
    batch = packed()
    want = jax.jit(jax.grad(lambda p: enc.losses(
        {**p, **state["buffers"]}, cfg, *batch)[0]))(state["params"])
    before = jax.device_get(enc.report_of(cfg, state["params"]))
    after, _ = jax.jit(enc.train_step(cfg, 0.01))(state, *batch)
    report = jax.jit(enc.first_step_report(cfg))(after)
    for name, g in enc.report_of(cfg, want).items():
        close(report["grads"][name], g, 1e-6)
        moved = np.sign(np.asarray(report["params"][name]) - before[name])
        big = np.abs(np.asarray(g)) > 1e-6
        assert np.array_equal(moved[big], -np.sign(np.asarray(g))[big])


def test_the_scorer_equals_the_reference_forward(params):
    tokens, _, _ = packed()
    seq = np.zeros((2, 16), np.int32)
    seq[0, :10], seq[1, :5] = tokens[0, :10], tokens[1, :5]
    got = jax.jit(lambda p, s, n: enc.score(p, CFG, s, n))(
        params, jnp.asarray(seq), jnp.asarray([10, 5]))
    one = jax.jit(lambda p, h: ref.score(p, CFG, h))
    close(got[0], one(params, tokens[0, :10]), 1e-4)
    close(got[1], one(params, tokens[1, :5]), 1e-4)


def test_the_published_configuration_counts_680_million_parameters():
    with open(os.path.join(ROOT, "perf", "configs",
                           "joyai_llm_flash_1of16.json")) as f:
        raw = json.load(f)
    cfg = enc.EncoderConfig.from_dict(raw)
    n = enc.count_parameters(cfg, cfg.vocab_size)
    assert abs(n - 680.4e6) / 680.4e6 < 0.01
    assert (cfg.n_dense, cfg.n_moe, cfg.experts_total) == (1, 4, 256)
    assert [b[0] for b in cfg.report_blocks][:3] == [
        "expert_w13", "expert_w2", "router"]
    # every width as published
    for key, value in {"hidden_size": 2048, "intermediate_size": 7168,
                       "moe_intermediate_size": 768, "q_lora_rank": 1536,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                       "qk_rope_head_dim": 64, "v_head_dim": 128,
                       "num_attention_heads": 32,
                       "num_experts_per_tok": 8}.items():
        assert raw[key] == value == getattr(cfg, key)


def test_the_default_block_is_a_configuration_of_the_same_encoder():
    from predictionio_tpu.templates.sessionrec import engine

    cfg = engine._encoder_config("")
    assert cfg == engine._encoder_config(engine.DEFAULT_ENCODER)
    wide = engine._encoder_config("", 32, 2, 4)
    assert (wide.hidden_size, wide.n_dense, wide.qk_nope_head_dim) == (32, 2, 8)
    assert (cfg.n_dense, cfg.n_moe, cfg.num_nextn_predict_layers) == (1, 0, 0)
    assert cfg.compute_dtype == "float32" and not cfg.remat
    shapes = enc.param_shapes(cfg, 9)
    assert set(shapes) == {"emb", "head", "final_norm", "dense"}
    assert enc.init_buffers(cfg) == {}
