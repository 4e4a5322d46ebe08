"""Plain reference of the next-item encoder (`models/encoder.py`): the
forward pass, both losses and, through `jax.grad`, their gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no packing tricks
(attention is a dense [L, L] mask from the segment ids), no
recomputation, no dispatch (an expert runs on every token and its result
is weighted by the router's weight for that token, zero where the token
did not pick it). It takes the same parameter tree as the system and is
given the same share of the model: the experts `first .. first + held - 1`
of every expert layer and the vocabulary rows the tree holds.

A layer's token mixer is the one whose parameters its block holds:
latent attention (`attn`; with a low-rank query or `w_q` alone, rotated
or, under `mla_use_nope`, not) or Kimi Delta Attention (`kda`), the
gated delta rule run a token at a time:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t

from a zero state at a history's first token, after a causal depthwise
convolution whose taps before that token read zero. Or it is one of a
decoder-hybrid-decoder's five (`mamba`, `diff`, `gmu`, `cross`), with
LayerNorm and a tied head where the configuration says so:

    Mamba     [x | z] = u W_in; x = SiLU(conv(x) + b_c); [dl | B | C] = x W_x
              dt = softplus(dl W_dt + b_dt); A = -exp(A_log)
              s_t = exp(dt_t (x) A) s_{t-1} + (dt_t x_t) (x) B_t
              y_t = s_t C_t + D x_t; out = (y SiLU(z)) W_out; m = y
    GMU       out = (SiLU(u W_g) m) W_o
    DiffAttn  o_j = P(q1_j, k1_g) V_g - lam P(q2_j, k2_g) V_g, g = j // 2
              lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0
              o_j <- RMSNorm(o_j) (1 - lam0), lam0 = 0.8 - 0.6 exp(-0.3 l)
    Cross     the same with Q of its own and the full layer's K, V

the scan a token at a time from a zero state at a history's first
token, the softmaxes P over the keys s <= t of the query's history and,
in a windowed layer, with t - s < sliding_window; `m` is the last Mamba
layer's y and K, V the full-attention layer's, handed from layer to
layer beside the residual stream. Or it is one of the two of a Mamba-2
hybrid (`ssd`, `gqa`), under its four multipliers:

    embed     h0 = embedding_multiplier E[token]
    block     h += residual_multiplier Mixer(RMSNorm(h))
              h += residual_multiplier FFN(RMSNorm(h))
    Mamba-2   [z | xBC | dt'] = u W_in; xBC = SiLU(conv(xBC) + b_c)
              [x | B | C] = xBC, x as heads of P channels, B, C [N] for
              all heads; dt = softplus(dt' + dt_bias), A = -exp(A_log),
              one scalar a head
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, S [P, N] a head
              y_t = S_t C_t + D x_t
              out = (RMSNorm(y SiLU(z)) w) W_out, one norm over all channels
    GQA       q, k, v = u W_q, u W_k, u W_v, query head j reads key and
              value head j // (heads / kv heads), no rotation
              o = softmax over s <= t of the history of (q_t . k_s
              attention_multiplier) v_s; out = concat(o) W_o
    head      logits = RMSNorm(h) E^T / logits_scaling

the recurrence a token at a time, in the recurrent form (no chunks). Or
the model routes before it mixes (SmallThinker's block: every layer
grouped-query attention beside routed experts, no shared expert, no
dense layer, an untied head), the layer l of the published model:

    r        = h W_r                    the block's input, not normed
    idx      = top-k of r; w = softmax(r[idx]) over the picked
    a        = h + GQA_l(RMSNorm(h))
      sliding_window_layout[l] = 1: keys s <= t of the history with t - s
               < sliding_window_size; rope_layout[l] = 1: RoPE on q and k,
               pairs (x[i], x[i + d/2]) turned by pos theta^(-2i/d)
      = 0:     every key s <= t of the history; nothing rotated
    out      = a + sum over the held e in idx of w_e W_down,e (relu(
               W_gate,e x) * (W_up,e x)), x = RMSNorm(a)

Or every layer is one sublayer (Nemotron-H's pattern, a letter a layer:
`M`, `E`, `*`), h += Mixer_l(RMSNorm_l(h)) under one norm and one
residual:

    M        Mamba-2 as above with G = `mamba_n_groups` groups of B and C,
             head h reading group h // (heads / G) in the update and the
             read-out, and out = (RMSNorm_per_group(y SiLU(z)) w) W_out:
             the gate first, then a norm over each group's channels
    *        GQA as above, no rotation, scores / sqrt(d)
    E        s = sigmoid(u W_g); idx = top-k of s + bias; w =
             routed_scaling_factor s[idx] / sum(s[idx]); out = sum over
             the held e in idx of w_e W_down,e relu(W_up,e u)^2 +
             W_down,s relu(W_up,s u)^2, the shared expert of its own width

then the final norm and an untied head. Or the model is one of short
convolutions and attention (LFM2's `layer_types`, `conv` or
`full_attention` a layer), the layer l of the published model:

    block    h += Op_l(RMSNorm(h)); h += FFN_l(RMSNorm(h))
    conv     [B | C | x] = u W_in; v = B * x; c_t = sum_j w_j v_{t-2+j} a
             channel over the taps j = 0..2 (depthwise, causal, the last
             tap on the token itself, a tap on another history reads
             zero, no activation, no bias); out = (C * c) W_out
    full_attention
             q, k, v = u W_q, u W_k, u W_v; q = RMSNorm_d(q) g_q, k =
             RMSNorm_d(k) g_k a head, before the rotation; RoPE on q and
             k, pairs (x[i], x[i + d/2]); scores / sqrt(d), causal
             inside a history; out = concat(o) W_o
    FFN_l    l < num_dense_layers: W_2 (SiLU(W_1 x) * (W_3 x)); else s =
             sigmoid(x W_g), idx = top-k of s + bias, w =
             routed_scaling_factor s[idx] / (sum(s[idx]) + 1e-6), out =
             sum over the held e in idx of w_e W_2,e (SiLU(W_1,e x) *
             (W_3,e x)); no shared expert

then the final norm and the tied head.

Departures from the published models (DeepSeek-V3's equations under
JoyAI-LLM-Flash's config.json; Kimi Linear's under
Kimi-Linear-48B-A3B-Instruct's), each shared with the system:

- The absent experts' part of an expert layer's result is left out, and
  the partial result goes on to the next layer (a chip's share of a
  deployment, without its exchange).
- The vocabulary is the slice held here: logits, softmax and both losses
  are over it.
- Tokens are items of packed user histories: attention is causal inside
  a history's own segment, and a RoPE position counts from the history's
  start.
- RoPE turns adjacent pairs (x[2i], x[2i+1]) (`rope_interleave`); the
  published code permutes them into halves first, which changes no score
  because queries and keys are permuted alike.
- A KDA layer's state and its convolution start anew at every history
  of a packed sequence (the published code resets at a sequence's
  start; a packed history is a sequence). No projection has a bias; the
  L2 norm's epsilon is the configuration's `l2_norm_eps`.
- Mamba's state and convolution start anew at every history likewise;
  differential attention's sub-norm takes the configuration's
  `layer_norm_eps`; the held layers of a slice are numbered from the
  configuration's `layer_first` (lam0 reads the published index).
- Mamba-2's state and its convolution over x, B and C start anew at
  every history likewise, which the published model has no notion of;
  the held layers are a slice of the published `layer_types` from
  `layer_first` on; the multipliers default to 1 and the attention
  scale to 1 / sqrt(d) where a configuration states none.
- Nemotron-H's Mamba-2 state and convolution start anew at every history
  likewise; its attention applies no positional encoding (the published
  attention applies none: `rope_theta` and `partial_rotary_factor` are
  read by nothing); the held layers are a slice of the published
  `hybrid_override_pattern` from `layer_first` on; its router's bias is
  a buffer updated from the load as JoyAI's is.
- LFM2's convolution starts anew at every history likewise (the
  published code knows a sequence's start alone); its router's bias
  (`expert_bias` in the published code) is a buffer updated from the
  load as JoyAI's is; the head is tied (`tie_word_embeddings`, the
  family's default); the held layers are a slice of the published
  `layer_types` from `layer_first` on, the dense ones those before
  `num_dense_layers`.
- SmallThinker's router has no load-balancing term at all (no bias, no
  auxiliary loss: its config names none), so nothing but the loss moves
  it; a window counts tokens of the history, as a position does.
- The MTP module takes h_t before the final norm (the last block's
  residual stream); its own expert block has its own router bias.
- The router's load-balance bias is a buffer: it picks, and is not in
  the weights or the gradient. `n_group = topk_group = 1`, so the pick
  is a plain top-k.

One departure is the system's alone: in `ops/moe.py` an expert's output
rows travel to the combine, and the gradient rows back, in the
operands' dtype (bfloat16 in the benchmark's configuration) and not as
the float32 sum, as an exchange between chips would carry them. Here
everything stays in the dtype of the arrays given: float32, or bfloat16
throughout where the benchmark's control hands it bfloat16 weights.

`wrap` is applied to every block function, to the attention of a query
block, to a run of `kda_block` tokens of the recurrence and to an
expert's turn; the default does nothing. A caller that has to fit the
published widths on one chip passes `jax.checkpoint`, a `q_block`, a
`kda_block` and an `ssm_block` (runs of tokens of Mamba's scan), which
change where intermediates are kept and not what is computed. Three
switches are controls': `kda_resets=False` lets every recurrence (KDA's,
Mamba's) and its convolution run on across history boundaries;
`windowed=False` takes the window off the windowed layers;
`stale_carry=True` hands the gated memory unit Mamba's gated output
y SiLU(z) for y, and has the cross layer make K, V from its own normed
input with the full layer's weights. `wrong` names what a control gets
wrong of a block that routes before it mixes: `sigmoid_scores` (the
picked logits through a sigmoid, normalised, in place of the softmax),
`silu_gate` (SiLU for ReLU), `router_after_attention` (the router reads
the normed stream the experts read) and `rotate_full` (RoPE in the
layers whose `rope_layout` is 0 too); and of a model whose layers are
one sublayer each: `one_group` (every head reads B and C of group 0),
`norm_all_channels` (one gated norm over all channels, not one a group),
`norm_before_gate` (the norm on y, then the gate), `relu_not_squared`
(relu(W_up x) for its square), `gated_expert` (SiLU(W_up x) * (W_up x): a
gate where there is none), `scale_1` (the routed weights not scaled) and
`no_shared` (the shared expert left out); and of a model of short
convolutions and attention: `no_in_gate` (conv(x) for conv(B * x)),
`no_out_gate` (conv(..) for C * conv(..)), `gates_swapped` (B for C and C
for B), `silu_on_taps` (SiLU of the convolution's sum, as every
recurrent mixer's has it), `no_qk_norm` (q and k rotated as projected),
`norm_after_rotation` (the two norms after RoPE: the same lengths,
another weight an entry), `interleaved_pairs` (RoPE over (x[2i],
x[2i+1])), `no_router_eps` and `router_eps_large` (0 and 1e-2 for the
1e-6 in the picked scores' sum) and `bias_in_weights` (the picking bias
added to the picked scores).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _identity(fn):
    return fn


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w + bias


def norm(cfg, x, p, name):
    """LayerNorm with a bias where the configuration has
    `layer_norm_eps`, else RMSNorm."""
    if cfg.layer_norm_eps:
        return layer_norm(x, p[name], p[name + "_bias"], cfg.layer_norm_eps)
    return rms(x, p[name], cfg.rms_norm_eps)


def rope(x, positions, theta, interleave=True):
    """x [L, heads, d] as d/2 pairs (x[2i], x[2i+1]) or, without
    `interleave`, (x[i], x[i + d/2]), pair i turned by position *
    theta^(-2i/d)."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    if not interleave:
        re, im = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([re * cos - im * sin, re * sin + im * cos],
                               axis=-1)
    re, im = x[..., 0::2], x[..., 1::2]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     axis=-1).reshape(x.shape)


def swiglu(x, w13, w2, act=jax.nn.silu):
    """(act(x W_gate) * (x W_up)) W_down, gate and up side by side in w13."""
    f = w2.shape[0]
    h = x @ w13
    return (act(h[:, :f]) * h[:, f:]) @ w2


def attention(q, k, v, seg, scale, q_block, wrap, window=None):
    """q, k [L, H, dk], v [L, H, dv]: softmax over the keys that are not
    later than the query and lie in its segment, and with a `window`
    are fewer than `window` tokens before it. With `q_block`, the
    queries go a block after another (`lax.map`), each against every
    key."""
    l = q.shape[0]

    def rows(block):
        q_rows, seg_rows, at = block
        s = jnp.einsum("qhd,khd->hqk", q_rows, k) * scale
        q_pos = at + jnp.arange(q_rows.shape[0])
        mask = ((jnp.arange(l)[None, :] <= q_pos[:, None])
                & (seg_rows[:, None] == seg[None, :]))
        if window is not None:
            mask = mask & (q_pos[:, None] - jnp.arange(l)[None, :] < window)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    if not q_block or q_block >= l:
        return wrap(rows)((q, seg, 0))
    n = l // q_block
    out = jax.lax.map(wrap(rows), (q.reshape(n, q_block, *q.shape[1:]),
                                   seg.reshape(n, q_block),
                                   jnp.arange(n) * q_block))
    return out.reshape(l, *out.shape[2:])


def mla(p, cfg, x, seg, pos, q_block, wrap):
    l = x.shape[0]
    h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    q = (x @ p["w_q"] if "w_q" in p else
         rms(x @ p["w_qa"], p["q_norm"], cfg.rms_norm_eps) @ p["w_qb"])
    q = q.reshape(l, h, dn + dr)
    kva = x @ p["w_kva"]
    kv = (rms(kva[:, :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
          @ p["w_kvb"]).reshape(l, h, dn + dv)
    turn = ((lambda a: a) if cfg.mla_use_nope
            else (lambda a: rope(a, pos, cfg.rope_theta)))
    k_rope = turn(kva[:, None, cfg.kv_lora_rank:])
    q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (l, h, dr))], axis=-1)
    o = attention(q, k, kv[..., dn:], seg, (dn + dr) ** -0.5, q_block, wrap)
    return o.reshape(l, h * dv) @ p["w_o"]


def short_conv(x, w, seg):
    """Causal depthwise convolution of x [L, C] with taps w [W, C], the
    last tap on the token itself; a tap on another history reads zero."""
    l, width = x.shape[0], w.shape[0]
    out = x * w[width - 1]
    for back in range(1, width):
        same = seg[back:] == seg[:l - back]
        out = out.at[back:].add(
            jnp.where(same[:, None], x[:l - back], 0) * w[width - 1 - back])
    return out


def delta_rule(q, k, v, log_a, beta, first, block, wrap):
    """The recurrence a token at a time: q, k, log_a [L, H, dk], v [L,
    H, dv], beta [L, H], first [L] (the token starts a history). With
    `block`, runs of that many tokens go through `wrap` one after
    another."""
    def token(s, xs):
        q_t, k_t, v_t, la_t, b_t, new = xs
        s = jnp.where(new, jnp.zeros_like(s), s) * jnp.exp(la_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    xs = (q, k, v, log_a, beta, first)
    s = jnp.zeros(k.shape[1:] + v.shape[-1:], v.dtype)
    l = q.shape[0]
    if not block or block >= l or l % block:
        return jax.lax.scan(token, s, xs)[1]
    run = wrap(lambda s, xs: jax.lax.scan(token, s, xs))
    _, o = jax.lax.scan(
        run, s, tuple(a.reshape((l // block, block) + a.shape[1:])
                      for a in xs))
    return o.reshape((l,) + o.shape[2:])


def kda(p, cfg, x, seg, kda_block, wrap):
    l = x.shape[0]
    h, dh = cfg.kda_num_heads, cfg.kda_head_dim
    q, k, v = (jax.nn.silu(short_conv(x @ p[w], p[c], seg)).reshape(l, h, dh)
               for w, c in (("w_q", "conv_q"), ("w_k", "conv_k"),
                            ("w_v", "conv_v")))
    unit = lambda a: a / jnp.sqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + cfg.l2_norm_eps)
    log_a = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (x @ p["w_fa"] @ p["w_fb"] + p["dt_bias"]).reshape(l, h, dh))
    first = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
    o = delta_rule(unit(q) * dh ** -0.5, unit(k), v, log_a,
                   jax.nn.sigmoid(x @ p["w_b"]), first, kda_block, wrap)
    gate = jax.nn.sigmoid(x @ p["w_ga"] @ p["w_gb"]).reshape(l, h, dh)
    return (rms(o, p["o_norm"], cfg.rms_norm_eps) * gate).reshape(
        l, h * dh) @ p["w_o"]


def selective_scan(x, dt, a, b, c, d, first, block, wrap):
    """Mamba's recurrence a token at a time: x, dt [L, D], a [D, N], b,
    c [L, N], d [D], first [L] (the token starts a history). With
    `block`, runs of that many tokens go through `wrap` one after
    another."""
    def token(s, xs):
        x_t, dt_t, b_t, c_t, new = xs
        s = (jnp.where(new, jnp.zeros_like(s), s)
             * jnp.exp(dt_t[:, None] * a)
             + (dt_t * x_t)[:, None] * b_t[None, :])
        return s, s @ c_t + d * x_t

    xs = (x, dt, b, c, first)
    s = jnp.zeros(a.shape, x.dtype)
    l = x.shape[0]
    if not block or block >= l or l % block:
        return jax.lax.scan(token, s, xs)[1]
    run = wrap(lambda s, xs: jax.lax.scan(token, s, xs))
    _, y = jax.lax.scan(
        run, s, tuple(v.reshape((l // block, block) + v.shape[1:])
                      for v in xs))
    return y.reshape(l, -1)


def mamba(p, cfg, x, seg, ssm_block, wrap):
    """Returns (out, y, y SiLU(z)): the mixer's result, the scan's
    output (the memory) and, for a control, the gated output."""
    di, n = p["a_log"].shape
    rank = p["w_dt"].shape[0]
    xz = x @ p["w_in"]
    xi = jax.nn.silu(short_conv(xz[:, :di], p["conv_x"], seg)
                     + p["conv_bias"])
    low = xi @ p["w_x"]
    dt = jax.nn.softplus(low[:, :rank] @ p["w_dt"] + p["dt_bias"])
    first = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
    y = selective_scan(xi, dt, -jnp.exp(p["a_log"]), low[:, rank:rank + n],
                       low[:, rank + n:], p["d_skip"], first, ssm_block,
                       wrap)
    gated = y * jax.nn.silu(xz[:, di:])
    return gated @ p["w_out"], y, gated


def ssd_recurrence(x, dt, a, b, c, d, first, block, wrap):
    """Mamba-2's recurrence a token at a time: x [L, H, P], dt [L, H],
    a [H], b, c [L, N] (one group) or [L, G, N] (head h reads group h //
    (H / G)), d [H], first [L] (the token starts a history). With
    `block`, runs of that many tokens go through `wrap` one after
    another."""
    def token(s, xs):
        x_t, dt_t, b_t, c_t, new = xs
        # a row of B and of C for every head [H, N]: its group's
        b_t, c_t = (jnp.repeat(v.reshape(-1, v.shape[-1]),
                               x_t.shape[0] // (v.size // v.shape[-1]), axis=0)
                    for v in (b_t, c_t))
        s = (jnp.where(new, jnp.zeros_like(s), s)
             * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x_t

    xs = (x, dt, b, c, first)
    s = jnp.zeros(x.shape[1:] + b.shape[-1:], x.dtype)
    l = x.shape[0]
    if not block or block >= l or l % block:
        return jax.lax.scan(token, s, xs)[1]
    run = wrap(lambda s, xs: jax.lax.scan(token, s, xs))
    _, y = jax.lax.scan(
        run, s, tuple(v.reshape((l // block, block) + v.shape[1:])
                      for v in xs))
    return y.reshape((l,) + y.shape[2:])


def ssd(p, cfg, x, seg, ssm_block, wrap, wrong=()):
    """Mamba-2's mixer: one input projection, one convolution over x, B
    and C together, the recurrence, the gate and then one norm over all
    channels or, with `mamba_n_groups` groups of B and C, over each
    group's. `wrong`: a control's (`one_group`, `norm_all_channels`,
    `norm_before_gate`)."""
    l = x.shape[0]
    h, groups = p["a_log"].shape[0], cfg.mamba_n_groups
    n = groups * cfg.mamba_d_state
    di = p["w_out"].shape[0]
    zxbcdt = x @ p["w_in"]
    xbc = short_conv(zxbcdt[:, di:2 * di + 2 * n], p["conv_w"], seg)
    xbc = jax.nn.silu(xbc + p.get("conv_bias", 0.0))
    dt = jax.nn.softplus(zxbcdt[:, 2 * di + 2 * n:] + p["dt_bias"])
    first = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
    b, c = xbc[:, di:di + n], xbc[:, di + n:]
    if groups > 1:
        b, c = (v.reshape(l, groups, -1) for v in (b, c))
        if "one_group" in wrong:
            b, c = b[:, :1], c[:, :1]
    y = ssd_recurrence(xbc[:, :di].reshape(l, h, di // h), dt,
                       -jnp.exp(p["a_log"]), b, c, p["d_skip"], first,
                       ssm_block, wrap).reshape(l, di)
    gate = jax.nn.silu(zxbcdt[:, :di])
    if "norm_all_channels" in wrong:
        groups = 1
    by_group = lambda v: rms(v.reshape(l, groups, -1), 1.0,  # noqa: E731
                             cfg.rms_norm_eps).reshape(l, di) * p["norm"]
    if "norm_before_gate" in wrong:
        return (by_group(y) * gate) @ p["w_out"]
    return by_group(y * gate) @ p["w_out"]


def gated_conv(p, x, seg, wrong=()):
    """The doubly gated short convolution: [B | C | x] = u W_in, (C *
    conv(B * x)) W_out, the convolution three shifted sums under the
    histories' mask. `wrong`: a control's (`no_in_gate`, `no_out_gate`,
    `gates_swapped`, `silu_on_taps`)."""
    d = p["w_out"].shape[0]
    bcx = x @ p["w_in"]
    b, c, xi = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    if "gates_swapped" in wrong:
        b, c = c, b
    y = short_conv(xi if "no_in_gate" in wrong else b * xi, p["taps"], seg)
    if "silu_on_taps" in wrong:
        y = jax.nn.silu(y)
    return (y if "no_out_gate" in wrong else c * y) @ p["w_out"]


def gqa(p, cfg, x, seg, q_block, wrap, pos=None, window=None, rotate=False,
        wrong=()):
    """Plain softmax attention over grouped heads; the scale is the
    configuration's `attention_multiplier` where it states one. Where
    the block holds `q_norm` and `k_norm`, an RMSNorm a head on q and k
    first; with `rotate` RoPE on q and k at the positions `pos`, with a
    `window` the keys fewer than `window` tokens before the query alone.
    `wrong`: a control's (`no_qk_norm`, `norm_after_rotation`,
    `interleaved_pairs`)."""
    l = x.shape[0]
    h, hk = cfg.num_attention_heads, cfg.num_key_value_heads
    dh = p["w_q"].shape[1] // h
    q = (x @ p["w_q"]).reshape(l, h, dh)
    k, v = ((x @ p[w]).reshape(l, hk, dh) for w in ("w_k", "w_v"))
    normed = lambda q, k: (rms(q, p["q_norm"], cfg.rms_norm_eps),  # noqa: E731
                           rms(k, p["k_norm"], cfg.rms_norm_eps))
    late = "norm_after_rotation" in wrong
    if "q_norm" in p and "no_qk_norm" not in wrong and not late:
        q, k = normed(q, k)
    if rotate:
        q, k = (rope(a, pos, cfg.rope_theta, cfg.rope_interleave
                     or "interleaved_pairs" in wrong) for a in (q, k))
    if late:
        q, k = normed(q, k)
    k, v = (jnp.repeat(a, h // hk, axis=1) for a in (k, v))
    o = attention(q, k, v, seg, cfg.attention_multiplier or dh ** -0.5,
                  q_block, wrap, window)
    return o.reshape(l, h * dh) @ p["w_o"]


def keys_and_values(p, cfg, x):
    """K [L, kv heads, d] and V of a differential attention layer."""
    d = x.shape[1]
    dh = d // cfg.num_attention_heads
    kv = (x @ p["w_qkv"] + p["qkv_bias"])[:, d:]
    wide = cfg.num_key_value_heads * dh
    return (kv[:, :wide].reshape(-1, cfg.num_key_value_heads, dh),
            kv[:, wide:].reshape(-1, cfg.num_key_value_heads, dh))


def diff_attention(p, cfg, x, seg, layer, window, kv, q_block, wrap):
    """Differential attention at the published layer index `layer`;
    `kv` None: the layer's own K, V, else the ones given (a cross
    layer). Returns (out, (K, V))."""
    l, d = x.shape
    h, hk = cfg.num_attention_heads, cfg.num_key_value_heads
    dh = d // h
    if kv is None:
        q = (x @ p["w_qkv"] + p["qkv_bias"])[:, :d]
        kv = keys_and_values(p, cfg, x)
    else:
        q = x @ p["w_q"] + p["q_bias"]
    k, v = kv
    q = q.reshape(l, h, dh)
    rep = h // hk                          # pairs of queries a group
    value = jnp.repeat(v.reshape(l, hk // 2, 2 * dh), rep, axis=1)
    one, two = (attention(q[:, s::2], jnp.repeat(k[:, s::2], rep, axis=1),
                          value, seg, dh ** -0.5, q_block, wrap, window)
                for s in (0, 1))
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
           + lam0).astype(x.dtype)
    o = rms(one - lam * two, p["sub_norm"], cfg.layer_norm_eps
            or cfg.rms_norm_eps) * (1.0 - lam0)
    return (o.reshape(l, d) @ p["w_o"] + p["o_bias"]).astype(x.dtype), kv


def carried_block(p, cfg, layer, h, seg, carry, q_block, wrap, ssm_block,
                  resets, windowed, stale_carry):
    """A decoder-hybrid-decoder's block, the mixer the one whose
    parameters it holds. `carry`: `m`, `kv` and, for a control, the
    full layer's parameters and Mamba's gated output. Returns (h,
    carry)."""
    x = norm(cfg, h, p, "norm1")
    if "ssd" in p:
        y = ssd(p["ssd"], cfg, x, seg if resets else jnp.zeros_like(seg),
                ssm_block, wrap)
    elif "gqa" in p:
        y = gqa(p["gqa"], cfg, x, seg, q_block, wrap)
    elif "mamba" in p:
        y, m, gated = mamba(p["mamba"], cfg, x,
                            seg if resets else jnp.zeros_like(seg),
                            ssm_block, wrap)
        carry = {**carry, "m": gated if stale_carry else m}
    elif "gmu" in p:
        y = (jax.nn.silu(x @ p["gmu"]["w_g"]) * carry["m"]) @ p["gmu"]["w_o"]
    elif "cross" in p:
        kv = (keys_and_values(carry["kv_maker"], cfg, x) if stale_carry
              else carry["kv"])
        y, _ = diff_attention(p["cross"], cfg, x, seg, layer, None, kv,
                              q_block, wrap)
    else:
        kind = cfg.kinds[layer - cfg.layer_first]
        y, kv = diff_attention(
            p["diff"], cfg, x, seg, layer,
            cfg.sliding_window if kind == "swa" and windowed else None,
            None, q_block, wrap)
        if kind == "full":
            carry = {**carry, "kv": kv, "kv_maker": p["diff"]}
    scale = cfg.residual_multiplier
    h = h + scale * y
    return (h + scale * swiglu(norm(cfg, h, p, "norm2"), p["w13"], p["w2"]),
            carry)


def expert_layer(p, bias, cfg, x, first=None, held=None, wrap=_identity,
                 router_input=None, wrong=()):
    """Shared expert (where the model has one) + the part of the routed
    result that the experts `first .. first + held - 1` give (the
    tree's own by default; the share test passes other shares and an
    uncut tree), an expert after another. The router reads x, or
    `router_input` where the model routes on another stream; its rule
    and the experts' gate are the configuration's (`wrong`: a
    control's). Returns (y, tokens per held expert, each token's picks
    [L, k])."""
    first = cfg.expert_first if first is None else first
    held = p["experts_w2"].shape[0] if held is None else held
    offset = first - cfg.expert_first  # into the tree's stacked experts
    logits = (x if router_input is None else router_input) @ p["w_g"]
    if cfg.router_scoring == "softmax":
        picked, idx = jax.lax.top_k(logits, cfg.num_experts_per_tok)
        if "sigmoid_scores" in wrong:
            picked = jax.nn.sigmoid(picked)
            w = picked / picked.sum(-1, keepdims=True)
        else:
            w = jax.nn.softmax(picked, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + bias[None, :], cfg.num_experts_per_tok)
        picked = jnp.take_along_axis(
            s + bias[None, :] if "bias_in_weights" in wrong else s, idx,
            axis=-1)
        eps = (0.0 if "no_router_eps" in wrong else
               1e-2 if "router_eps_large" in wrong else cfg.router_norm_eps)
        w = ((1.0 if "scale_1" in wrong else cfg.routed_scaling_factor)
             * picked / (picked.sum(-1, keepdims=True) + eps))
    act = (jax.nn.relu if cfg.moe_gate == "relu" and "silu_gate" not in wrong
           else jax.nn.silu)
    if "experts_w1" in p:  # two matrices an expert, no gate
        w_in, shared_in = "experts_w1", "shared_w1"
        ffn = lambda x, w1, w2: ungated(x, w1, w2, wrong)  # noqa: E731
    else:
        w_in, shared_in = "experts_w13", "shared_w13"
        ffn = lambda x, w13, w2: swiglu(x, w13, w2, act)  # noqa: E731

    def one(y, expert):
        e, w13, w2 = expert
        hit = idx == e
        return (y + (w * hit).sum(-1)[:, None] * ffn(x, w13, w2), hit.sum())

    y, counts = jax.lax.scan(
        wrap(one), (ffn(x, p[shared_in], p["shared_w2"])
                    if shared_in in p and "no_shared" not in wrong
                    else jnp.zeros_like(x)),
        (first + jnp.arange(held), p[w_in][offset:offset + held],
         p["experts_w2"][offset:offset + held]))
    return y, counts, idx


def ungated(x, w1, w2, wrong=()):
    """W_down relu(W_up x)^2, two matrices; `wrong`: a control's
    (`relu_not_squared`, `gated_expert`)."""
    u = x @ w1
    if "gated_expert" in wrong:
        return (jax.nn.silu(u) * u) @ w2
    r = jax.nn.relu(u)
    return (r if "relu_not_squared" in wrong else r * r) @ w2


def sublayer(p, bias, cfg, kind, h, seg, q_block, wrap, ssm_block, resets,
             wrong=()):
    """One layer of a model whose layers are one sublayer each: h +=
    Mixer(RMSNorm(h)), the mixer by `kind` ("ssd", "gqa", "experts").
    Returns (h, (counts, picks) or None)."""
    x = norm(cfg, h, p, "norm")
    if kind == "ssd":
        return h + ssd(p["ssd"], cfg, x,
                       seg if resets else jnp.zeros_like(seg), ssm_block,
                       wrap, wrong), None
    if kind == "gqa":
        return h + gqa(p["gqa"], cfg, x, seg, q_block, wrap), None
    y, counts, picks = expert_layer(p, bias, cfg, x, wrap=wrap, wrong=wrong)
    return h + y, (counts, picks)


def routed_block(p, cfg, n, h, seg, pos, q_block, wrap, windowed=True,
                 wrong=()):
    """The held layer `n` of a model that routes before it mixes: the
    router on the block's input, grouped-query attention windowed and
    rotated as the configuration's layouts say of the layer, the held
    experts on the normed stream after it. Returns (h, (counts,
    picks))."""
    a = h + gqa(p["gqa"], cfg, norm(cfg, h, p, "norm1"), seg, q_block, wrap,
                pos, cfg.sliding_window
                if cfg.layer_windowed[n] and windowed else None,
                cfg.layer_rotated[n] or "rotate_full" in wrong)
    x = norm(cfg, a, p, "norm2")
    y, counts, picks = expert_layer(
        p, None, cfg, x, wrap=wrap, wrong=wrong,
        router_input=x if "router_after_attention" in wrong else h)
    return a + y, (counts, picks)


def block(p, bias, cfg, h, seg, pos, q_block, wrap, kda_block=None,
          kda_resets=True, n=0, wrong=()):
    """The held layer `n`: the mixer whose parameters the block holds,
    then a dense feed-forward (`bias` None) or the expert layer."""
    x = norm(cfg, h, p, "norm1")
    if "kda" in p:
        h = h + kda(p["kda"], cfg, x,
                    seg if kda_resets else jnp.zeros_like(seg), kda_block,
                    wrap)
    elif "sconv" in p:
        h = h + gated_conv(p["sconv"], x,
                           seg if kda_resets else jnp.zeros_like(seg), wrong)
    elif "gqa" in p:
        h = h + gqa(p["gqa"], cfg, x, seg, q_block, wrap, pos, None,
                    bool(cfg.layer_rotated) and cfg.layer_rotated[n], wrong)
    else:
        h = h + mla(p["attn"], cfg, x, seg, pos, q_block, wrap)
    x = norm(cfg, h, p, "norm2")
    if bias is None:
        return h + swiglu(x, p["w13"], p["w2"]), None
    y, counts, picks = expert_layer(p, bias, cfg, x, wrap=wrap, wrong=wrong)
    return h + y, (counts, picks)


CARRIED = ("mamba", "diff", "gmu", "cross", "ssd", "gqa")


def forward(params, cfg, tokens, seg, pos, q_block=None, wrap=_identity,
            kda_block=None, kda_resets=True, ssm_block=None, windowed=True,
            stale_carry=False, wrong=()):
    """One sequence: tokens, seg, pos [L]. Returns (h [L, D] before the
    final norm, h_mtp or None, (counts, picks) per expert layer, the MTP
    block's or None). Expert blocks are a list, or stacked on a leading
    axis."""
    h = cfg.embedding_multiplier * params["emb"][tokens]
    if cfg.single_sublayer:
        routed, biases = [], iter(params.get("router_bias", ()))
        for p, kind in zip(params["layers"], cfg.kinds):
            bias = next(biases) if kind == "experts" else None
            h, r = wrap(lambda p, bias, h, kind=kind: sublayer(
                p, bias, cfg, kind, h, seg, q_block, wrap, ssm_block,
                kda_resets, wrong))(p, bias, h)
            if r is not None:
                routed.append(r)
        return h, None, routed, None
    carry = {}
    for n, p in enumerate(params["dense"]):
        if any(key in p for key in CARRIED):
            h, carry = wrap(lambda p, h, carry, n=n: carried_block(
                p, cfg, cfg.layer_first + n, h, seg, carry, q_block, wrap,
                ssm_block, kda_resets, windowed, stale_carry))(p, h, carry)
            continue
        h, _ = wrap(lambda p, h, n=n: block(
            p, None, cfg, h, seg, pos, q_block, wrap, kda_block, kda_resets,
            n, wrong))(p, h)
    routed = []
    for n in range(cfg.n_moe):
        p = (params["moe"][n] if isinstance(params["moe"], (list, tuple))
             else jax.tree_util.tree_map(lambda a: a[n], params["moe"]))
        if cfg.router_on_block_input:
            h, r = wrap(lambda p, h, n=n: routed_block(
                p, cfg, n, h, seg, pos, q_block, wrap, windowed, wrong))(p, h)
            routed.append(r)
            continue
        h, r = wrap(lambda p, b, h, n=n: block(
            p, b, cfg, h, seg, pos, q_block, wrap, kda_block, kda_resets,
            cfg.n_dense + n, wrong))(p, params["router_bias"][n], h)
        routed.append(r)
    if not cfg.num_nextn_predict_layers:
        return h, None, routed, None
    m = params["mtp"]
    nxt = params["emb"][jnp.roll(tokens, -1)]
    both = jnp.concatenate([rms(h, m["norm_h"], cfg.rms_norm_eps),
                            rms(nxt, m["norm_e"], cfg.rms_norm_eps)], -1)
    h2, r2 = wrap(lambda p, b, x: block(p, b, cfg, x, seg, pos, q_block,
                                        wrap))(
        m["block"], params["mtp_router_bias"], both @ m["w_eh"])
    return h, h2, routed, r2


def logits_of(params, cfg, h):
    x = norm(cfg, h, params, "final_norm")
    return (x @ (params["head"] if "head" in params else params["emb"].T)
            / cfg.logits_scaling)


def _nll_rows(params, cfg, hidden, tokens, seg, k, wrap):
    """-log softmax(logits)[token k ahead] of every row [L], zero where
    that token is not in the row's history, and the rows that count."""
    l = tokens.shape[0]
    ahead = jnp.roll(seg, -k)
    ok = (seg != 0) & (ahead == seg) & (jnp.arange(l) < l - k)
    logits = wrap(lambda p, x: logits_of(p, cfg, x))(params, hidden)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(
        logp, jnp.roll(tokens, -k)[:, None], axis=-1)[:, 0]
    return jnp.where(ok, nll, 0.0), ok


def nll_rows(params, cfg, tokens, seg, pos, q_block=None, wrap=_identity,
             **switches):
    """One sequence's CE terms a row ([L], zero where a row does not
    count), the rows that count, and (counts, picks) per expert layer:
    for a sum over some of the rows (those a window cuts keys from)."""
    h, _, routed, _ = forward(params, cfg, tokens, seg, pos, q_block, wrap,
                              **switches)
    return (*_nll_rows(params, cfg, h, tokens, seg, 1, wrap), routed)


def nll_sums(params, cfg, tokens, seg, pos, q_block=None, wrap=_identity,
             kda_block=None, kda_resets=True, **switches):
    """One sequence's sums: (sum of CE terms, their count, sum of MTP
    terms, their count, (counts, picks) per expert layer, the MTP
    block's)."""
    h, h2, routed, r2 = forward(params, cfg, tokens, seg, pos, q_block, wrap,
                                kda_block, kda_resets, **switches)

    def part(hidden, k):
        nll, ok = _nll_rows(params, cfg, hidden, tokens, seg, k, wrap)
        return jnp.sum(nll), jnp.sum(ok)

    s1, n1 = part(h, 1)
    if h2 is None:
        return s1, n1, 0.0, 0, routed, None
    s2, n2 = part(h2, 2)
    return s1, n1, s2, n2, routed, r2


def losses(params, cfg, tokens, seg, pos, q_block=None, wrap=_identity):
    """A batch [B, L], a sequence at a time: (loss, ce, ce_mtp, counts
    [n_moe, held] or None, MTP counts or None)."""
    with jax.default_matmul_precision("highest"):
        parts = [nll_sums(params, cfg, tokens[b], seg[b], pos[b], q_block,
                          wrap) for b in range(tokens.shape[0])]
    s1, n1, s2, n2 = (sum(p[i] for p in parts) for i in range(4))
    ce = s1 / jnp.maximum(n1, 1)
    counts = (sum(jnp.stack([c for c, _ in p[4]]) for p in parts)
              if cfg.n_moe else None)
    if not cfg.num_nextn_predict_layers:
        return ce, ce, None, counts, None
    ce_mtp = s2 / jnp.maximum(n2, 1)
    return (ce + cfg.mtp_loss_weight * ce_mtp, ce, ce_mtp, counts,
            sum(p[5][0] for p in parts))


def score(params, cfg, history):
    """Next-item logits of one history (a 1-D array of item rows)."""
    n = len(history)
    with jax.default_matmul_precision("highest"):
        h, _, _, _ = forward(params, cfg, jnp.asarray(history, jnp.int32),
                             jnp.ones(n, jnp.int32), jnp.arange(n))
        return logits_of(params, cfg, h[-1:])[0]
