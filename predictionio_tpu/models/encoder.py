"""The next-item encoder, driven by a configuration: in every block a
token mixer, latent attention (MLA), Kimi Delta Attention (KDA, a
gated delta-rule recurrence, `ops/kda.py`), one of the five of a
decoder-hybrid-decoder (Mamba's selective scan, `ops/ssm.py`; windowed
and full differential attention over grouped key and value heads; a
gated memory unit and a differential cross-attention, which read what
an earlier layer left) or one of the two of a Mamba-2 hybrid (the
scalar-decay state-space layer in its chunked matrix form, `ops/ssd.py`;
plain softmax attention over grouped heads without positions) as the
configuration says layer by layer, and a dense or a sparse-expert
feed-forward, the router on the normed stream after the mixer or on the
block's own input; or, in a model whose layers are one sublayer each
(Nemotron-H's pattern), a Mamba-2 layer with several B/C groups, an
expert feed-forward of ungated squared-ReLU experts or attention, alone
under its one norm and residual; or, in a model of short convolutions
and attention (LFM2's `layer_types`), a doubly gated short convolution
that is the whole mixer, or rotated grouped-query attention under a
norm a head on q and k, beside a dense or a sigmoid-routed expert
feed-forward without a shared expert; an optional
multi-token-prediction (MTP) module; trained on packed histories.

One code path runs every size. A configuration file in the published
model's own key names (`EncoderConfig.from_json`) gives the widths, the
share of the model held here and the training sizes; the sessionrec
template's 16-wide default (`templates/sessionrec/encoder-16.json`) is
one such file.

Equations (the plain reference is `quality/encoder_reference.py`):

    block   h += Mixer(RMSNorm(h));  h += FFN(RMSNorm(h))
    MLA     c_q = RMSNorm(x W_qa); [q_nope | q_rope] = c_q W_qb per head
            (without `q_lora_rank`: [q_nope | q_rope] = x W_q)
            [c_kv | k_rope] = x W_kva; c_kv = RMSNorm(c_kv)
            [k_nope | v] = c_kv W_kvb per head; interleaved RoPE on q_rope
            and on the one k_rope all heads share; scores
            (q_nope.k_nope + q_rope.k_rope) / sqrt(d_nope + d_rope), causal
            and inside a history's own segment; out = concat(heads) W_o;
            with `mla_use_nope` nothing is rotated (no positional
            encoding: the recurrent layers carry the order)
    KDA     q~, k~, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(
            x W_v)), conv causal and depthwise, its taps before a
            history's first token zero; per head q = q~/|q~| d_k^-1/2,
            k = k~/|k~|; log a = -exp(A_log) softplus(x W_fa W_fb +
            dt_bias) a channel; b = sigmoid(x W_b) a head; S_t = (I - b_t
            k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T from zero at a
            history's first token; o_t = S_t^T q_t; y = (RMSNorm per
            head(o) * sigmoid(x W_ga W_gb)) W_o
    Mamba   [x | z] = u W_in; x = SiLU(conv(x) + b_c), conv as KDA's;
            [dl | B | C] = x W_x; dt = softplus(dl W_dt + b_dt);
            s_t = exp(dt_t (x) A) s_{t-1} + (dt_t x_t) (x) B_t, A =
            -exp(A_log), s zero entering a history's first token;
            y_t = s_t C_t + D x_t; out = (y SiLU(z)) W_out. The last
            such layer's y is the memory m the later layers read.
    GMU     out = (SiLU(u W_g) m) W_o
    DiffAttn [Q | K | V] = u W_qkv + b. Query heads (2j, 2j+1) are pair
            j's q1, q2, key heads (2g, 2g+1) group g's k1, k2, value
            heads (2g, 2g+1) side by side its V_g; pair j reads group
            j // (heads / kv heads). o_j = P(q1, k1) V_g - lam P(q2, k2)
            V_g, P a softmax over the keys s <= t of the history (kind
            `swa`: with t - s < sliding_window too), scores / sqrt(d);
            lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0, lam0 = 0.8 - 0.6
            exp(-0.3 l) at the published layer index l; o_j <- RMSNorm(
            o_j) (1 - lam0); out = [o_0 ..] W_o + b_o. Kind `full`
            keeps its K, V; kind `cross` has Q = u W_q + b alone and
            reads them. No positional encoding.
    Mamba-2 [z | xBC | dt'] = u W_in, one product; xBC = SiLU(conv(xBC)
            + b_c), conv as KDA's over x, B and C together; [x | B | C]
            = xBC, x as `mamba_n_heads` heads of `mamba_d_head`
            channels, B and C [N] one group for all heads; dt =
            softplus(dt' + dt_bias), A = -exp(A_log), one scalar a head;
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, S [P, N] a
            head, zero entering a history's first token; y_t = S_t C_t +
            D x_t; out = (RMSNorm(y SiLU(z)) w) W_out: gate first, then
            one norm over all channels. With `mamba_n_groups` G > 1: B
            and C are G groups of N, head h reads group g(h) = h // (heads
            / G) in the update and the read-out, and the norm runs over
            each group's channels apart: out = (RMSNorm_per_group(y
            SiLU(z)) w) W_out
    GQA     q, k, v = u W_q, u W_k, u W_v; query head j reads key and
            value head j // (heads / kv heads); o = softmax over the
            keys s <= t of the history of (q_t . k_s attention_multiplier)
            v_s, the multiplier the scale itself (1 / sqrt(d) where none
            is stated); out = concat(o) W_o. No positional encoding;
            layer by layer, where the configuration's layouts say so
            (`rope_layout`, `sliding_window_layout`), RoPE on q and k
            (`rope_interleave` false: pairs (x[i], x[i + d/2])) and a
            window, t - s < sliding_window
    RxB     the block of a model that routes before it mixes (`w_g` on
            the block's input h, not normed): r = h W_r in float32; idx
            = top-k of r; w = softmax(r[idx]) over the picked; a = h +
            GQA(RMSNorm(h)); out = a + sum over the held e in idx of w_e
            W_down,e (relu(W_gate,e x) * (W_up,e x)), x = RMSNorm(a). No
            bias on the router, no shared expert, no dense layer before
    1xL     the layer of a model whose layers are one sublayer each
            (`hybrid_override_pattern`, a letter a layer): h += Mixer_l(
            RMSNorm_l(h)), one norm and one residual, Mixer_l by the
            letter: M Mamba-2 as above (groups and all), * GQA as above
            (no positional encoding, scores / sqrt(d)), E the expert
            feed-forward: s = sigmoid(u W_g) in float32; idx = top-k of
            s + bias (no group limit); w = routed_scaling_factor s[idx]
            / sum(s[idx]); out = sum over the held e in idx of w_e
            W_down,e relu(W_up,e u)^2 + W_down,s relu(W_up,s u)^2, the
            shared expert of its own width
            (`moe_shared_expert_intermediate_size`) and of the same
            ungated form. After the last layer the final norm and an
            untied head
    SConv   the doubly gated short convolution (`layer_types` entry
            `conv`): [B | C | x] = u W_in, no bias; v = B * x; c_t =
            sum_{j < taps} w_j v_{t - taps + 1 + j} a channel (depthwise,
            causal, the last tap on the token itself, a tap that would
            read another history reads zero, no activation); out = (C *
            c) W_out. No recurrence and no state: the convolution is
            the mixer, a multiplicative gate before and after it
    CxA     the block of such a model (LFM2's): h += Op_l(RMSNorm(h)); h
            += FFN_l(RMSNorm(h)), Op_l SConv or, at a `full_attention`
            entry, GQA with q = RMSNorm_d(q) g_q and k = RMSNorm_d(k) g_k
            a head before the rotation (`qk_norm`), then RoPE in
            half-rotation pairs, scores / sqrt(d). FFN_l, l <
            `num_dense_layers`: SwiGLU; else s = sigmoid(x W_g) in
            float32, idx = top-k of s + bias, w =
            routed_scaling_factor s[idx] / (sum(s[idx]) + 1e-6), out =
            sum over the held e in idx of w_e W_2,e (SiLU(W_1,e x) *
            (W_3,e x)); no shared expert, so a token none of whose picks
            is held here adds nothing. After the last layer one RMSNorm
            and the tied head
    x4      the four multipliers of such a hybrid, each 1 where a
            configuration states none: h0 = embedding_multiplier
            E[token]; h += residual_multiplier Mixer(..) and h +=
            residual_multiplier FFN(..) in its blocks; the attention
            scale above; logits = head(..) / logits_scaling
    LN      with `layer_norm_eps` every norm is LayerNorm with a bias;
            with `tie_word_embeddings` the head is the embedding
            transposed
    FFN     SwiGLU in the first `first_k_dense_replace` blocks; after them
            shared SwiGLU (where the model has a shared expert) + the
            held experts' part of the routed result (`ops/moe.py`), the
            experts' gate SiLU or, under `moe_gate`, ReLU
    MTP     h' = W_eh [RMSNorm(h_t) ; RMSNorm(E(x_{t+1}))] -> one expert
            block -> the shared final norm and head -> predicts x_{t+2}
    loss    CE + mtp_loss_weight * CE_mtp, both inside segments

Precision: parameters, gradients and Adam moments float32; matrix products
take `compute_dtype` operands, accumulate in float32 and hand the float32
sum on; router scores, softmax, norms, RoPE and the loss are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import kda as kda_ops
from predictionio_tpu.ops import moe
from predictionio_tpu.ops import ssd as ssd_ops
from predictionio_tpu.ops import ssm
from predictionio_tpu.ops.attention import segment_attention

# the same sizes under other published names (`kimi_linear`'s)
_ALIASES = {"num_experts": "n_routed_experts",
            "num_experts_per_token": "num_experts_per_tok",
            "num_shared_experts": "n_shared_experts"}
# a published `layer_types` entry -> the mixer kind here (Mamba-2's:
# `mamba` there names the scalar-decay layer)
_LAYER_TYPES = {"mamba": "ssd", "attention": "gqa"}
# the same of a model of short convolutions and attention (LFM2's)
_CONV_LAYER_TYPES = {"conv": "sconv", "full_attention": "gqa"}
# a letter of a published `hybrid_override_pattern` -> the one sublayer of
# the layer (`-`, a dense feed-forward alone, is in no model run here)
_PATTERN = {"M": "ssd", "E": "experts", "*": "gqa"}


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int = 0            # the four of MLA; a configuration
    qk_nope_head_dim: int = 0        # without a latent leaves them out
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int = 0             # 0 (null): x W_q, no low-rank query
    mla_use_nope: bool = False       # MLA without rotation
    # "mla" | "kda" | "mamba" | "swa" | "full" | "gmu" | "cross" | "ssd" |
    # "gqa" | "sconv" a layer; (): all MLA. With `single_sublayer` the layer
    # is that mixer alone ("ssd" | "gqa") or an expert feed-forward alone
    # ("experts")
    layer_kinds: tuple = ()
    single_sublayer: bool = False
    num_key_value_heads: int = 0     # grouped K/V heads of swa/full/cross/gqa
    sliding_window: int = 0          # of the swa layers
    head_dim: int = 0                # gqa's head width; 0: hidden / heads
    # of a model whose grouped-query layers differ, a flag a held layer:
    # a window of `sliding_window`; RoPE on q and k. (): neither anywhere
    layer_windowed: tuple = ()
    layer_rotated: tuple = ()
    rope_interleave: bool = True     # pairs (2i, 2i+1); False: (i, i + d/2)
    qk_norm: bool = False            # gqa: RMSNorm a head on q, k, unrotated
    sconv_kernel: int = 3            # taps of the gated short convolution
    layer_first: int = 0             # published index of the first held layer
    mamba_expand: int = 2            # channels / hidden_size
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0           # 0: hidden_size / 16, rounded up
    ssm_chunk: int = 64
    ssm_channels: int = 0            # channels a pass of the scan; 0: all
    mamba_n_heads: int = 0           # Mamba-2 (ssd): heads of mamba_d_head
    mamba_d_head: int = 0            # channels, one scalar decay a head
    mamba_conv_bias: bool = True
    mamba_n_groups: int = 1          # B/C groups; the gated norm's too
    mamba_chunk_size: int = 256      # tokens a chunk of the ssd scan
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0  # of the carried kinds' blocks
    attention_multiplier: float = 0.0  # gqa's scale; 0: 1 / sqrt(head width)
    logits_scaling: float = 1.0      # the head's logits are divided by it
    layer_norm_eps: float = 0.0      # > 0: LayerNorm with a bias, not RMSNorm
    tie_word_embeddings: bool = False
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_size: int = 4
    kda_chunk: int = 64
    kda_head_block: int = 0          # heads a pass of the mixer; 0: all
    l2_norm_eps: float = 1e-6
    first_k_dense_replace: int = 1 << 30  # every block dense
    moe_intermediate_size: int = 0
    n_routed_experts: int = 0        # held here
    experts_total: int = 0           # the router's width
    expert_first: int = 0            # id of the first held expert
    n_shared_experts: int = 1
    # the shared expert's width; 0: moe_intermediate_size x n_shared_experts
    moe_shared_expert_intermediate_size: int = 0
    num_experts_per_tok: int = 0
    router_scoring: str = "sigmoid"  # | "softmax" (`ops/moe.py::route`)
    router_norm_eps: float = 0.0     # added to the picked sigmoid scores' sum
    router_on_block_input: bool = False  # w_g reads h before the mixer
    # the experts' gate, "silu" | "relu"; "relu2": ungated, relu(W_up x)^2
    moe_gate: str = "silu"
    routed_scaling_factor: float = 1.0
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    bias_update_rate: float = 0.001
    vocab_size: int = 0              # 0: as many items as the data has
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    init_std: float = 0.02
    compute_dtype: str = "float32"   # operands of the matrix products
    attention_block: int = 512
    moe_block_rows: int = 256
    loss_chunk: int = 4096
    remat: bool = True
    pack_len: int = 0                # 0: the sequence tier of maxSeqLen
    seqs_per_step: int = 0           # 0: every sequence in one step
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    report_blocks: tuple = ()        # ((name, "leaf.path", (index ...)), ...)

    @property
    def n_dense(self) -> int:
        return min(self.first_k_dense_replace, self.num_hidden_layers)

    @property
    def n_moe(self) -> int:
        """Layers with routed experts (and so a row of the router's bias)."""
        if self.single_sublayer:
            return self.kinds.count("experts")
        return self.num_hidden_layers - self.n_dense

    @property
    def expert_layers(self) -> tuple:
        """The held layers that route, by their index among the held."""
        if self.single_sublayer:
            return tuple(n for n, kind in enumerate(self.kinds)
                         if kind == "experts")
        return tuple(range(self.n_dense, self.num_hidden_layers))

    @property
    def kinds(self) -> tuple:
        """The mixer of every layer, the dense layers first."""
        return self.layer_kinds or ("mla",) * self.num_hidden_layers

    @property
    def mamba_channels(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def norm_eps(self) -> float:
        return self.layer_norm_eps or self.rms_norm_eps

    @property
    def router_biased(self) -> bool:
        """A sigmoid router picks with a load-balance bias (a buffer)."""
        return self.router_scoring == "sigmoid"

    @property
    def moe_stacked(self) -> bool:
        """Expert blocks of one kind are stacked on a leading axis and
        run by one scan; of two kinds, or with a window or rotation
        that differs layer by layer, they are a list."""
        return (len(set(self.kinds[self.n_dense:])) <= 1
                and not (self.layer_windowed or self.layer_rotated))

    @classmethod
    def from_dict(cls, d: dict) -> "EncoderConfig":
        """From a file in the published config's key names. The keys
        `share`, `precision` and `train` are groups of this repo's own."""
        flat = dict(d)
        for group in ("share", "precision", "train"):
            flat.update(d.get(group, {}))
        for theirs, ours in _ALIASES.items():
            if theirs in flat:
                flat[ours] = flat[theirs]
        linear = flat.get("linear_attn_config")
        if linear:  # layers are numbered from 1 there
            layers = range(1, int(flat["num_hidden_layers"]) + 1)
            kda, full = (set(linear[k]) for k in ("kda_layers",
                                                  "full_attn_layers"))
            if any((n in kda) == (n in full) for n in layers):
                raise ValueError("linear_attn_config: every layer is in "
                                 "one of kda_layers and full_attn_layers")
            flat.update(
                layer_kinds=tuple("kda" if n in kda else "mla"
                                  for n in layers),
                kda_num_heads=linear["num_heads"],
                kda_head_dim=linear["head_dim"],
                kda_conv_size=linear["short_conv_kernel_size"])
        if flat.get("mb_per_layer"):
            flat["layer_kinds"] = hybrid_decoder_kinds(
                int(flat.get("layer_first", 0)),
                int(flat["num_hidden_layers"]),
                int(flat.get("layers_total", flat["num_hidden_layers"])),
                int(flat["mb_per_layer"]))
        if "conv_L_cache" in flat:
            _convolutions_and_attention(flat)
        elif flat.get("layer_types"):
            flat["layer_kinds"] = _held_layer_types(flat)
        if "moe_num_primary_experts" in flat:
            _routed_before_mixing(flat)
        if "hybrid_override_pattern" in flat:
            _one_sublayer_a_layer(flat)
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in flat.items() if k in known and v is not None}
        kw["report_blocks"] = tuple(
            (b["name"], b["leaf"],
             tuple(tuple(i) if isinstance(i, list) else int(i)
                   for i in b.get("index", ())))
            for b in flat.get("report_blocks", ()))
        return cls(**kw)

    @classmethod
    def from_json(cls, path: str) -> "EncoderConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _held_slice(flat: dict, key: str) -> tuple:
    """(first, entries) of the published per-layer list `key`: the
    `num_hidden_layers` entries of the held layers, from `layer_first`
    on where the list is the whole model's, else the list itself."""
    listed, held = flat[key], int(flat["num_hidden_layers"])
    first = int(flat.get("layer_first", 0)) if len(listed) > held else 0
    return first, listed[first:first + held]


def _held_layer_types(flat: dict) -> tuple:
    """The kinds of the held layers from a published `layer_types` list
    (the whole model's, or the held slice alone): `num_hidden_layers`
    entries from `layer_first` on. Such a model's feed-forward is its
    shared one where it has no experts. What the two kinds here cannot
    express is refused: an entry of another type, several B/C groups, a
    bias on the state-space layer's projections, rotated attention."""
    held = int(flat["num_hidden_layers"])
    first, mine = _held_slice(flat, "layer_types")
    unknown = sorted(set(mine) - set(_LAYER_TYPES))
    if unknown or len(mine) != held:
        raise ValueError(
            f"layer_types[{first}:{first + held}] = {mine}: {held} entries "
            f"of {sorted(_LAYER_TYPES)} wanted, {unknown} not known")
    for key, only in (("mamba_n_groups", 1), ("mamba_proj_bias", False),
                      ("attention_bias", False),
                      ("position_embedding_type", "nope"),
                      ("num_local_experts", 0)):
        if flat.get(key, only) != only:
            raise ValueError(f"{key} = {flat[key]!r}: a layer_types model "
                             f"runs with {only!r} alone")
    if flat.get("shared_intermediate_size"):
        flat["intermediate_size"] = flat["shared_intermediate_size"]
    return tuple(_LAYER_TYPES[t] for t in mine)


def _convolutions_and_attention(flat: dict) -> None:
    """The sizes here from LFM2's key names (`lfm2_moe`): the held
    layers' entries of `layer_types` (whole and sliced from
    `layer_first`, or the held slice alone), `conv` the doubly gated
    short convolution of `conv_L_cache` taps and `full_attention`
    grouped-query attention under a norm a head on q and k, rotated in
    half-rotation pairs by `rope_parameters.rope_theta`; the published
    layers before `num_dense_layers` a dense SwiGLU of
    `intermediate_size`, the rest `num_experts` (held here) sigmoid-routed
    SiLU-gated experts of `moe_intermediate_size` picked by a bias
    buffer, their weights normalised over the picked scores' sum plus
    1e-6 and scaled, and no shared expert; RMSNorm with `norm_eps`; a
    tied head where the file does not say otherwise. What the code
    cannot run is refused by name: an entry it does not know, a bias on
    the convolution or its projections, a router without the picking
    bias or without the normalisation, a rotation of another type, more
    picks than experts, attention in a dense layer."""
    held = int(flat["num_hidden_layers"])
    at, mine = _held_slice(flat, "layer_types")
    unknown = sorted(set(mine) - set(_CONV_LAYER_TYPES))
    if unknown or len(mine) != held:
        raise ValueError(
            f"layer_types[{at}:{at + held}] = {mine}: {held} entries of "
            f"{sorted(_CONV_LAYER_TYPES)} wanted, {unknown} not known")
    for key, only in (("conv_bias", False), ("use_expert_bias", True),
                      ("norm_topk_prob", True)):
        if flat.get(key, only) is not only:
            raise ValueError(f"{key} = {flat[key]!r}: a model of short "
                             f"convolutions and attention runs with "
                             f"{only!r} alone")
    turning = flat.get("rope_parameters") or {}
    if turning.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters.rope_type = "
                         f"{turning['rope_type']!r}: 'default' alone")
    total = int(flat.get("experts_total") or flat["num_experts"])
    if int(flat["num_experts_per_tok"]) > total:
        raise ValueError(f"num_experts_per_tok = "
                         f"{flat['num_experts_per_tok']}: over the "
                         f"{total} experts the router scores")
    kinds = tuple(_CONV_LAYER_TYPES[t] for t in mine)
    dense = max(0, min(held, int(flat.get("num_dense_layers", 0))
                       - int(flat.get("layer_first", 0))))
    if "gqa" in kinds[:dense]:
        raise ValueError(
            f"layer_types: a full_attention layer before num_dense_layers "
            f"= {flat['num_dense_layers']}: a dense layer's mixer is a "
            f"convolution here")
    flat.setdefault("tie_word_embeddings", True)
    flat.update(
        layer_kinds=kinds, first_k_dense_replace=dense,
        layer_rotated=tuple(kind == "gqa" for kind in kinds),
        rope_interleave=False, qk_norm=True,
        rope_theta=turning.get("rope_theta", flat.get("rope_theta", 10000.0)),
        sconv_kernel=flat["conv_L_cache"], rms_norm_eps=flat["norm_eps"],
        experts_total=total, n_shared_experts=0, router_scoring="sigmoid",
        router_norm_eps=1e-6, moe_gate="silu")


def _routed_before_mixing(flat: dict) -> None:
    """The sizes here from SmallThinker's key names: every layer
    grouped-query attention, windowed and rotated as the two published
    layouts say (whole, sliced from `layer_first`, or the held slice
    alone), beside routed experts with the router on the block's input,
    a softmax over the picked logits and a ReLU gate; no shared expert,
    no dense layer. A layout entry, router rule or gate the code does
    not know is refused."""
    held = int(flat["num_hidden_layers"])
    flags = {}
    for key in ("sliding_window_layout", "rope_layout"):
        first, mine = _held_slice(flat, key)
        mine = list(mine)
        if len(mine) != held or set(mine) - {0, 1}:
            raise ValueError(f"{key}[{first}:{first + held}] = {mine}: "
                             f"{held} entries of 0 and 1 wanted")
        flags[key] = tuple(bool(v) for v in mine)
    if flat["moe_primary_router_apply_softmax"] is not True:
        raise ValueError("moe_primary_router_apply_softmax = "
                         f"{flat['moe_primary_router_apply_softmax']!r}: "
                         "the router's rule is a softmax over the picked")
    if flat.setdefault("moe_gate", "relu") not in moe.GATES:
        raise ValueError(f"moe_gate = {flat['moe_gate']!r}: one of "
                         f"{sorted(moe.GATES)}")
    flat.update(
        layer_kinds=("gqa",) * held,
        layer_windowed=flags["sliding_window_layout"],
        layer_rotated=flags["rope_layout"],
        sliding_window=flat["sliding_window_size"], rope_interleave=False,
        intermediate_size=0, first_k_dense_replace=0, n_shared_experts=0,
        moe_intermediate_size=flat["moe_ffn_hidden_size"],
        n_routed_experts=flat["moe_num_primary_experts"],
        num_experts_per_tok=flat["moe_num_active_primary_experts"],
        router_scoring="softmax", router_on_block_input=True)


def _one_sublayer_a_layer(flat: dict) -> None:
    """The sizes here from Nemotron-H's key names: the held layers'
    letters of `hybrid_override_pattern` (whole and sliced from
    `layer_first`, or the held slice alone), a layer one sublayer;
    Mamba-2 with `n_groups` B/C groups under a gated norm a group;
    attention without positions (`rope_theta` and
    `partial_rotary_factor` are read by nothing); a sigmoid router with
    a bias that picks, its weights normalised and scaled, over ungated
    squared-ReLU experts beside a shared one of its own width. The dense
    feed-forward's `intermediate_size` is no size of such a program (0).
    A letter, a group count, an activation, a bias or a group-limited
    router the code does not know is refused by name."""
    held = int(flat["num_hidden_layers"])
    first, mine = _held_slice(flat, "hybrid_override_pattern")
    unknown = sorted(set(mine) - set(_PATTERN))
    if unknown or len(mine) != held:
        raise ValueError(
            f"hybrid_override_pattern[{first}:{first + held}] = {mine!r}: "
            f"{held} letters of {sorted(_PATTERN)} wanted, {unknown} not "
            f"known")
    heads, groups = int(flat["mamba_num_heads"]), int(flat["n_groups"])
    if groups < 1 or heads % groups:
        raise ValueError(f"n_groups = {groups}: the B/C groups have to "
                         f"divide mamba_num_heads = {heads}")
    if flat.get("mlp_hidden_act") != "relu2":
        raise ValueError(
            f"mlp_hidden_act = {flat.get('mlp_hidden_act')!r}: the experts "
            f"of a pattern-driven model are ungated 'relu2' alone")
    for key, only in (("mamba_hidden_act", "silu"), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("use_bias", False), ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if flat.get(key, only) != only:
            raise ValueError(f"{key} = {flat[key]!r}: a pattern-driven "
                             f"model runs with {only!r} alone")
    flat.update(
        layer_kinds=tuple(_PATTERN[c] for c in mine), single_sublayer=True,
        intermediate_size=0, first_k_dense_replace=0,
        mamba_n_heads=heads, mamba_d_head=flat["mamba_head_dim"],
        mamba_d_state=flat["ssm_state_size"], mamba_n_groups=groups,
        mamba_d_conv=flat["conv_kernel"],
        mamba_conv_bias=flat.get("use_conv_bias", True),
        mamba_chunk_size=flat["chunk_size"],
        rms_norm_eps=flat["layer_norm_epsilon"],
        router_scoring="sigmoid", moe_gate="relu2")


def hybrid_decoder_kinds(first: int, held: int, total: int,
                         period: int) -> tuple:
    """The kinds of the `held` layers from the published index `first`
    of a decoder-hybrid-decoder of `total` layers: every `period`-th
    layer (0, period, ..) on the Mamba side, the rest on the attention
    side. In the first half: Mamba | attention with a window. Layer
    total/2: Mamba (its scan output is the memory); total/2 + 1: full
    attention (its K, V are kept). After them: a gated memory unit |
    cross-attention. A slice that reads a memory or K, V it does not
    make is refused."""
    half, kinds = total // 2, []
    for n in range(first, first + held):
        if n % period == 0:
            kinds.append("mamba" if n <= half else "gmu")
        else:
            kinds.append("swa" if n < half else
                         "full" if n == half + 1 else "cross")
    for reader, maker in (("gmu", "mamba"), ("cross", "full")):
        if reader in kinds and maker not in kinds[:kinds.index(reader)]:
            raise ValueError(
                f"layers {first}..{first + held - 1} of {total} hold a "
                f"{reader!r} layer and not the {maker!r} layer it reads")
    return tuple(kinds)


def _dt(name: str):
    return jnp.dtype(name)


def layer_norm(x, w, bias, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w + bias


def _norm(cfg: "EncoderConfig", x, p, name: str):
    """The configuration's norm of x with p's parameters `name`."""
    if cfg.layer_norm_eps:
        return layer_norm(x, p[name], p[name + "_bias"], cfg.layer_norm_eps)
    return rms_norm(x, p[name], cfg.rms_norm_eps)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, positions, theta: float, interleave: bool = True):
    """Rotary embedding: pair i of x turns by position * theta^(-2i/d),
    the pairs (x[2i], x[2i+1]) or, without `interleave`, (x[i],
    x[i + d/2]) (half rotation). x [..., L, heads, d]; positions [..., L]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    if not interleave:
        lo, hi = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos],
                               axis=-1)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _mm(cfg: "EncoderConfig", x, w):
    """x w with `compute_dtype` operands and a float32 sum."""
    dtype = _dt(cfg.compute_dtype)
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def swiglu(cfg: "EncoderConfig", x, w13, w2):
    """SwiGLU with gate and up side by side in w13 [D, 2F]; w2 [F, D]."""
    f = w2.shape[0]
    h = _mm(cfg, x, w13)
    return _mm(cfg, jax.nn.silu(h[..., :f]) * h[..., f:], w2)


def _by_rows(cfg: "EncoderConfig", fn, x2d):
    """fn over x2d [T, D], `loss_chunk` rows at a time where T is a
    whole number of them, so that a wide hidden activation is only ever
    held for one chunk (recomputed in the backward pass)."""
    t, chunk = x2d.shape[0], cfg.loss_chunk
    if not cfg.remat or t <= chunk or t % chunk:
        return fn(x2d)
    out = jax.lax.map(jax.checkpoint(fn),
                      x2d.reshape(t // chunk, chunk, x2d.shape[1]))
    return out.reshape(t, out.shape[-1])


def mla(p, cfg: EncoderConfig, x, seg, pos, scope: str = "enc.mla"):
    """Latent attention on x [B, L, D] (already normed)."""
    cd = _dt(cfg.compute_dtype)
    b, l, _ = x.shape
    h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    if cfg.q_lora_rank:
        c_q = rms_norm(_mm(cfg, x, p["w_qa"]), p["q_norm"], cfg.rms_norm_eps)
        q = _mm(cfg, c_q, p["w_qb"]).reshape(b, l, h, dn + dr)
    else:
        q = _mm(cfg, x, p["w_q"]).reshape(b, l, h, dn + dr)
    kva = _mm(cfg, x, p["w_kva"])
    c_kv = rms_norm(kva[..., :cfg.kv_lora_rank], p["kv_norm"],
                    cfg.rms_norm_eps)
    kv = _mm(cfg, c_kv, p["w_kvb"]).reshape(b, l, h, dn + dv)
    if cfg.mla_use_nope:
        q_rope, k_rope = q[..., dn:], kva[..., None, cfg.kv_lora_rank:]
    else:
        q_rope = rope(q[..., dn:], pos, cfg.rope_theta)
        k_rope = rope(kva[..., None, cfg.kv_lora_rank:], pos,
                      cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, l, h, dr))], axis=-1)
    heads_first = lambda t: t.astype(cd).transpose(0, 2, 1, 3)  # noqa: E731
    scale = 1.0 / math.sqrt(dn + dr)
    o = segment_attention(heads_first(q), heads_first(k),
                          heads_first(kv[..., dn:]), seg, pos,
                          block=cfg.attention_block, scale=scale,
                          scope=scope)
    return _mm(cfg, o.transpose(0, 2, 1, 3).reshape(b, l, h * dv), p["w_o"])


def kda(p, cfg: EncoderConfig, x, seg, scope: str = "enc.kda"):
    """Kimi Delta Attention on x [B, L, D] (already normed). Everything
    between the input and the output projection is a head's own, so the
    mixer runs `kda_head_block` heads at a time, each pass recomputed in
    the backward pass: its projections, convolution, gates, the scan
    (`ops/kda.py`), the head norm and its rows of W_o. Scopes `proj`,
    `conv`, `gate`, `scan`, `out` under `scope`."""
    cd = _dt(cfg.compute_dtype)
    b, l, d = x.shape
    h, dh = cfg.kda_num_heads, cfg.kda_head_dim
    hb = cfg.kda_head_block or h
    groups = h // hb

    def by_cols(w, dtype=None):  # [.., H dh] -> [groups, .., hb dh]
        w = w if dtype is None else w.astype(dtype)
        return jnp.moveaxis(w.reshape(w.shape[:-1] + (groups, hb * dh)),
                            -2, 0)

    with jax.named_scope(f"{scope}.gate"):
        f_low = _mm(cfg, x, p["w_fa"])
        gate_low = _mm(cfg, x, p["w_ga"])
        beta = jax.nn.sigmoid(_mm(cfg, x, p["w_b"]))            # [B, L, H]

    def heads(ws):
        (w_q, w_k, w_v, conv_q, conv_k, conv_v, w_fb, dt_bias, a_log,
         w_gb, w_o, beta_g) = ws
        with jax.named_scope(f"{scope}.proj"):
            q, k, v = (_mm(cfg, x, w) for w in (w_q, w_k, w_v))
        with jax.named_scope(f"{scope}.conv"):
            # q and k leave with every head of unit length, q over sqrt(dh)
            unit = lambda scale: (dh, cfg.l2_norm_eps, scale)  # noqa: E731
            q, k, v = (
                kda_ops.causal_conv(a, w, seg, silu=True, unit=norm,
                                    scope=f"{scope}.conv").reshape(
                                        b, l, hb, dh)
                for a, w, norm in ((q, conv_q, unit(dh ** -0.5)),
                                   (k, conv_k, unit(1.0)), (v, conv_v, None)))
        with jax.named_scope(f"{scope}.gate"):
            log_a = (-jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (_mm(cfg, f_low, w_fb) + dt_bias).reshape(b, l, hb, dh)))
        o = kda_ops.kda_scan(q, k, v, log_a, beta_g, seg, cfg.kda_chunk, cd,
                             f"{scope}.scan")
        with jax.named_scope(f"{scope}.out"):
            o = (rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
                 * jax.nn.sigmoid(_mm(cfg, gate_low, w_gb)).reshape(
                     b, l, hb, dh))
            return _mm(cfg, o.reshape(b, l, hb * dh), w_o)

    # the sum is outside what is recomputed, so no partial sum is kept
    y, _ = jax.lax.scan(
        lambda y, ws: (y + _maybe_remat(heads, cfg)(ws), None),
        jnp.zeros((b, l, d), jnp.float32),
        (by_cols(p["w_q"], cd), by_cols(p["w_k"], cd), by_cols(p["w_v"], cd),
         by_cols(p["conv_q"]), by_cols(p["conv_k"]), by_cols(p["conv_v"]),
         by_cols(p["w_fb"], cd), by_cols(p["dt_bias"]),
         p["a_log"].reshape(groups, hb),
         by_cols(p["w_gb"], cd),
         p["w_o"].astype(cd).reshape(groups, hb * dh, d),
         jnp.moveaxis(beta.reshape(b, l, groups, hb), 2, 0)))
    return y


def mamba(p, cfg: EncoderConfig, x, seg, scope: str = "enc.mamba"):
    """Mamba's mixer on x [B, L, D] (already normed). Returns (out, y):
    y [B, L, channels] is the scan's output before the gate, the memory
    a later gated memory unit reads. Scopes `proj`, `conv`, `dt`,
    `scan` (`ops/ssm.py`), `out` under `scope`."""
    n, rank = cfg.mamba_d_state, cfg.dt_rank
    di = cfg.mamba_channels
    with jax.named_scope(f"{scope}.proj"):
        # two products: [x | z] [B, L, 2 channels] is never held whole
        xi, z = _mm(cfg, x, p["w_in"][:, :di]), _mm(cfg, x, p["w_in"][:, di:])
    with jax.named_scope(f"{scope}.conv"):
        xi = kda_ops.causal_conv(xi, p["conv_x"], seg, p["conv_bias"],
                                 silu=True, scope=f"{scope}.conv")
    with jax.named_scope(f"{scope}.dt"):
        low = _mm(cfg, xi, p["w_x"])
        dt = jax.nn.softplus(_mm(cfg, low[..., :rank], p["w_dt"])
                             + p["dt_bias"])
    y = ssm.selective_scan(
        xi, dt, -jnp.exp(p["a_log"]), low[..., rank:rank + n],
        low[..., rank + n:], p["d_skip"], seg, cfg.ssm_chunk, jnp.float32,
        f"{scope}.scan", channels=cfg.ssm_channels)
    with jax.named_scope(f"{scope}.out"):
        return _mm(cfg, y * jax.nn.silu(z), p["w_out"]), y


def ssd(p, cfg: EncoderConfig, x, seg, scope: str = "enc.ssd"):
    """Mamba-2's mixer on x [B, L, D] (already normed): one input
    projection [z | xBC | dt'], one convolution over x, B and C
    together, the chunked scan (`ops/ssd.py`), the gate and then one
    norm over all channels or, with `mamba_n_groups` groups of B and C,
    over each group's. Scopes `proj`, `conv`, `dt`, `scan`, `norm`,
    `out` under `scope`."""
    b, l, _ = x.shape
    h, dh = cfg.mamba_n_heads, cfg.mamba_d_head
    groups = cfg.mamba_n_groups
    di, n = h * dh, groups * cfg.mamba_d_state   # B's columns, and C's
    with jax.named_scope(f"{scope}.proj"):
        zxbcdt = _mm(cfg, x, p["w_in"])
    with jax.named_scope(f"{scope}.conv"):
        # xBC is read where it stands in [z | xBC | dt]
        xbc = kda_ops.causal_conv(zxbcdt, p["conv_w"], seg,
                                  p.get("conv_bias"), first=di, silu=True,
                                  scope=f"{scope}.conv")
    with jax.named_scope(f"{scope}.dt"):
        dt = jax.nn.softplus(zxbcdt[..., 2 * di + 2 * n:] + p["dt_bias"])
    by_group = ((lambda v: v.reshape(b, l, groups, cfg.mamba_d_state))
                if groups > 1 else (lambda v: v))
    y = ssd_ops.ssd_scan(
        xbc[..., :di].reshape(b, l, h, dh), dt, -jnp.exp(p["a_log"]),
        by_group(xbc[..., di:di + n]), by_group(xbc[..., di + n:]),
        p["d_skip"], seg, cfg.mamba_chunk_size, _dt(cfg.compute_dtype),
        f"{scope}.scan")
    with jax.named_scope(f"{scope}.norm"):
        if groups > 1:  # one group keeps the ops it had
            y = ssd_ops.gated_group_norm(y.reshape(b, l, di),
                                         zxbcdt[..., :di], p["norm"], groups,
                                         cfg.rms_norm_eps)
        else:
            y = rms_norm(y.reshape(b, l, di) * jax.nn.silu(zxbcdt[..., :di]),
                         p["norm"], cfg.rms_norm_eps)
    with jax.named_scope(f"{scope}.out"):
        return _mm(cfg, y, p["w_out"])


def short_conv(p, cfg: EncoderConfig, x, seg, scope: str = "enc.sconv"):
    """The doubly gated short convolution on x [B, L, D] (already
    normed): [B | C | x] = u W_in, (C * conv(B * x)) W_out, the
    convolution (`ops/kda.py::causal_conv`) depthwise and causal inside
    a history, bare: no bias, no activation. Scopes `proj`, `gate` (both
    products), `conv`, `out` under `scope`."""
    d = p["w_out"].shape[0]
    with jax.named_scope(f"{scope}.proj"):
        bcx = _mm(cfg, x, p["w_in"])
    with jax.named_scope(f"{scope}.gate"):
        v = bcx[..., :d] * bcx[..., 2 * d:]
    with jax.named_scope(f"{scope}.conv"):
        c = kda_ops.causal_conv(v, p["taps"], seg, scope=f"{scope}.conv")
    with jax.named_scope(f"{scope}.gate"):
        y = bcx[..., d:2 * d] * c
    with jax.named_scope(f"{scope}.out"):
        return _mm(cfg, y, p["w_out"])


def gqa(p, cfg: EncoderConfig, x, seg, pos, scope: str = "enc.gqa",
        window=None, rotate: bool = False):
    """Plain softmax attention over grouped heads on x [B, L, D]
    (already normed): query head j reads key and value head j // (heads
    / kv heads). Where the block holds `q_norm` and `k_norm`, an RMSNorm
    a head on q and on k first (scope `qk_norm`, opened by no other
    model); with `rotate` RoPE on q and k then, with a `window` the keys
    t - s < window alone. Scopes `proj`, `pairs`, `out`."""
    cd = _dt(cfg.compute_dtype)
    b, l, _ = x.shape
    h, hk = cfg.num_attention_heads, cfg.num_key_value_heads
    dh = p["w_q"].shape[1] // h

    def turn(t, norm=None):
        if norm in p:
            with jax.named_scope(f"{scope}.qk_norm"):
                t = rms_norm(t, p[norm], cfg.rms_norm_eps)
        return (rope(t, pos, cfg.rope_theta, cfg.rope_interleave)
                if rotate else t)

    with jax.named_scope(f"{scope}.proj"):
        q = turn(_mm(cfg, x, p["w_q"]).reshape(b, l, h, dh), "q_norm")
        k, v = (jnp.repeat(f(_mm(cfg, x, p[w]).reshape(b, l, hk, dh)),
                           h // hk, axis=2)
                for w, f in (("w_k", lambda t: turn(t, "k_norm")),
                             ("w_v", lambda t: t)))
        q, k, v = (t.astype(cd).transpose(0, 2, 1, 3) for t in (q, k, v))
    o = segment_attention(q, k, v, seg, pos, block=cfg.attention_block,
                          scale=cfg.attention_multiplier or dh ** -0.5,
                          scope=f"{scope}.pairs", window=window)
    with jax.named_scope(f"{scope}.out"):
        return _mm(cfg, o.transpose(0, 2, 1, 3).reshape(b, l, h * dh),
                   p["w_o"])


def gmu(p, cfg: EncoderConfig, x, m):
    """The gated memory unit on x [B, L, D] (already normed): the
    memory m [B, L, channels] gated by x, element by element."""
    return _mm(cfg, jax.nn.silu(_mm(cfg, x, p["w_g"])) * m, p["w_o"])


def lambda_init(layer: int) -> float:
    """lam0 of differential attention at the published layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_attention(p, cfg: EncoderConfig, x, seg, pos, layer: int,
                   window=None, kv=None, scope: str = "enc.attn"):
    """Differential attention on x [B, L, D] (already normed) at the
    published layer index `layer`. Without `kv` the block makes Q, K, V
    itself; with it (a cross layer) Q alone, and reads the K [B, L,
    kv heads, d], V given. Returns (out, (K, V)). The two softmaxes of a
    pair are two heads of one `segment_attention` over the stacked
    query heads, each beside its key head and its group's value, so the
    score tile is a head's. Scopes `proj`, `pairs`, `subln`, `out`."""
    cd = _dt(cfg.compute_dtype)
    b, l, d = x.shape
    h, hk = cfg.num_attention_heads, cfg.num_key_value_heads
    dh = d // h
    with jax.named_scope(f"{scope}.proj"):
        if kv is None:
            qkv = _mm(cfg, x, p["w_qkv"]) + p["qkv_bias"]
            q = qkv[..., :h * dh]
            k = qkv[..., h * dh:(h + hk) * dh].reshape(b, l, hk, dh)
            v = qkv[..., (h + hk) * dh:].reshape(b, l, hk, dh)
        else:
            q = _mm(cfg, x, p["w_q"]) + p["q_bias"]
            k, v = kv
        # query head 2j + s reads key head 2g + s and value group g = j //
        # rep: [groups, rep, 2] is the order of the query heads
        rep = h // hk

        def beside_queries(t, width):  # [B, L, groups, 1 | 2, width]
            t = jnp.broadcast_to(t[:, :, :, None],
                                 (b, l, hk // 2, rep, 2, width))
            return t.reshape(b, l, h, width).astype(cd).transpose(0, 2, 1, 3)

        stacked = (
            q.reshape(b, l, h, dh).astype(cd).transpose(0, 2, 1, 3),
            beside_queries(k.reshape(b, l, hk // 2, 2, dh), dh),
            beside_queries(v.reshape(b, l, hk // 2, 1, 2 * dh), 2 * dh))
    o = segment_attention(*stacked, seg, pos, block=cfg.attention_block,
                          scale=dh ** -0.5, scope=f"{scope}.pairs",
                          window=window)                # [B, H, L, 2 dh]
    with jax.named_scope(f"{scope}.subln"):
        lam0 = lambda_init(layer)
        lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
               - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
        o = rms_norm(o[:, 0::2] - lam * o[:, 1::2], p["sub_norm"],
                     cfg.norm_eps) * (1.0 - lam0)
    with jax.named_scope(f"{scope}.out"):
        out = _mm(cfg, o.transpose(0, 2, 1, 3).reshape(b, l, d),
                  p["w_o"]) + p["o_bias"]
    return out, (k, v)


def carried_block(p, cfg: EncoderConfig, kind: str, layer: int, h, seg, pos,
                  carry: dict):
    """One block of a decoder-hybrid-decoder on the residual stream h
    [B, L, D], of `kind` at the published index `layer`. `carry` holds
    what earlier layers left for later ones beside the stream: `m` (the
    last Mamba layer's scan output) and `kv` (the full-attention
    layer's K, V). Returns (h, carry). Under the configuration's
    recomputation a block is one recomputed function: what it leaves is
    an output of it and what it reads an input, so a maker is not
    computed again for its readers."""
    def run(p, h, m, kv):
        x = _norm(cfg, h, p, "norm1")
        made = None
        if kind == "mamba":
            with jax.named_scope("enc.mamba"):
                y, made = mamba(p["mamba"], cfg, x, seg)
        elif kind == "ssd":
            with jax.named_scope("enc.ssd"):
                y = ssd(p["ssd"], cfg, x, seg)
        elif kind == "gqa":
            with jax.named_scope("enc.gqa"):
                y = gqa(p["gqa"], cfg, x, seg, pos)
        elif kind == "gmu":
            with jax.named_scope("enc.gmu"):
                y = gmu(p["gmu"], cfg, x, m)
        elif kind == "cross":
            with jax.named_scope("enc.cross"):
                y, _ = diff_attention(p["cross"], cfg, x, seg, pos, layer,
                                      kv=kv, scope="enc.cross")
        else:
            with jax.named_scope("enc.attn"):
                y, made = diff_attention(
                    p["diff"], cfg, x, seg, pos, layer,
                    window=cfg.sliding_window if kind == "swa" else None)
        if cfg.residual_multiplier != 1.0:
            y = cfg.residual_multiplier * y
        return _feed_forward(p, None, cfg, h + y,
                             scale=cfg.residual_multiplier)[0], made

    h, made = _maybe_remat(run, cfg)(
        p, h, carry.get("m") if kind == "gmu" else None,
        carry.get("kv") if kind == "cross" else None)
    if kind == "mamba":
        carry = {**carry, "m": made}
    elif kind == "full":
        carry = {**carry, "kv": made}
    return h, carry


def _route(p, bias, cfg: EncoderConfig, x2d):
    return moe.route(x2d, p["w_g"], bias, cfg.num_experts_per_tok,
                     cfg.routed_scaling_factor, cfg.router_scoring,
                     cfg.router_norm_eps)


def expert_ffn(p, bias, cfg: EncoderConfig, x2d, scope: str = "enc.moe",
               routing=None):
    """Shared SwiGLU (where the block holds one) + the held experts'
    part, routed here on x2d or as `routing` says (`_route`'s result on
    the block's input). Returns (y, routed): the held experts' token
    counts, every expert's load and each token's picks [T, k]."""
    cd = _dt(cfg.compute_dtype)
    if routing is None:
        with jax.named_scope("moe.router"):
            routing = _route(p, bias, cfg, x2d)
    idx, weights, load = routing
    with jax.named_scope("moe.experts"):
        y, counts = moe.held_experts(
            x2d, weights, idx, p["experts_w13"], p["experts_w2"],
            cfg.expert_first, cfg.moe_block_rows, cd, scope, cfg.moe_gate)
    if "shared_w13" in p:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(cfg, x2d, p["shared_w13"], p["shared_w2"])
    return y, {"counts": counts, "load": load, "picks": idx}


def _mix(p, cfg: EncoderConfig, h, seg, pos, scope: str = "", n: int = 0):
    """The first half of `block`: h += Mixer(norm(h)), the mixer the
    one whose parameters the block holds (`attn`: MLA, `kda`: KDA,
    `sconv`: the gated short convolution, `gqa`: grouped-query
    attention, windowed and rotated as the configuration says of the
    held layer `n`, traced under `enc.gqa_swa` with a window and
    `enc.gqa_full` without)."""
    x = _norm(cfg, h, p, "norm1")
    if "kda" in p:
        with jax.named_scope(scope or "enc.kda"):
            return h + kda(p["kda"], cfg, x, seg, scope or "enc.kda")
    if "sconv" in p:
        with jax.named_scope(scope or "enc.sconv"):
            return h + short_conv(p["sconv"], cfg, x, seg,
                                  scope or "enc.sconv")
    if "gqa" in p:
        windowed = bool(cfg.layer_windowed) and cfg.layer_windowed[n]
        scope = scope or ("enc.gqa_swa" if windowed else "enc.gqa_full")
        with jax.named_scope(scope):
            return h + gqa(
                p["gqa"], cfg, x, seg, pos, scope,
                window=cfg.sliding_window if windowed else None,
                rotate=bool(cfg.layer_rotated) and cfg.layer_rotated[n])
    with jax.named_scope(scope or "enc.mla"):
        return h + mla(p["attn"], cfg, x, seg, pos, scope or "enc.mla")


def block(p, bias, cfg: EncoderConfig, h, seg, pos, scope: str = "",
          n: int = 0):
    """One block on the residual stream h [B, L, D], the held layer `n`
    where layers differ; a dense block holds no router (`w_g`), and
    `bias` is None there and for a router without one. Returns (h,
    routed), `routed` (see `expert_ffn`) None when dense. Its ops are
    traced under `enc.mla`, `enc.kda` or `enc.gqa_*`, `enc.dense_ffn`
    and `enc.moe`, or all under `scope` where one is given (the MTP
    module's block). With `router_on_block_input` the router reads h as
    it enters, before the mixer (`enc.router`), and the experts run
    under `enc.experts`; so they do, the router on the normed stream,
    in a block that holds no shared expert (`_feed_forward`)."""
    routing = None
    if cfg.router_on_block_input and "w_g" in p:
        with jax.named_scope(scope or "enc.router"):
            routing = _route(p, bias, cfg, h.reshape(-1, h.shape[-1]))
    h = _mix(p, cfg, h, seg, pos, scope, n)
    return _feed_forward(
        p, bias, cfg, h,
        scope or ("enc.experts" if routing is not None else ""),
        routing=routing)


def _feed_forward(p, bias, cfg: EncoderConfig, h, scope: str = "",
                  scale: float = 1.0, routing=None):
    """The second half of `block`: h += FFN(norm(h)), a dense one times
    `scale` (a carried block's residual multiplier). An expert block
    that holds no shared expert has two parts and not three, traced
    apart: the router under `enc.router`, the held experts under
    `enc.experts` (as a block that routes on its input has them)."""
    b, l, d = h.shape
    x2d = _norm(cfg, h, p, "norm2").reshape(b * l, d)
    if "w_g" in p and "shared_w13" not in p and routing is None and not scope:
        with jax.named_scope("enc.router"):
            routing = _route(p, bias, cfg, x2d)
        scope = "enc.experts"
    if "w_g" not in p:
        with jax.named_scope(scope or "enc.dense_ffn"):
            y = _by_rows(cfg, lambda x: swiglu(cfg, x, p["w13"], p["w2"]),
                         x2d)
            if scale != 1.0:
                y = scale * y
        return h + y.reshape(b, l, d), None
    with jax.named_scope(scope or "enc.moe"):
        y, routed = expert_ffn(p, bias, cfg, x2d, scope or "enc.moe",
                               routing)
    return h + y.reshape(b, l, d), routed


def ungated_ffn(cfg: "EncoderConfig", x, w1, w2):
    """W_down relu(W_up x)^2: w1 [D, F], w2 [F, D]."""
    r = jax.nn.relu(_mm(cfg, x, w1))
    return _mm(cfg, r * r, w2)


def ungated_expert_ffn(p, bias, cfg: EncoderConfig, x2d):
    """The expert feed-forward of a pattern-driven model on x2d [T, D]
    (already normed): the router (`enc.router`), the held ungated
    experts' part of the routed result (`enc.experts`, the dispatch plan
    under `enc.experts.plan`) and the shared ungated expert of its own
    width (`enc.shared`). Returns (y, routed) as `expert_ffn`."""
    with jax.named_scope("enc.router"):
        idx, weights, load = _route(p, bias, cfg, x2d)
    y, counts = moe.held_experts(
        x2d, weights, idx, p["experts_w1"], p["experts_w2"], cfg.expert_first,
        cfg.moe_block_rows, _dt(cfg.compute_dtype), "enc.experts",
        cfg.moe_gate)
    if "shared_w1" in p:
        with jax.named_scope("enc.shared"):
            y = y + ungated_ffn(cfg, x2d, p["shared_w1"], p["shared_w2"])
    return y, {"counts": counts, "load": load, "picks": idx}


def sublayer(p, bias, cfg: EncoderConfig, kind: str, h, seg, pos):
    """One layer of a model whose layers are one sublayer each, on the
    residual stream h [B, L, D]: h += Mixer(norm(h)), the mixer by
    `kind`: "ssd" (traced under `enc.ssd`), "gqa" (`enc.gqa`) or
    "experts" (`ungated_expert_ffn`; `bias` its router's). Returns (h,
    routed), `routed` None for a layer that routes nothing."""
    b, l, d = h.shape
    x = _norm(cfg, h, p, "norm")
    if kind == "ssd":
        with jax.named_scope("enc.ssd"):
            return h + ssd(p["ssd"], cfg, x, seg), None
    if kind == "gqa":
        with jax.named_scope("enc.gqa"):
            return h + gqa(p["gqa"], cfg, x, seg, pos), None
    y, routed = ungated_expert_ffn(p, bias, cfg, x.reshape(b * l, d))
    return h + y.reshape(b, l, d), routed


def _run_sublayers(params, cfg: EncoderConfig, h, seg, pos):
    """`run_blocks` for a model whose layers are one sublayer each: a
    list of unlike layers, each one recomputed function."""
    routed = []
    biases = iter(params.get("router_bias", ()))
    for p, kind in zip(params["layers"], cfg.kinds):
        bias = next(biases) if kind == "experts" else None
        h, r = _maybe_remat(
            lambda p, bias, h, kind=kind: sublayer(p, bias, cfg, kind, h, seg,
                                                   pos), cfg)(p, bias, h)
        if r is not None:
            routed.append(r)
    if not routed:
        return h, None
    return h, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


CARRIED_KINDS = ("mamba", "swa", "full", "gmu", "cross", "ssd", "gqa")


def _maybe_remat(fn, cfg: EncoderConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _kda_block(p, bias, cfg: EncoderConfig, h, seg, pos):
    """`block` for a KDA layer under the configuration's recomputation.
    The mixer recomputes its own passes of heads (`kda`), so only the
    feed-forward half is wrapped here: wrapped whole, as a
    latent-attention block is, every pass would be computed a third
    time."""
    return _maybe_remat(
        lambda p, bias, h: _feed_forward(p, bias, cfg, h), cfg)(
        p, bias, _mix(p, cfg, h, seg, pos))


def encode(params, cfg: EncoderConfig, tokens, seg, pos):
    """tokens, seg, pos [B, L] -> the last block's residual stream
    [B, L, D] (before the final norm) and what the expert layers routed
    (`expert_ffn`), stacked over the layers: counts [n_moe, held], load
    [n_moe, experts_total], picks [n_moe, B * L, k]; None without one."""
    h = jnp.take(params["emb"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        h = cfg.embedding_multiplier * h
    return run_blocks(params, cfg, h, seg, pos)


def run_blocks(params, cfg: EncoderConfig, h, seg, pos):
    """`encode` from the residual stream h [B, L, D] that enters the
    first held block (the item embeddings, or what an earlier pipeline
    stage hands on)."""
    if cfg.single_sublayer:
        return _run_sublayers(params, cfg, h, seg, pos)
    carry: dict = {}
    for n, p in enumerate(params["dense"]):
        if cfg.kinds[n] in CARRIED_KINDS:
            h, carry = carried_block(p, cfg, cfg.kinds[n],
                                     cfg.layer_first + n, h, seg, pos, carry)
            continue
        if "kda" in p:
            h, _ = _kda_block(p, None, cfg, h, seg, pos)
            continue
        h = _maybe_remat(
            lambda p, h, n=n: block(p, None, cfg, h, seg, pos, n=n)[0],
            cfg)(p, h)
    if not cfg.n_moe:
        return h, None

    def one(h, layer, n=0):
        p, bias = layer
        return block(p, bias, cfg, h, seg, pos, n=n)

    if cfg.moe_stacked:
        return jax.lax.scan(_maybe_remat(one, cfg), h,
                            (params["moe"], params["router_bias"]))
    routed = []
    biases = params.get("router_bias", (None,) * cfg.n_moe)
    for n, layer in enumerate(zip(params["moe"], biases), cfg.n_dense):
        h, r = (_kda_block(*layer, cfg, h, seg, pos) if "kda" in layer[0]
                else _maybe_remat(functools.partial(one, n=n), cfg)(h, layer))
        routed.append(r)
    return h, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def mtp_hidden(params, cfg: EncoderConfig, h, tokens, seg, pos):
    """The MTP module's residual stream for every position t: from h_t
    and the embedding of token t+1 (rolled in; the last position of a
    segment is masked by the loss)."""
    p = params["mtp"]

    def run(p, h):
        nxt = jnp.take(params["emb"], jnp.roll(tokens, -1, axis=1), axis=0)
        both = jnp.concatenate(
            [rms_norm(h, p["norm_h"], cfg.rms_norm_eps),
             rms_norm(nxt, p["norm_e"], cfg.rms_norm_eps)], axis=-1)
        h2 = _mm(cfg, both, p["w_eh"])
        return block(p["block"], params["mtp_router_bias"], cfg, h2, seg,
                     pos, scope="enc.mtp")

    with jax.named_scope("enc.mtp"):
        return _maybe_remat(run, cfg)(p, h)


def head_logits(params, cfg: EncoderConfig, h):
    """The final norm and the head on h [.., D]: logits [.., V]. A tied
    head is the embedding read transposed, by the product itself."""
    x = _norm(cfg, h, params, "final_norm")
    if not cfg.tie_word_embeddings:
        logits = _mm(cfg, x, params["head"])
    else:
        dtype = _dt(cfg.compute_dtype)
        logits = jax.lax.dot_general(
            x.astype(dtype), params["emb"].astype(dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def cross_entropy_sum(params, cfg: EncoderConfig, h2d, targets, valid,
                      rows: bool = False):
    """Sum over the valid rows of h2d [T, D] of -log softmax(head(
    norm(h)))[target], a chunk of rows at a time: [chunk, vocabulary]
    is the most that is ever held, and the backward pass recomputes it.
    Returns (the sum, with `rows` every row's term [T], zero where not
    valid, else None)."""
    t = h2d.shape[0]
    chunk = cfg.loss_chunk if t % cfg.loss_chunk == 0 else t

    def one(total, xs):
        h_c, t_c, v_c = xs
        logits = head_logits(params, cfg, h_c)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
        nll = (lse - tgt) * v_c
        return total + jnp.sum(nll), nll if rows else None

    split = lambda a: a.reshape((t // chunk, chunk) + a.shape[1:])  # noqa: E731
    total, nll = jax.lax.scan(
        _maybe_remat(one, cfg), jnp.float32(0.0),
        (split(h2d), split(targets), split(valid.astype(jnp.float32))))
    return total, nll.reshape(t) if rows else None


def losses(params, cfg: EncoderConfig, tokens, seg, pos):
    """(CE + w * CE_mtp, aux). A position counts where its target lies in
    its own history: t+1 for CE, t+2 for the MTP module. A model with
    a window layout reports `nll_rows` too, every position's CE term
    [B, L] (zero where none counts): a window cuts keys from a few rows
    of a batch, too few for the mean to feel them."""
    b, l = tokens.shape
    h, routed = encode(params, cfg, tokens, seg, pos)

    def shifted(k):
        ahead = jnp.roll(seg, -k, axis=1)
        ok = (seg != 0) & (ahead == seg) & (jnp.arange(l) < l - k)[None, :]
        return jnp.roll(tokens, -k, axis=1).reshape(-1), ok.reshape(-1)

    with jax.named_scope("enc.head_loss"):
        t1, v1 = shifted(1)
        total, nll = cross_entropy_sum(params, cfg, h.reshape(b * l, -1), t1,
                                       v1, rows=any(cfg.layer_windowed))
        ce = total / jnp.maximum(jnp.sum(v1), 1)
    aux = {"ce": ce, **(routed or {})}
    if nll is not None:
        aux["nll_rows"] = nll.reshape(b, l)
    if not cfg.num_nextn_predict_layers:
        return ce, aux
    h2, mtp_routed = mtp_hidden(params, cfg, h, tokens, seg, pos)
    with jax.named_scope("enc.head_loss"):
        t2, v2 = shifted(2)
        ce_mtp = (cross_entropy_sum(params, cfg, h2.reshape(b * l, -1),
                                    t2, v2)[0] / jnp.maximum(jnp.sum(v2), 1))
    aux.update(ce_mtp=ce_mtp,
               **{"mtp_" + k: v for k, v in mtp_routed.items()})
    return ce + cfg.mtp_loss_weight * ce_mtp, aux


# -- parameters ----------------------------------------------------------------

def _attn_shapes(cfg: EncoderConfig) -> dict:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    q_out = h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    query = ({"w_qa": (d, cfg.q_lora_rank), "q_norm": (cfg.q_lora_rank,),
              "w_qb": (cfg.q_lora_rank, q_out)} if cfg.q_lora_rank
             else {"w_q": (d, q_out)})
    return {
        **query,
        "w_kva": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": (cfg.kv_lora_rank,),
        "w_kvb": (cfg.kv_lora_rank,
                  h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "w_o": (h * cfg.v_head_dim, d),
    }


def _kda_shapes(cfg: EncoderConfig) -> dict:
    d, dh = cfg.hidden_size, cfg.kda_head_dim
    wide = cfg.kda_num_heads * dh
    return {
        "w_q": (d, wide), "w_k": (d, wide), "w_v": (d, wide),
        "conv_q": (cfg.kda_conv_size, wide),
        "conv_k": (cfg.kda_conv_size, wide),
        "conv_v": (cfg.kda_conv_size, wide),
        "w_fa": (d, dh), "w_fb": (dh, wide),
        "a_log": (cfg.kda_num_heads,), "dt_bias": (wide,),
        "w_b": (d, cfg.kda_num_heads),
        "w_ga": (d, dh), "w_gb": (dh, wide),
        "o_norm": (dh,), "w_o": (wide, d),
    }


def _mamba_shapes(cfg: EncoderConfig) -> dict:
    d, di, n = cfg.hidden_size, cfg.mamba_channels, cfg.mamba_d_state
    return {"w_in": (d, 2 * di), "conv_x": (cfg.mamba_d_conv, di),
            "conv_bias": (di,), "w_x": (di, cfg.dt_rank + 2 * n),
            "w_dt": (cfg.dt_rank, di), "dt_bias": (di,), "a_log": (di, n),
            "d_skip": (di,), "w_out": (di, d)}


def _ssd_shapes(cfg: EncoderConfig) -> dict:
    """Mamba-2's: w_in's columns are [z | x | B | C | dt'], the
    convolution runs over [x | B | C]; B and C are `mamba_n_groups`
    groups of `mamba_d_state` columns each."""
    d, h = cfg.hidden_size, cfg.mamba_n_heads
    di, n = h * cfg.mamba_d_head, cfg.mamba_n_groups * cfg.mamba_d_state
    bias = {"conv_bias": (di + 2 * n,)} if cfg.mamba_conv_bias else {}
    return {"w_in": (d, 2 * di + 2 * n + h),
            "conv_w": (cfg.mamba_d_conv, di + 2 * n), **bias,
            "dt_bias": (h,), "a_log": (h,), "d_skip": (h,), "norm": (di,),
            "w_out": (di, d)}


def _gqa_shapes(cfg: EncoderConfig) -> dict:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    dh = cfg.head_dim or d // h
    norms = {"q_norm": (dh,), "k_norm": (dh,)} if cfg.qk_norm else {}
    return {"w_q": (d, h * dh), "w_k": (d, cfg.num_key_value_heads * dh),
            "w_v": (d, cfg.num_key_value_heads * dh), "w_o": (h * dh, d),
            **norms}


def _sconv_shapes(cfg: EncoderConfig) -> dict:
    """The gated short convolution's: w_in's columns are [B | C | x]."""
    d = cfg.hidden_size
    return {"w_in": (d, 3 * d), "taps": (cfg.sconv_kernel, d),
            "w_out": (d, d)}


def _diff_shapes(cfg: EncoderConfig, cross: bool) -> dict:
    """Differential attention's; a cross layer projects queries alone."""
    d = cfg.hidden_size
    dh = d // cfg.num_attention_heads
    wide = d + 2 * cfg.num_key_value_heads * dh
    proj = ({"w_q": (d, d), "q_bias": (d,)} if cross
            else {"w_qkv": (d, wide), "qkv_bias": (wide,)})
    return {**proj, "lambda_q1": (dh,), "lambda_k1": (dh,),
            "lambda_q2": (dh,), "lambda_k2": (dh,), "sub_norm": (2 * dh,),
            "w_o": (d, d), "o_bias": (d,)}


def _mixer_shapes(cfg: EncoderConfig, kind: str) -> dict:
    """{the key a block holds its mixer under: the mixer's shapes}."""
    if kind == "kda":
        return {"kda": _kda_shapes(cfg)}
    if kind == "mamba":
        return {"mamba": _mamba_shapes(cfg)}
    if kind == "gmu":
        return {"gmu": {"w_g": (cfg.hidden_size, cfg.mamba_channels),
                        "w_o": (cfg.mamba_channels, cfg.hidden_size)}}
    if kind in ("swa", "full"):
        return {"diff": _diff_shapes(cfg, False)}
    if kind == "cross":
        return {"cross": _diff_shapes(cfg, True)}
    if kind == "ssd":
        return {"ssd": _ssd_shapes(cfg)}
    if kind == "gqa":
        return {"gqa": _gqa_shapes(cfg)}
    if kind == "sconv":
        return {"sconv": _sconv_shapes(cfg)}
    if kind == "mla":
        return {"attn": _attn_shapes(cfg)}
    raise ValueError(f"no mixer of kind {kind!r}")


def _norm_shapes(cfg: EncoderConfig, *names: str) -> dict:
    """A scale for each norm and, for LayerNorm, a bias."""
    d = cfg.hidden_size
    out = {name: (d,) for name in names}
    if cfg.layer_norm_eps:
        out.update({name + "_bias": (d,) for name in names})
    return out


def _block_shapes(cfg: EncoderConfig, dense: bool, kind: str = "mla") -> dict:
    d = cfg.hidden_size
    out = {**_mixer_shapes(cfg, kind), **_norm_shapes(cfg, "norm1", "norm2")}
    if dense:
        out.update(w13=(d, 2 * cfg.intermediate_size),
                   w2=(cfg.intermediate_size, d))
    else:
        f, e = cfg.moe_intermediate_size, cfg.n_routed_experts
        fs = f * cfg.n_shared_experts
        out.update(w_g=(d, cfg.experts_total),
                   experts_w13=(e, d, 2 * f), experts_w2=(e, f, d))
        if fs:  # a model without a shared expert holds no such arrays
            out.update(shared_w13=(d, 2 * fs), shared_w2=(fs, d))
    return out


def _sublayer_shapes(cfg: EncoderConfig, kind: str) -> dict:
    """A layer that is one sublayer: its norm and the mixer's own, or
    the router, the held ungated experts (up and down, two matrices an
    expert) and the shared one."""
    d = cfg.hidden_size
    if kind != "experts":
        return {**_norm_shapes(cfg, "norm"), **_mixer_shapes(cfg, kind)}
    f, e = cfg.moe_intermediate_size, cfg.n_routed_experts
    fs = (cfg.moe_shared_expert_intermediate_size
          or f * cfg.n_shared_experts) if cfg.n_shared_experts else 0
    shared = {"shared_w1": (d, fs), "shared_w2": (fs, d)} if fs else {}
    return {**_norm_shapes(cfg, "norm"), "w_g": (d, cfg.experts_total),
            "experts_w1": (e, d, f), "experts_w2": (e, f, d), **shared}


def param_shapes(cfg: EncoderConfig, vocab: int) -> dict:
    """The parameter tree as shapes. Expert blocks of one kind are
    stacked on a leading axis (`moe`), so that one scan runs them; of
    two kinds (`EncoderConfig.moe_stacked`) `moe` is a list. A model
    whose layers are one sublayer each holds them as the list `layers`."""
    d = cfg.hidden_size
    kinds = cfg.kinds
    if cfg.single_sublayer:
        head = {} if cfg.tie_word_embeddings else {"head": (d, vocab)}
        return {"emb": (vocab, d), **_norm_shapes(cfg, "final_norm"), **head,
                "layers": [_sublayer_shapes(cfg, kind) for kind in kinds]}
    shapes = {"emb": (vocab, d), **_norm_shapes(cfg, "final_norm"),
              "dense": [_block_shapes(cfg, True, kind)
                        for kind in kinds[:cfg.n_dense]]}
    if not cfg.tie_word_embeddings:
        shapes["head"] = (d, vocab)
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    if cfg.n_moe and cfg.moe_stacked:
        shapes["moe"] = jax.tree_util.tree_map(
            lambda s: (cfg.n_moe,) + s,
            _block_shapes(cfg, False, kinds[cfg.n_dense]), is_leaf=is_shape)
    elif cfg.n_moe:
        shapes["moe"] = [_block_shapes(cfg, False, kind)
                         for kind in kinds[cfg.n_dense:]]
    if cfg.num_nextn_predict_layers:
        shapes["mtp"] = {"norm_h": (d,), "norm_e": (d,), "w_eh": (2 * d, d),
                         "block": _block_shapes(cfg, False)}
    return shapes


def count_parameters(cfg: EncoderConfig, vocab: int) -> int:
    leaves = jax.tree_util.tree_leaves(
        param_shapes(cfg, vocab), is_leaf=lambda s: isinstance(s, tuple))
    return sum(math.prod(s) for s in leaves)


def init_params(cfg: EncoderConfig, vocab: int, key):
    """Weights normal(0, init_std), norms one, biases zero; a KDA
    layer's decay rates A = exp(a_log) uniform in [1, 16], a `dt_bias`
    the inverse softplus of a step log-uniform in [0.001, 0.1], a
    convolution's taps uniform within 1 / sqrt(width) (a gated short
    convolution's `taps` normal like a matrix); a Mamba layer's
    A = 1..states a channel (Mamba-2's: 1..heads, one a head) and its
    skip D one; differential
    attention's four lambda vectors normal(0, 0.1). Made where `key`
    lives."""
    shapes = param_shapes(cfg, vocab)
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        name = str(path[-1])
        if "a_log" in name and any(kind in str(path)
                                   for kind in ("mamba", "ssd")):
            out.append(jnp.broadcast_to(
                jnp.log(jnp.arange(1.0, shape[-1] + 1.0)), shape))
        elif "a_log" in name:
            out.append(jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                  1.0, 16.0)))
        elif "dt_bias" in name:
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        elif "bias" in name:
            out.append(jnp.zeros(shape, jnp.float32))
        elif "norm" in name or "d_skip" in name:
            out.append(jnp.ones(shape, jnp.float32))
        elif "lambda_" in name:
            out.append(0.1 * jax.random.normal(k, shape, jnp.float32))
        elif "conv_" in name:
            bound = shape[-2] ** -0.5
            out.append(jax.random.uniform(k, shape, jnp.float32, -bound,
                                          bound))
        else:
            out.append(cfg.init_std
                       * jax.random.normal(k, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, out)


def init_buffers(cfg: EncoderConfig) -> dict:
    """What the gradient does not touch: the routers' load-balance bias."""
    out = {}
    if not cfg.router_biased:
        return out
    if cfg.n_moe:
        out["router_bias"] = jnp.zeros((cfg.n_moe, cfg.experts_total),
                                       jnp.float32)
    if cfg.num_nextn_predict_layers:
        out["mtp_router_bias"] = jnp.zeros(cfg.experts_total, jnp.float32)
    return out


def leaf_of(tree, path: str, index=()):
    """`tree["a"]["b"][...]` for the path "a.b"; a digit indexes a list.
    `index` then picks inside the leaf: an int, or (lo, hi) for a slice."""
    node = tree
    for part in path.split("."):
        node = node[int(part)] if part.isdigit() else node[part]
    if not index:
        return node
    return node[tuple(slice(*i) if isinstance(i, tuple) else i
                      for i in index)]


# -- the train step and the scorer -------------------------------------------

def _balance(bias, load, rate):
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)


def train_step(cfg: EncoderConfig, lr: float):
    """`step(state, tokens, seg, pos) -> (state, metrics)`: one Adam step
    on a batch of packed sequences. state = {params, m, v, t, buffers}."""

    def sessionrec_train_step(state, tokens, seg, pos):
        buffers = state["buffers"]

        def loss_fn(p):
            return losses({**p, **buffers}, cfg, tokens, seg, pos)

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"])
        with jax.named_scope("enc.adam"):
            t = state["t"] + 1.0
            b1, b2 = cfg.adam_b1, cfg.adam_b2
            tree_map = jax.tree_util.tree_map
            m = tree_map(lambda mm, g: b1 * mm + (1 - b1) * g,
                         state["m"], grads)
            v = tree_map(lambda vv, g: b2 * vv + (1 - b2) * g * g,
                         state["v"], grads)
            params = tree_map(
                lambda p, mm, vv: p - lr * (mm / (1.0 - b1 ** t))
                / (jnp.sqrt(vv / (1.0 - b2 ** t)) + cfg.adam_eps),
                state["params"], m, v)
            new_buffers = dict(buffers)
            if "router_bias" in buffers:
                new_buffers["router_bias"] = _balance(
                    buffers["router_bias"], aux["load"],
                    cfg.bias_update_rate)
            if "mtp_router_bias" in buffers:
                new_buffers["mtp_router_bias"] = _balance(
                    buffers["mtp_router_bias"], aux["mtp_load"],
                    cfg.bias_update_rate)
        metrics = {"loss": loss, **{k: v for k, v in aux.items()
                                    if not k.endswith("load")}}
        return ({"params": params, "m": m, "v": v, "t": t,
                 "buffers": new_buffers}, metrics)

    return sessionrec_train_step


def first_step_report(cfg: EncoderConfig):
    """`report(state) -> {"grads", "params"}` for the configuration's
    report blocks, from the state the first step of a train left: the
    gradient the step used (Adam's first moment started at zero, so it
    holds (1 - b1) times that gradient) and the parameters it moved."""

    def sessionrec_first_step_report(state):
        return {"grads": jax.tree_util.tree_map(
                    lambda m: m / (1.0 - cfg.adam_b1),
                    report_of(cfg, state["m"])),
                "params": report_of(cfg, state["params"])}

    return sessionrec_first_step_report


def init_state(cfg: EncoderConfig, vocab: int, key):
    params = init_params(cfg, vocab, key)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    return {"params": params, "m": zeros(), "v": zeros(),
            "t": jnp.float32(0.0), "buffers": init_buffers(cfg)}


def report_of(cfg: EncoderConfig, params) -> dict:
    """The configuration's report blocks out of `params` (or a tree of
    its shape), as arrays of their own."""
    return {name: jnp.array(leaf_of(params, path, ix))
            for name, path, ix in cfg.report_blocks}


def score(params, cfg: EncoderConfig, seq, lengths):
    """Next-item logits [B, V] from right-padded histories seq [B, L]
    with `lengths` real tokens each: the last real position's state
    through the final norm and the head. Pads are a segment of their
    own, so a real position never sees one."""
    b, l = seq.shape
    real = jnp.arange(l)[None, :] < lengths[:, None]
    seg = real.astype(jnp.int32)
    pos = jnp.where(real, jnp.arange(l)[None, :],
                    jnp.arange(l)[None, :] - lengths[:, None])
    h, _ = encode(params, cfg, jnp.where(real, seq, 0), seg,
                  pos.astype(jnp.int32))
    last = h[jnp.arange(b), jnp.clip(lengths - 1, 0, l - 1)]
    return head_logits(params, cfg, last)
