"""Operations of one train step of the encoder as a model of short
convolutions and attention (LFM2's `layer_types`), from its
configuration, layer by layer: what the forward and backward passes
require (a backward pass counted as twice its forward pass), not what
the program spends on recomputation.

A product of [m, k] by [k, n] is 2 m k n operations. A `conv` layer
counts its two projections (hidden -> [B | C | x], and out) and, beside
them, the two gates' products and the taps (`perf/ops/gated_short_conv.py`
has the same count with its bytes); a `full_attention` layer its four
projections and, for each (query, key) pair the masks leave, every query
head's q.k and p.v (the pairs counted from the configuration's shape,
`perf/sequences.py`, the same for every seed: s <= t inside a history).
The feed-forward of a published layer before `num_dense_layers` is the
dense SwiGLU's three matrices of `intermediate_size`; of the others the
router over every expert of the model (`share.experts_total`) and the
expected held share of the picks (tokens x picks x held / experts: 4 x 8
/ 64 = 0.5 held picks a token) of an expert's three matrices of
`moe_intermediate_size`; there is no shared expert. The tied head over
the held vocabulary is counted once; the embedding's gather, the norms
(the two a head among them), RoPE, the sigmoid and the softmaxes are no
matrix products and are not counted.
"""

from __future__ import annotations

from perf.ops.gated_short_conv import layer_kinds
from perf.ops.sambay_step import pairs_per_step


def dense_layers(c: dict) -> int:
    """The held layers whose feed-forward is the dense one."""
    return max(0, min(c["num_hidden_layers"],
                      c["num_dense_layers"] - c["share"]["layer_first"]))


def held_picks_per_token(c: dict) -> float:
    return (c["num_experts_per_tok"] * c["num_experts"]
            / c["share"]["experts_total"])


def mixer_flops_per_token(c: dict, kind: str) -> float:
    """One layer's mixer a token; attention's pairs are a step's."""
    d = c["hidden_size"]
    if kind == "sconv":
        return 2.0 * (d * 3 * d + d * d) + d * (2.0 + 2.0 * c["conv_L_cache"])
    dh = d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    return 2.0 * (d * q + 2 * d * kv + q * d)


def ffn_flops_per_token(c: dict, dense: bool) -> float:
    d = c["hidden_size"]
    if dense:
        return 2.0 * 3 * d * c["intermediate_size"]
    return 2.0 * (d * c["share"]["experts_total"]
                  + 3 * d * c["moe_intermediate_size"]
                  * held_picks_per_token(c))


def pair_flops(c: dict) -> float:
    """One (query, key) pair, every query head: q.k and p.v over d."""
    return 2.0 * (2 * c["hidden_size"])


def forward_flops_by_kind(c: dict) -> dict:
    """One step's forward pass: the two mixers, the two feed-forwards and
    the head."""
    tokens = c["train"]["seqs_per_step"] * c["train"]["pack_len"]
    out = {"sconv": 0.0, "gqa": 0.0, "dense_ffn": 0.0, "experts": 0.0,
           "head": 2.0 * c["hidden_size"] * c["vocab_size"] * tokens}
    for n, kind in enumerate(layer_kinds(c)):
        out[kind] += mixer_flops_per_token(c, kind) * tokens
        if kind == "gqa":
            out[kind] += pairs_per_step(c) * pair_flops(c)
        dense = n < dense_layers(c)
        out["dense_ffn" if dense else "experts"] += (
            ffn_flops_per_token(c, dense) * tokens)
    return out


def forward_flops(c: dict) -> float:
    return sum(forward_flops_by_kind(c).values())


def model_flops(c: dict) -> float:
    """Forward + backward operations of one step."""
    return 3.0 * forward_flops(c)
