"""Operations of one train step of the encoder, from its configuration:
what the forward and backward passes require (a backward pass counted as
twice its forward pass), not what the program spends on recomputation.

A product of [m, k] by [k, n] is 2 m k n operations. Attention counts
the (query, key) pairs a causal mask inside each history leaves: n (n +
1) / 2 for a history of n tokens, over the histories of the
configuration's shape (`perf/sequences.py`; the same for every seed), a
step holding the mean share of them. An expert layer counts the router
over every expert, the shared expert, and for the held experts the
expected share of the picks: tokens x picks x held / experts.
"""

from __future__ import annotations

from perf import sequences


def forward_flops_per_token(c: dict) -> float:
    """Matrix products of one token's forward pass, attention scores
    left out."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    mla = (d * c["q_lora_rank"] + c["q_lora_rank"] * h * (dn + dr)
           + d * (c["kv_lora_rank"] + dr)
           + c["kv_lora_rank"] * h * (dn + dv) + h * dv * d)
    dense = 3 * d * c["intermediate_size"]
    expert = 3 * d * c["moe_intermediate_size"]
    held_share = (c["num_experts_per_tok"] * c["n_routed_experts"]
                  / c["share"]["experts_total"])
    moe = (d * c["share"]["experts_total"]
           + expert * c["n_shared_experts"] + expert * held_share)
    n_dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    n_moe = c["num_hidden_layers"] - n_dense
    mtp = c["num_nextn_predict_layers"]
    head = d * c["vocab_size"]
    total = (n_dense * (mla + dense) + n_moe * (mla + moe)
             + mtp * (2 * d * d + mla + moe + head) + head)
    return 2.0 * total


def attention_pairs_per_step(c: dict) -> float:
    lengths = sequences.history_lengths(c["shape"]).astype(float)
    steps = c["shape"]["n_sequences"] / c["train"]["seqs_per_step"]
    return float((lengths * (lengths + 1) / 2).sum()) / steps


def model_flops(c: dict) -> float:
    """Forward + backward operations of one step."""
    tokens = c["train"]["seqs_per_step"] * c["train"]["pack_len"]
    layers = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    attention = (2.0 * attention_pairs_per_step(c) * c["num_attention_heads"]
                 * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
                    + c["v_head_dim"]) * layers)
    return 3.0 * (forward_flops_per_token(c) * tokens + attention)
