"""Operations of one train step of the encoder with two kinds of token
mixer, from its configuration, layer by layer: what the forward and
backward passes require (a backward pass counted as twice its forward
pass), not what the program spends on recomputation.

A product of [m, k] by [k, n] is 2 m k n operations. A KDA layer counts
its projections, its convolutions' taps and the scan in the recurrent
form (`perf/ops/kda_scan.py`); an MLA layer its projections (a direct
query where `q_lora_rank` is null) and the (query, key) pairs a causal
mask inside each history leaves (`perf/ops/encoder_step.py` counts
them from the configuration's shape); an expert layer the router over
every expert, the shared experts and, for the held experts, the
expected share of the picks: tokens x picks x held / experts.
"""

from __future__ import annotations

from perf.ops import kda_scan
from perf.ops.encoder_step import attention_pairs_per_step


def kda_flops_per_token(c: dict) -> float:
    lin = c["linear_attn_config"]
    d, dh = c["hidden_size"], lin["head_dim"]
    wide = lin["num_heads"] * dh
    products = (3 * d * wide + d * dh + dh * wide + d * lin["num_heads"]
                + d * dh + dh * wide + wide * d)
    conv = 3 * lin["short_conv_kernel_size"] * wide
    scan = kda_scan.cost_per_token_head(dh, dh)["flops"] * lin["num_heads"]
    return 2.0 * (products + conv) + scan


def mla_flops_per_token(c: dict) -> float:
    """Projections only; the pairs are counted a step."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rank = c.get("q_lora_rank")
    query = d * rank + rank * h * (dn + dr) if rank else d * h * (dn + dr)
    return 2.0 * (query + d * (c["kv_lora_rank"] + dr)
                  + c["kv_lora_rank"] * h * (dn + dv) + h * dv * d)


def ffn_flops_per_token(c: dict, dense: bool) -> float:
    d = c["hidden_size"]
    if dense:
        return 2.0 * 3 * d * c["intermediate_size"]
    expert = 3 * d * c["moe_intermediate_size"]
    held_share = (c["num_experts_per_token"] * c["num_experts"]
                  / c["share"]["experts_total"])
    return 2.0 * (d * c["share"]["experts_total"]
                  + expert * c["num_shared_experts"] + expert * held_share)


def forward_flops_per_token(c: dict) -> float:
    """One token's forward pass, attention pairs left out."""
    mixer = {"kda": kda_flops_per_token(c), "mla": mla_flops_per_token(c)}
    total = 2.0 * c["hidden_size"] * c["vocab_size"]
    for n, kind in enumerate(kda_scan.layer_kinds(c)):
        total += mixer[kind] + ffn_flops_per_token(
            c, n < c["first_k_dense_replace"])
    return total


def model_flops(c: dict) -> float:
    """Forward + backward operations of one step."""
    tokens = c["train"]["seqs_per_step"] * c["train"]["pack_len"]
    attention = (2.0 * attention_pairs_per_step(c) * c["num_attention_heads"]
                 * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
                    + c["v_head_dim"]) * kda_scan.layer_kinds(c).count("mla"))
    return 3.0 * (forward_flops_per_token(c) * tokens + attention)
