"""Operations of one train step of the encoder as a model whose layers
are one sublayer each (Nemotron-H's pattern), from its configuration,
layer by layer: what the forward and backward passes require (a backward
pass counted as twice its forward pass), not what the program spends on
recomputation.

A product of [m, k] by [k, n] is 2 m k n operations. An `M` layer counts
its two projections (in: hidden -> z, x, G groups of B and of C, dt; out),
its convolution's taps over x, B and C and the scan in the recurrent form
(`perf/ops/ssd_scan_grouped.py`); a `*` layer its four projections and,
for each (query, key) pair the masks leave, every query head's q.k and
p.v (the pairs counted from the configuration's shape,
`perf/sequences.py`, the same for every seed: s <= t inside a history);
an `E` layer the router over every expert of the model
(`share.experts_total`), the expected held share of the picks (tokens x
picks x held / experts: 6 x 8 / 128 = 0.375 held picks a token) of an
expert's two matrices, and the shared expert's two of its own width.
The untied head over the held vocabulary is counted once; the
embedding's gather, the norms, the gate, the sigmoid and the softmaxes
are no matrix products and are not counted.
"""

from __future__ import annotations

from perf.ops import ssd_scan_grouped
from perf.ops.sambay_step import pairs_per_step


def held_picks_per_token(c: dict) -> float:
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["share"]["experts_total"])


def layer_flops_per_token(c: dict, kind: str) -> float:
    """One layer's products (and an `M` layer's taps and scan) a token;
    attention's pairs are a step's."""
    d = c["hidden_size"]
    if kind == "ssd":
        h, p, n = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
        di, bc = h * p, 2 * c["n_groups"] * n
        products = d * (2 * di + bc + h) + di * d
        taps = c["conv_kernel"] * (di + bc)
        return (2.0 * (products + taps) + ssd_scan_grouped.cost_per_token(
            h, p, n, c["n_groups"])["flops"])
    if kind == "gqa":
        q = c["num_attention_heads"] * c["head_dim"]
        kv = c["num_key_value_heads"] * c["head_dim"]
        return 2.0 * (d * q + 2 * d * kv + q * d)
    router = d * c["share"]["experts_total"]
    experts = 2 * d * c["moe_intermediate_size"] * held_picks_per_token(c)
    shared = (2 * d * c["moe_shared_expert_intermediate_size"]
              * c["n_shared_experts"])
    return 2.0 * (router + experts + shared)


def pair_flops(c: dict) -> float:
    """One (query, key) pair, every query head: q.k and p.v over d."""
    return c["num_attention_heads"] * 2.0 * (2 * c["head_dim"])


def forward_flops_by_kind(c: dict) -> dict:
    """One step's forward pass, by layer kind and the head."""
    tokens = c["train"]["seqs_per_step"] * c["train"]["pack_len"]
    out = {"ssd": 0.0, "gqa": 0.0, "experts": 0.0,
           "head": 2.0 * c["hidden_size"] * c["vocab_size"] * tokens}
    for kind in ssd_scan_grouped.layer_kinds(c):
        out[kind] += layer_flops_per_token(c, kind) * tokens
        if kind == "gqa":
            out[kind] += pairs_per_step(c) * pair_flops(c)
    return out


def forward_flops(c: dict) -> float:
    return sum(forward_flops_by_kind(c).values())


def model_flops(c: dict) -> float:
    """Forward + backward operations of one step."""
    return 3.0 * forward_flops(c)
