"""Operations of one train step of the encoder as a Mamba-2 hybrid, from
its configuration, layer by layer: what the forward and backward passes
require (a backward pass counted as twice its forward pass), not what
the program spends on recomputation.

A product of [m, k] by [k, n] is 2 m k n operations. A Mamba-2 layer
counts its two projections, its convolution's taps over x, B and C and
the scan in the recurrent form (`perf/ops/ssd_scan.py`); an attention
layer its four projections and, for each (query, key) pair the masks
leave, every query head's q.k and p.v. The pairs are counted from the
configuration's shape (`perf/sequences.py`; the same for every seed):
s <= t inside a history. Every layer has the shared feed-forward; the
tied head is counted once.
"""

from __future__ import annotations

from perf.ops import ssd_scan
from perf.ops.sambay_step import pairs_per_step


def mixer_flops_per_token(c: dict, kind: str) -> float:
    """Projections, taps and the scan; attention pairs are a step's."""
    d = c["hidden_size"]
    if kind == "ssd":
        h, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
        di = h * p
        products = d * (2 * di + 2 * n + h) + di * d
        taps = c["mamba_d_conv"] * (di + 2 * n)
        return (2.0 * (products + taps)
                + ssd_scan.cost_per_token(h, p, n)["flops"])
    dh = d // c["num_attention_heads"]
    return 2.0 * (d * (d + 2 * c["num_key_value_heads"] * dh) + d * d)


def pair_flops(c: dict) -> float:
    """One (query, key) pair, every query head: q.k and p.v over d."""
    dh = c["hidden_size"] // c["num_attention_heads"]
    return c["num_attention_heads"] * 2.0 * (dh + dh)


def forward_flops(c: dict) -> float:
    """One step's forward pass."""
    tokens = c["train"]["seqs_per_step"] * c["train"]["pack_len"]
    per_token = 2.0 * c["hidden_size"] * c["vocab_size"]
    pairs = 0.0
    for kind in ssd_scan.layer_kinds(c):
        per_token += (mixer_flops_per_token(c, kind) + 2.0 * 3
                      * c["hidden_size"] * c["shared_intermediate_size"])
        if kind == "gqa":
            pairs += pairs_per_step(c)
    return per_token * tokens + pairs * pair_flops(c)


def model_flops(c: dict) -> float:
    """Forward + backward operations of one step."""
    return 3.0 * forward_flops(c)
