"""Operations of one train step of the encoder as a model that routes
before it mixes (SmallThinker's block), from its configuration, layer by
layer: what the forward and backward passes require (a backward pass
counted as twice its forward pass), not what the program spends on
recomputation.

A product of [m, k] by [k, n] is 2 m k n operations. Every layer counts
attention's four projections (queries `num_attention_heads` x
`head_dim` wide, keys and values `num_key_value_heads` x `head_dim`),
the router over every expert of the model (`share.experts_total`: 64)
and, for the held experts, the expected share of the picks: tokens x
picks x held / experts (6 x 16 / 64 = 1.5 held picks a token) of an
expert's three matrices; and for each (query, key) pair the masks leave,
every query head's q.k and p.v. The pairs are counted from the
configuration's shape (`perf/sequences.py`; the same for every seed): s
<= t inside a history, and in a layer whose `sliding_window_layout` is 1
t - s < `sliding_window_size` too. The head over the held vocabulary is
counted once; the embedding's gather, RoPE, the norms and the softmaxes
are no matrix products and are not counted.
"""

from __future__ import annotations

from perf.ops.sambay_step import pairs_per_step


def windowed_layers(c: dict) -> list[bool]:
    """The held layers' entries of `sliding_window_layout`: the layout
    whole and sliced from `share.layer_first`, or the held slice alone."""
    layout, held = c["sliding_window_layout"], c["num_hidden_layers"]
    first = c["share"].get("layer_first", 0) if len(layout) > held else 0
    return [bool(v) for v in layout[first:first + held]]


def held_picks_per_token(c: dict) -> float:
    return (c["moe_num_active_primary_experts"] * c["moe_num_primary_experts"]
            / c["share"]["experts_total"])


def layer_flops_per_token(c: dict) -> float:
    """Projections, the router and the held experts' expected share;
    attention pairs are a step's."""
    d, dh = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    attention = d * q + 2 * d * kv + q * d
    router = d * c["share"]["experts_total"]
    experts = 3 * d * c["moe_ffn_hidden_size"] * held_picks_per_token(c)
    return 2.0 * (attention + router + experts)


def pair_flops(c: dict) -> float:
    """One (query, key) pair, every query head: q.k and p.v over d."""
    return c["num_attention_heads"] * 2.0 * (2 * c["head_dim"])


def pairs(c: dict) -> float:
    """(query, key) pairs of one step, all held layers."""
    return sum(pairs_per_step(c, c["sliding_window_size"] if windowed
                              else None) for windowed in windowed_layers(c))


def forward_flops(c: dict) -> float:
    """One step's forward pass."""
    tokens = c["train"]["seqs_per_step"] * c["train"]["pack_len"]
    per_token = (c["num_hidden_layers"] * layer_flops_per_token(c)
                 + 2.0 * c["hidden_size"] * c["vocab_size"])
    return per_token * tokens + pairs(c) * pair_flops(c)


def model_flops(c: dict) -> float:
    """Forward + backward operations of one step."""
    return 3.0 * forward_flops(c)
