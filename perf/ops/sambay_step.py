"""Operations of one train step of the encoder as a
decoder-hybrid-decoder, from its configuration, layer by layer: what
the forward and backward passes require (a backward pass counted as
twice its forward pass), not what the program spends on recomputation.

A product of [m, k] by [k, n] is 2 m k n operations. A Mamba layer
counts its four projections, its convolution's taps and the scan in the
recurrent form (`perf/ops/selective_scan.py`); a gated memory unit its
two products; a differential-attention layer its projections (a cross
layer: the query's and the output's alone) and, for each (query, key)
pair the masks leave, the two softmaxes of each of its pairs of heads:
every query head's q.k and p.V_g, V_g two heads wide. The pairs are
counted from the configuration's shape (`perf/sequences.py`; the same
for every seed): s <= t inside a history, and in a windowed layer t - s
< sliding_window too. The tied head is counted once.
"""

from __future__ import annotations

import numpy as np

from perf import sequences
from perf.ops import selective_scan


def pairs_per_step(c: dict, window: int | None = None) -> float:
    """(query, key) pairs of one step, a step holding the mean share of
    the histories: a query at offset t of its history sees min(t + 1,
    window) keys."""
    lengths = sequences.history_lengths(c["shape"])
    steps = c["shape"]["n_sequences"] / c["train"]["seqs_per_step"]
    total = 0.0
    for n in lengths.tolist():
        seen = np.arange(1, n + 1)
        total += float((seen if window is None
                        else np.minimum(seen, window)).sum())
    return total / steps


def mixer_flops_per_token(c: dict, kind: str) -> float:
    """Projections, taps and the scan; attention pairs are a step's."""
    d, di = c["hidden_size"], selective_scan.channels(c)
    dh = d // c["num_attention_heads"]
    if kind == "mamba":
        n, rank = c["mamba_d_state"], c["mamba_dt_rank"]
        products = d * 2 * di + di * (rank + 2 * n) + rank * di + di * d
        taps = c["mamba_d_conv"] * di
        return (2.0 * (products + taps)
                + selective_scan.cost_per_token(di, n)["flops"])
    if kind == "gmu":
        return 2.0 * (d * di + di * d)
    if kind == "cross":
        return 2.0 * (d * d + d * d)
    return 2.0 * (d * (d + 2 * c["num_key_value_heads"] * dh) + d * d)


def pair_flops(c: dict) -> float:
    """One (query, key) pair, every query head: q.k over d and p.V over
    the two heads of a value."""
    dh = c["hidden_size"] // c["num_attention_heads"]
    return c["num_attention_heads"] * 2.0 * (dh + 2 * dh)


def forward_flops(c: dict) -> float:
    """One step's forward pass."""
    tokens = c["train"]["seqs_per_step"] * c["train"]["pack_len"]
    per_token = 2.0 * c["hidden_size"] * c["vocab_size"]
    pairs = 0.0
    for kind in selective_scan.layer_kinds(c):
        per_token += (mixer_flops_per_token(c, kind)
                      + 2.0 * 3 * c["hidden_size"] * c["intermediate_size"])
        if kind == "swa":
            pairs += pairs_per_step(c, c["sliding_window"])
        elif kind in ("full", "cross"):
            pairs += pairs_per_step(c)
    return per_token * tokens + pairs * pair_flops(c)


def model_flops(c: dict) -> float:
    """Forward + backward operations of one step."""
    return 3.0 * forward_flops(c)
