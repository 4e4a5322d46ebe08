"""Operations and bytes of the doubly gated short convolution's middle
(`models/encoder.py::short_conv` between its two projections: `v = B *
x`, the causal depthwise convolution of `conv_L_cache` taps over v, `y
= C * c`), whatever implements it: a later kernel that takes both gates
in is judged on the same job.

For one token and one channel, forward: one product for the first gate,
a multiply and an add a tap, one product for the second gate. The bytes,
float32, of what the part is handed and hands back and nothing between:
[B | C | x] read once (three values) and C * c written once (one); the
taps and the histories' ids are small beside them and not counted. The
backward pass reads [B | C | x] and the cotangent of y and writes the
cotangent of [B | C | x]: 3 + 1 read, 3 written, and about twice the
forward pass's operations (the sum recomputed, the taps' pullback, two
products a gate). v, c and their cotangents stay on the chip in this
count, which is what makes it the job of one pass and not of the five
passes the program makes today (gate, convolution, gate, each with its
own reads and writes).
"""

from __future__ import annotations

_KINDS = {"conv": "sconv", "full_attention": "gqa"}


def layer_kinds(c: dict) -> list[str]:
    """The kind of each held layer: `num_hidden_layers` entries of the
    published `layer_types` from `share.layer_first` on."""
    first = c["share"]["layer_first"]
    return [_KINDS[t] for t in
            c["layer_types"][first:first + c["num_hidden_layers"]]]


def cost_per_token(channels: int, taps: int) -> dict:
    """Forward + backward of one token of `channels` channels."""
    forward = channels * (2.0 + 2.0 * taps)
    return {"flops": 3.0 * forward,
            "bytes": 4.0 * channels * ((3 + 1) + (3 + 1 + 3))}


def cost(c: dict) -> dict:
    """Forward + backward of one train step's gated convolutions, every
    `conv` layer (recomputation not counted)."""
    one = cost_per_token(c["hidden_size"], c["conv_L_cache"])
    units = (c["train"]["seqs_per_step"] * c["train"]["pack_len"]
             * layer_kinds(c).count("sconv"))
    return {k: v * units for k, v in one.items()}


def least_seconds(c: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it) for one step at the chip's peaks."""
    job = cost(c)
    by_flops = job["flops"] / peaks["flops_per_s"]
    by_bytes = job["bytes"] / peaks["bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
