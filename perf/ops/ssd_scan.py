"""Operations and bytes of Mamba-2's scan (`ops/ssd.py::ssd_scan`),
counted in the recurrent form, whatever the chunk size and whatever
implements it: a later kernel is judged on the same job.

For one token of one head of P channels with a state of P x N numbers:

    the decay         dt a, its exponential, S *= it      P N + 2
    the update        dt x, times B, added to S           2 P N + P
    the read-out      S C, and d x added                  2 P N + 2 P

and the bytes of what the scan is handed and hands back, float32: x
read and y written (P each a head), dt read (one a head), B and C read
(N each a token, whatever the heads). The state itself stays on the
chip in this count. A backward pass is counted as twice its forward
pass, in operations and in bytes.
"""

from __future__ import annotations

_KINDS = {"mamba": "ssd", "attention": "gqa"}


def layer_kinds(c: dict) -> list[str]:
    """The kind of each held layer: `num_hidden_layers` entries of the
    published `layer_types` from `share.layer_first` on."""
    first = c["share"]["layer_first"]
    return [_KINDS[t] for t in
            c["layer_types"][first:first + c["num_hidden_layers"]]]


def cost_per_token(heads: int, width: int, states: int) -> dict:
    """Forward pass of one token of `heads` heads of `width` channels."""
    return {"flops": heads * (5.0 * width * states + 3.0 * width + 2.0),
            "bytes": 4.0 * (2 * heads * width + heads + 2 * states)}


def cost(c: dict) -> dict:
    """Forward + backward of one train step's scans, every Mamba-2 layer."""
    one = cost_per_token(c["mamba_n_heads"], c["mamba_d_head"],
                         c["mamba_d_state"])
    units = (c["train"]["seqs_per_step"] * c["train"]["pack_len"]
             * layer_kinds(c).count("ssd"))
    return {k: 3.0 * v * units for k, v in one.items()}


def least_seconds(c: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it) for one step at the chip's peaks."""
    job = cost(c)
    by_flops = job["flops"] / peaks["flops_per_s"]
    by_bytes = job["bytes"] / peaks["bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
