"""Operations and bytes of Mamba's selective scan
(`ops/ssm.py::selective_scan`), counted in the recurrent form, whatever
the chunk size and whatever implements it: a later kernel is judged on
the same job.

For one token of one channel with a state of N numbers:

    the decay         dt a, its exponential, s *= it      3 N
    the update        dt x, times b, added to s           2 N + 1
    the read-out      s . c, and d x added                2 N + 2

and the bytes of what the scan is handed and hands back, float32: x and
dt read and y written (one a channel), b and c read (N each a token,
whatever the channels). The state itself stays on the chip in this
count. A backward pass is counted as twice its forward pass, in
operations and in bytes.
"""

from __future__ import annotations


def layer_kinds(c: dict) -> list[str]:
    """The kind of each held layer of a decoder-hybrid-decoder, from the
    published index `share.layer_first` on, of `share.layers_total`."""
    first, total = c["share"]["layer_first"], c["share"]["layers_total"]
    half, kinds = total // 2, []
    for n in range(first, first + c["num_hidden_layers"]):
        if n % c["mb_per_layer"] == 0:
            kinds.append("mamba" if n <= half else "gmu")
        else:
            kinds.append("swa" if n < half else
                         "full" if n == half + 1 else "cross")
    return kinds


def channels(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def cost_per_token(width: int, states: int) -> dict:
    """Forward pass of one token of `width` channels."""
    return {"flops": (7.0 * states + 3.0) * width,
            "bytes": 4.0 * (3 * width + 2 * states)}


def cost(c: dict) -> dict:
    """Forward + backward of one train step's scans, every Mamba layer."""
    one = cost_per_token(channels(c), c["mamba_d_state"])
    units = (c["train"]["seqs_per_step"] * c["train"]["pack_len"]
             * layer_kinds(c).count("mamba"))
    return {k: 3.0 * v * units for k, v in one.items()}


def least_seconds(c: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it) for one step at the chip's peaks."""
    job = cost(c)
    by_flops = job["flops"] / peaks["flops_per_s"]
    by_bytes = job["bytes"] / peaks["bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
