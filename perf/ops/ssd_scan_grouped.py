"""Operations and bytes of Mamba-2's scan with several B/C groups
(`ops/ssd.py::ssd_scan` given b, c [B, L, G, N]), counted in the
recurrent form, whatever the chunk size and whatever implements it: a
later kernel is judged on the same job.

For one token of one head the decay, the update and the read-out of its
P x N state are `perf/ops/ssd_scan.py`'s (a head reads one group's B and
C, so the operations do not know the groups). The bytes of what the scan
is handed and hands back, float32: x read and y written (P each a head),
dt read (one a head), B and C read, N each a GROUP a token: eight
groups' rows where one group has one. The state itself stays on the
chip in this count. A backward pass is counted as twice its forward
pass, in operations and in bytes.
"""

from __future__ import annotations

from perf.ops import ssd_scan

_LETTERS = {"M": "ssd", "E": "experts", "*": "gqa"}


def layer_kinds(c: dict) -> list[str]:
    """The kind of each held layer: `num_hidden_layers` letters of the
    published `hybrid_override_pattern` from `share.layer_first` on."""
    first = c["share"]["layer_first"]
    return [_LETTERS[letter] for letter in
            c["hybrid_override_pattern"][first:first + c["num_hidden_layers"]]]


def cost_per_token(heads: int, width: int, states: int, groups: int) -> dict:
    """Forward pass of one token of `heads` heads of `width` channels
    over `groups` groups of B and C."""
    one = ssd_scan.cost_per_token(heads, width, states)
    return {"flops": one["flops"],
            "bytes": 4.0 * (2 * heads * width + heads + 2 * groups * states)}


def cost(c: dict) -> dict:
    """Forward + backward of one train step's scans, every Mamba-2 layer."""
    one = cost_per_token(c["mamba_num_heads"], c["mamba_head_dim"],
                         c["ssm_state_size"], c["n_groups"])
    units = (c["train"]["seqs_per_step"] * c["train"]["pack_len"]
             * layer_kinds(c).count("ssd"))
    return {k: 3.0 * v * units for k, v in one.items()}


def least_seconds(c: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it) for one step at the chip's peaks."""
    job = cost(c)
    by_flops = job["flops"] / peaks["flops_per_s"]
    by_bytes = job["bytes"] / peaks["bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
