"""Operations and bytes of the Kimi Delta Attention scan
(`ops/kda.py::kda_scan`), counted in the recurrent form, whatever the
chunk size and whatever implements it: a later kernel is judged on the
same job.

For one token of one head with a state of d_k x d_v:

    the decay         Diag(a) S                d_k d_v
    k^T S             the state read by k      2 d_k d_v
    the update        S += k u^T (u: d_v)      2 d_k d_v + 2 d_v
    S^T q             the output               2 d_k d_v

and the bytes of what the scan is handed and hands back, float32: q, k
and log a (d_k each), v (d_v) and b read, o (d_v) written. The state
itself stays on the chip in this count. A backward pass is counted as
twice its forward pass, in operations and in bytes.
"""

from __future__ import annotations


def layer_kinds(c: dict) -> list[str]:
    """The mixer of each held layer; the config numbers layers from 1."""
    kda = set(c["linear_attn_config"]["kda_layers"])
    return ["kda" if n in kda else "mla"
            for n in range(1, c["num_hidden_layers"] + 1)]


def cost_per_token_head(d_k: int, d_v: int) -> dict:
    """Forward pass of one token of one head."""
    return {"flops": 7.0 * d_k * d_v + 2.0 * d_v,
            "bytes": 4.0 * (3 * d_k + 2 * d_v + 1)}


def cost(c: dict) -> dict:
    """Forward + backward of one train step's scans, every KDA layer."""
    lin = c["linear_attn_config"]
    one = cost_per_token_head(lin["head_dim"], lin["head_dim"])
    units = (c["train"]["seqs_per_step"] * c["train"]["pack_len"]
             * lin["num_heads"] * layer_kinds(c).count("kda"))
    return {k: 3.0 * v * units for k, v in one.items()}


def least_seconds(c: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it) for one step at the chip's peaks."""
    job = cost(c)
    by_flops = job["flops"] / peaks["flops_per_s"]
    by_bytes = job["bytes"] / peaks["bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
