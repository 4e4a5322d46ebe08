"""Operations and bytes of the batched normal-equation solve
(`ops/pallas_solve.py::gj_solve`): R symmetric positive definite systems
of order K with one right-hand side each, float32.

Counted is what the problem needs, not what a kernel happens to do: a
Cholesky factorisation (K^3 / 3 operations) and two triangular solves
(2 K^2), reading A and b once and writing x once. At K = 64 that is 5.7
operations a byte, far under a v5e's ridge of 240: the bound is bytes.
"""


def cost(rows: int, k: int) -> dict:
    flops = rows * (k ** 3 / 3.0 + 2.0 * k ** 2)
    nbytes = rows * 4.0 * (k * k + 2 * k)
    return {"flops": flops, "bytes": nbytes}


def least_seconds(rows: int, k: int, peaks: dict) -> tuple[float, str]:
    """(seconds, which peak bounds it) for one batch at the chip's peaks."""
    c = cost(rows, k)
    by_flops = c["flops"] / peaks["flops_per_s"]
    by_bytes = c["bytes"] / peaks["bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
