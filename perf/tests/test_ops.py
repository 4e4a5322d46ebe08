"""Operations and bytes against shapes worked by hand."""

from perf.ops import gj_solve


def test_gj_solve_cost_of_one_system_of_order_4():
    # Cholesky 4^3/3 + two triangular solves 2*4^2; A 16 + b 4 + x 4 floats
    c = gj_solve.cost(1, 4)
    assert abs(c["flops"] - (64 / 3 + 32)) < 1e-9
    assert c["bytes"] == 4 * (16 + 8)


def test_gj_solve_batch_of_rank_64_is_bound_by_bytes():
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    c = gj_solve.cost(12664, 64)
    assert c["bytes"] == 12664 * 4 * (4096 + 128)
    assert abs(c["flops"] - 12664 * (64 ** 3 / 3 + 2 * 4096)) < 1e-3
    seconds, bound = gj_solve.least_seconds(12664, 64, peaks)
    assert bound == "bytes"
    assert abs(seconds - c["bytes"] / 819e9) < 1e-12
