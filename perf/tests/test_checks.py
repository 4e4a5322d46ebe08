"""`perf/checks/als_rows.py` on tables made by the reference itself: a
sound pair of half-steps passes, and a lower precision confined to the
user half-step, which leaves the item table an exact solve of the user
table it got, is seen on the user rows and nowhere else."""

import types

import ml_dtypes
import numpy as np
import pytest

from perf import data
from perf.checks import als_rows
from perf.reference import als_normal_eq as ref
from perf.tests.conftest import load


def solve_table(opposing, n_rows, row_idx, col_idx, vals, reg):
    order = np.argsort(row_idx, kind="stable")
    ends = np.cumsum(np.bincount(row_idx, minlength=n_rows))
    out = np.zeros((n_rows, opposing.shape[1]), np.float32)
    for r in range(n_rows):
        sel = order[ends[r - 1] if r else 0:ends[r]]
        if len(sel):
            out[r] = ref.solve_row(opposing, col_idx[sel], vals[sel], reg)
    return out


@pytest.fixture(scope="module")
def half_steps():
    config = load("perf", "tests", "tiny", "als64_ml20m.json")
    u, i, v = data.make_ratings(config["shape"], 2 ** 31 + 23)
    n_users, n_items = data.table_heights(config["shape"])
    rank, reg = config["algorithm_params"]["rank"], config["check"]["reg"]
    before = (np.random.default_rng(1).standard_normal((n_items, rank))
              / np.sqrt(rank)).astype(np.float32)

    def model(items_seen_by_the_user_step):
        users = solve_table(items_seen_by_the_user_step, n_users, u, i, v, reg)
        items = solve_table(users, n_items, i, u, v, reg)
        return types.SimpleNamespace(user_factors=users, item_factors=items)

    def numbers(m):
        got = als_rows.run(config["check"],
                           {"user_idx": u, "item_idx": i, "values": v},
                           m, before, 2 ** 31 + 23)
        return {n["name"]: n["value"] <= n["limit"] for n in got}

    return before, model, numbers


def test_sound_half_steps_pass_every_number(half_steps):
    before, model, numbers = half_steps
    assert all(numbers(model(before)).values())


def test_a_lower_precision_in_the_user_half_step_alone_fails_the_user_rows(
        half_steps):
    before, model, numbers = half_steps
    rounded = before.astype(ml_dtypes.bfloat16).astype(np.float32)
    ok = numbers(model(rounded))
    assert not ok["user_rows_median_rel_err"]
    assert ok["item_rows_median_rel_err"] and ok["item_rows_max_rel_err"]
