"""`correct` for a train cell: rows of the model that the window's last
call returned, against the float64 reference of one half-step.

The last half-step of `als_train` solves the item table from the final
user table, so every item row has to equal the reference's solve of its
own ratings against the user table the call returned. The half-step
before it solved that user table from the item table of the iteration
before, which the driver hands over (a train from the same seed, one
iteration short), so every user row has to equal the reference's solve
against that. Both are compared row by row, at the timed size. Besides:
every user with ratings has a row that was solved (not the zeros it
starts from), and every entry is finite.

Rows are drawn from the seed, most of them among the rows with few
ratings (where a lower precision shows first: the solve divides by
lambda * n), a few among the busiest, and always the busiest row itself,
which the program splits into segments.
"""

from __future__ import annotations

import numpy as np

from perf.reference import als_normal_eq as ref


def sample_rows(degree: np.ndarray, n_low: int, n_high: int, rng):
    """Row ids: n_low among the 60 % of rated rows with fewest ratings,
    n_high among the rest, and the row with most."""
    rated = np.nonzero(degree > 0)[0]
    order = rated[np.argsort(degree[rated], kind="stable")]
    cut = int(0.6 * len(order))
    low = rng.choice(order[:cut], size=min(n_low, cut), replace=False)
    high = rng.choice(order[cut:-1], size=min(n_high, len(order) - cut - 1),
                      replace=False)
    return np.unique(np.concatenate([low, high, order[-1:]]))


def row_errors(solved, opposing, rows, row_idx, col_idx, vals, spec):
    """Relative error of each of `rows` of `solved` against the
    reference's solve from `opposing`: max |x - ref| / max |ref|."""
    pick = np.nonzero(np.isin(row_idx, rows))[0]
    order = pick[np.argsort(row_idx[pick], kind="stable")]
    r_sorted = row_idx[order]
    starts = np.searchsorted(r_sorted, rows)
    ends = np.searchsorted(r_sorted, rows, side="right")
    implicit = bool(spec["implicit"])
    yty = ref.gram(opposing) if implicit else None
    errs = np.empty(len(rows))
    for n, (j, s, e) in enumerate(zip(rows, starts, ends)):
        sel = order[s:e]
        want = ref.solve_row(opposing, col_idx[sel], vals[sel],
                             float(spec["reg"]), implicit,
                             float(spec["alpha"]), yty)
        errs[n] = (np.abs(solved[j].astype(np.float64) - want).max()
                   / max(np.abs(want).max(), 1e-30))
    return errs


def run(spec: dict, data: dict, model, item_factors_before,
        seed: int) -> list[dict]:
    """The numbers compared, each `{"name", "value", "limit"}`; a limit
    of None marks a number that is printed and not held."""
    user_idx, item_idx, vals = (data["user_idx"], data["item_idx"],
                                data["values"])
    U = np.asarray(model.user_factors)
    V = np.asarray(model.item_factors)
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    deg_u = np.bincount(user_idx, minlength=U.shape[0])
    deg_i = np.bincount(item_idx, minlength=V.shape[0])
    n_low, n_high = int(spec["rows_low"]), int(spec["rows_high"])
    items = sample_rows(deg_i, n_low, n_high, rng)
    users = sample_rows(deg_u, n_low, n_high, rng)
    e_items = row_errors(V, U, items, item_idx, user_idx, vals, spec)
    e_users = row_errors(U, np.asarray(item_factors_before), users,
                         user_idx, item_idx, vals, spec)
    unsolved = int(((deg_u > 0) & ~np.any(U != 0, axis=1)).sum())
    nonfinite = int((~np.isfinite(U)).sum() + (~np.isfinite(V)).sum())
    limits = spec["limits"]
    numbers = [
        ("item_rows_median_rel_err", float(np.median(e_items))),
        ("item_rows_max_rel_err", float(e_items.max())),
        ("user_rows_median_rel_err", float(np.median(e_users))),
        ("user_rows_max_rel_err", float(e_users.max())),
        ("user_rows_never_solved", unsolved),
        ("nonfinite_entries", nonfinite),
    ]
    return [{"name": n, "value": v, "limit": limits.get(n)}
            for n, v in numbers]
