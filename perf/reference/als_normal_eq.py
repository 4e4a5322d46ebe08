"""The plain reference of one ALS half-step: a row's normal equations in
float64, numpy only.

Copied in PR 23 from `predictionio_tpu/quality/mllib_als.py::solve_one_row`
(MLlib's semantics: ALS-WR regularisation lambda * n, Hu-Koren-Volinsky
confidence for implicit feedback). The program's file is no longer its
source: the program may change, this yardstick may not.

Explicit:  A = sum_c y_c y_c^T + lambda * n * I,        b = sum_c v_c y_c
Implicit:  A = Y^T Y + sum_c a|v_c| y_c y_c^T + lambda * n+ * I,
           b = sum_{c: v_c > 0} (1 + a|v_c|) y_c
A pair that comes up twice is two entries, summed like any others.
"""

from __future__ import annotations

import numpy as np


def gram(Y: np.ndarray) -> np.ndarray:
    """Y^T Y in float64: the implicit model's term over every row of the
    opposing table, computed once for a batch of rows."""
    Y64 = Y.astype(np.float64)
    return Y64.T @ Y64


def solve_row(Y: np.ndarray, cols: np.ndarray, vals: np.ndarray, reg: float,
              implicit: bool = False, alpha: float = 1.0,
              YtY: np.ndarray | None = None) -> np.ndarray:
    """The row of factors that the entries (cols, vals) give against the
    opposing table Y, float64."""
    Yr = Y[cols].astype(np.float64)
    v = np.asarray(vals, np.float64)
    k = Y.shape[1]
    if implicit:
        c1 = alpha * np.abs(v)
        A = (gram(Y) if YtY is None else YtY) + (Yr * c1[:, None]).T @ Yr
        b = ((1.0 + c1) * (v > 0)) @ Yr
        n = int((v > 0).sum())
    else:
        A = Yr.T @ Yr
        b = v @ Yr
        n = len(v)
    A = A + (reg * n) * np.eye(k)
    L = np.linalg.cholesky(A)
    return np.linalg.solve(L.T, np.linalg.solve(L, b))
