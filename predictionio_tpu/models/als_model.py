"""ALSModel — trained factor matrices + id mappings, with serving helpers.

Parity with the Recommendation template's «ALSModel extends PersistentModel»
and the Similar-Product template's collected feature map (SURVEY.md §2.4
[U]). Factors live as numpy on the host for low-latency single-query
serving; bulk paths go through the jitted scorer in ops.ranking.

Exception: grid-eval models (ALSAlgorithm.train_grid, host_factors=False)
carry DEVICE-resident jax factor arrays — ops.ranking routes them down its
device branch, `similar_products` coerces to host, and such models are
eval-scoped: never pickled into the blob store (Engine.eval discards them
after batch_predict).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from predictionio_tpu import native
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.ops import ranking
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.telemetry.spans import record as record_span

# Which way each seen-items map was built. A train whose build reads
# `numpy` fell back: no toolchain, PIO_NATIVE=0, or a user row outside
# [0, n_users), which the loader declines.
SEEN_ITEMS_BUILDS = REGISTRY.counter(
    "model_seen_items_builds_total",
    "SeenItems maps built, by the path that grouped the entries by user "
    "row (native | numpy)",
    labelnames=("path",))


class SeenItems:
    """CSR map of user row → seen item rows, with the dict-ish `.get`
    surface `recommend_products` uses. Built from the training COO by
    grouping the entries by user row in their given order: one counting
    pass in the native loader (`native.group_rows_native`: histogram,
    prefix sum, scatter), or where that is absent or declines, the stable
    argsort + searchsorted of `_group_rows_numpy`, which defines the
    result (the native path's arrays equal it bit for bit) — the per-event
    Python dict loop both replace dominated model-build time at 2M+ events
    (VERDICT r1 #4), the argsort a third of a 20M-event train call.
    Pickles as two arrays, so blob-store persistence stays cheap."""

    def __init__(self, user_idx: np.ndarray, item_idx: np.ndarray,
                 n_users: int):
        t0 = time.monotonic()
        grouped = native.group_rows_native(user_idx, item_idx, n_users)
        if grouped is not None:
            path = "native"
            self._items, self._indptr = grouped
        else:
            path = "numpy"
            self._group_rows_numpy(user_idx, item_idx, n_users)
        SEEN_ITEMS_BUILDS.labels(path=path).inc()
        record_span(f"model.seen_items.{path}", time.monotonic() - t0)

    def _group_rows_numpy(self, user_idx, item_idx, n_users: int) -> None:
        order = np.argsort(user_idx, kind="stable")
        self._items = np.ascontiguousarray(
            np.asarray(item_idx)[order], dtype=np.int32)
        su = np.asarray(user_idx)[order]
        self._indptr = np.searchsorted(
            su, np.arange(n_users + 1)).astype(np.int64)

    def get(self, user_row: int, default=None) -> Optional[np.ndarray]:
        if not 0 <= user_row < len(self._indptr) - 1:
            return default
        lo, hi = self._indptr[user_row], self._indptr[user_row + 1]
        if hi <= lo:
            return default
        return self._items[lo:hi]

    def __len__(self) -> int:
        return int(self._items.shape[0])


@dataclasses.dataclass
class ALSModel:
    user_factors: np.ndarray  # [n_users, K]
    item_factors: np.ndarray  # [n_items, K]
    user_ids: BiMap  # user id string → row
    item_ids: BiMap  # item id string → row
    # user row → seen item rows: a SeenItems CSR (or a plain dict — both
    # expose .get and truthiness)
    seen: Optional["SeenItems | dict[int, np.ndarray]"] = None
    rmse_history: list = dataclasses.field(default_factory=list)

    def recommend_products(
        self, user: str, num: int, exclude_seen: bool = True
    ) -> list[tuple[str, float]]:
        """Top-num (item id, score) for a user («recommendProducts» [U]).
        Unknown user → empty list (the reference's template behavior)."""
        return self.recommend_products_batch([user], num, exclude_seen)[0]

    def recommend_products_batch(
        self, users: list, num: int, exclude_seen: bool = True
    ) -> list[list[tuple[str, float]]]:
        """Top-num recommendations for MANY users in one scoring call —
        the bulk path `pio batchpredict` rides. Past
        `ranking.SERVE_HOST_MAX_BATCH` users this takes the accelerator
        branch of `recommend_topk` (one [B, n_items] device dispatch)
        instead of B host matvecs; unknown users get []."""
        out: list[list[tuple[str, float]]] = [[] for _ in users]
        known = [(pos, row) for pos, row in
                 ((pos, self.user_ids.get(str(u))) for pos, u in
                  enumerate(users)) if row is not None]
        if not known or num <= 0:
            return out
        ids = np.asarray([row for _, row in known], dtype=np.int32)
        exclude = None
        if exclude_seen and self.seen:
            exclude = {int(row): self.seen.get(int(row),
                                               np.empty(0, np.int32))
                       for row in set(ids.tolist())}
        scores, idx = ranking.recommend_topk(
            self.user_factors, self.item_factors, ids, num, exclude)
        inv = self.item_ids.inverse()
        for (pos, _), s_row, i_row in zip(known, scores, idx):
            out[pos] = [(inv[int(i)], float(s))
                        for s, i in zip(s_row, i_row) if np.isfinite(s)]
        return out

    def similar_products(
        self, items: list[str], num: int, exclude_self: bool = True
    ) -> list[tuple[str, float]]:
        """Item-item cosine on item factors — the Similar-Product template's
        predict path («ALSModel(productFeatures.collectAsMap)» [U]).
        Multiple query items → average of their unit vectors."""
        rows = [self.item_ids.get(i) for i in items]
        rows = [r for r in rows if r is not None]
        if not rows:
            return []
        # device-resident factors (grid eval): one host pull — the math
        # below mutates `sims` in place, which jax arrays can't
        item_factors = np.asarray(self.item_factors)
        v = item_factors[rows]
        v = v / np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-9)
        q = v.mean(axis=0)
        norms = np.maximum(np.linalg.norm(item_factors, axis=1), 1e-9)
        sims = (item_factors @ q) / norms
        if exclude_self:
            sims[rows] = -np.inf
        top = np.argsort(-sims)[:num]
        inv = self.item_ids.inverse()
        return [(inv[int(i)], float(sims[i])) for i in top if np.isfinite(sims[i])]

    # numpy arrays + BiMaps pickle cleanly, so the default blob-store
    # persistence (Engine.serialize_models) works for trained models.
    # Grid-EVAL models are the exception (device-resident factors, see
    # module docstring) and are never routed into persistence.
