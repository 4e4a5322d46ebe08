"""Top-k scoring + ranking metrics (MAP@k, precision@k, NDCG@k).

The serving/eval math of the Recommendation templates: score = U Vᵀ with
seen-item exclusion, then top-k. Batched over users in chunks sized so the
[chunk, n_items] score tile stays within a ~1 GiB budget (small runs score
in one tile; ML-20M-scale runs never materialize the full n_users ×
n_items matrix — SURVEY.md §6 tracks MAP@10 on ML-20M).
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

# batches up to this size score on the host (serving path); larger go to
# the accelerator (eval/bulk path)
SERVE_HOST_MAX_BATCH = 64


@functools.lru_cache(maxsize=16)
def _topk_fn(k: int, masked: bool):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.telemetry.registry import capped_label
    from predictionio_tpu.utils.profiling import metered_jit

    def score_topk(u_vecs, item_factors, ex_rows=None, ex_cols=None):
        # u_vecs [B, K]; item_factors [N, K]; exclusions as COO indices
        # (ex_rows[e], ex_cols[e]) scattered to -inf ON DEVICE — a dense
        # [B, N] host mask is ~1 GB of host→device transfer per
        # ML-20M-scale chunk, the index form ~8 bytes per seen item
        # (the two were last compared on an earlier machine; on the
        # current chip the difference is not measured). Padding entries
        # carry ex_rows == B (out of range) and vanish under mode="drop".
        scores = u_vecs @ item_factors.T
        if masked:
            scores = scores.at[ex_rows, ex_cols].set(-jnp.inf, mode="drop")
        top_scores, top_idx = jax.lax.top_k(scores, k)
        return top_scores, top_idx

    # compile activity per (k, masked) variant is visible on /metrics —
    # a recompile storm here (unstable batch shapes defeating the bucket
    # ladder) used to be diagnosable only as a serving latency cliff.
    # k is caller-controlled (the query's "num"), so the label passes
    # through its own capped group: the first few distinct k values keep
    # per-k series, the long tail collapses to score_topk_k<other>
    # instead of minting one /metrics series per requested k.
    return metered_jit(
        score_topk,
        label=f"ranking.score_topk_k{capped_label('ranking_topk_k', k, cap=8)}")


def _exclusion_coo(ids, exclude, n_rows: int):
    """Per-chunk COO exclusion indices, padded to a power of two so chunk
    batches reuse compiles: (ex_rows [E], ex_cols [E]) int32, padding
    rows = n_rows (dropped by the scatter)."""
    rows, cols = [], []
    for i, uid in enumerate(ids):
        ex = exclude.get(int(uid))
        if ex is not None and len(ex):
            cols.append(np.asarray(ex, dtype=np.int32))
            rows.append(np.full(len(ex), i, dtype=np.int32))
    n = sum(len(r) for r in rows)
    cap = 1 << max(0, (n - 1).bit_length())
    ex_rows = np.full(cap, n_rows, dtype=np.int32)
    ex_cols = np.zeros(cap, dtype=np.int32)
    if n:
        ex_rows[:n] = np.concatenate(rows)
        ex_cols[:n] = np.concatenate(cols)
    return ex_rows, ex_cols


def recommend_topk(
    user_factors: np.ndarray,
    item_factors: np.ndarray,
    user_ids: np.ndarray,
    k: int,
    exclude: Optional[dict[int, np.ndarray]] = None,
    chunk: Optional[int] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k items for each user id. `exclude` maps user id → item-id array
    to hide (the 'unseen only' contract of the reference templates).

    chunk: users scored per device dispatch. Default (None) auto-sizes to
    a ~1 GiB [chunk, n_items] score tile; pass an explicit value to bound
    memory — it is honored as-is."""
    n_items = item_factors.shape[0]
    k = min(k, n_items)
    if k <= 0 or len(user_ids) == 0:
        return (np.zeros((len(user_ids), 0), np.float32),
                np.zeros((len(user_ids), 0), np.int32))
    masked = bool(exclude)
    # device-resident factors (the grid-eval path keeps trained factors on
    # chip — ops/als_grid host_factors=False): always take the device
    # branch; the host fast path's in-place numpy masking can't touch a
    # jax array, and a readback would defeat the point of residency
    on_device = not (isinstance(user_factors, np.ndarray)
                     and isinstance(item_factors, np.ndarray))
    if len(user_ids) <= SERVE_HOST_MAX_BATCH and not on_device:
        # Serving fast path: tiny batches score in numpy on the host. A
        # device round trip costs more than the dot product at any catalog
        # size that fits serving, and it keeps the prediction server off
        # the accelerator entirely — a deployed server must not hold the
        # (single-tenant) TPU that a concurrent `pio train` needs.
        #
        # Scored per row (gemv), NOT as one [B,K]@[K,N] gemm: BLAS gemm
        # blocks the reduction differently per shape, so a user's scores
        # would shift in the last ulp with the batch they arrived in —
        # and the serving micro-batcher promises batched ≡ sequential
        # bitwise. Per-row gemv is batch-size-invariant; at serving
        # batch sizes (≤ SERVE_HOST_MAX_BATCH) the gemv loop is still
        # hundreds of microseconds against a millisecond-scale request.
        it_t = item_factors.T
        scores = np.empty((len(user_ids), n_items),
                          dtype=np.result_type(user_factors, item_factors))
        for i, uid in enumerate(user_ids):
            scores[i] = user_factors[uid] @ it_t
        if masked:
            for i, uid in enumerate(user_ids):
                ex = exclude.get(int(uid))
                if ex is not None and len(ex):
                    scores[i, ex] = -np.inf
        idx = np.argpartition(-scores, k - 1, axis=1)[:, :k]
        part = np.take_along_axis(scores, idx, axis=1)
        order = np.argsort(-part, axis=1)
        # pin dtypes to the device path's (float32 scores, int32 indices)
        return (
            np.take_along_axis(part, order, axis=1).astype(np.float32),
            np.take_along_axis(idx, order, axis=1).astype(np.int32),
        )
    import jax

    fn = _topk_fn(k, masked)
    # ship the item table once — a numpy arg would re-transfer it on every
    # chunk call (measured: that transfer, not the matmul, dominated
    # ML-20M-scale MAP@10). Chunks grow with the user count, bounded so
    # the [chunk, n_items] score tile stays ~1 GB.
    item_dev = jax.device_put(item_factors)
    from predictionio_tpu.parallel.mesh import describe_devices

    log.info("recommend_topk: %d users x %d items scored on %s",
             len(user_ids), n_items, describe_devices(list(item_dev.devices())))
    if chunk is None:
        # no floor: a floor of 1024 would blow the ~1 GiB tile bound past
        # ~262k items (at 10M items the [1024, n_items] tile is ~40 GB)
        chunk = max(1, (1 << 28) // max(n_items, 1))
    chunk = min(chunk, len(user_ids))
    all_scores, all_idx = [], []
    for s in range(0, len(user_ids), chunk):
        ids = user_ids[s : s + chunk]
        u = user_factors[ids]
        if masked:
            ex_rows, ex_cols = _exclusion_coo(ids, exclude, len(ids))
            ts, ti = fn(u, item_dev, ex_rows, ex_cols)
        else:
            ts, ti = fn(u, item_dev)
        all_scores.append(np.asarray(ts))
        all_idx.append(np.asarray(ti))
    return np.concatenate(all_scores), np.concatenate(all_idx)


def average_precision_at_k(predicted, actual: set, k: int) -> float:
    """AP@k for one user (the MAP building block the reference's
    Recommendation template evaluation uses [U]). Works on int row indices
    or string item ids — elements are compared as-is against `actual`."""
    if not actual:
        return 0.0
    hits = 0
    score = 0.0
    for i, p in enumerate(predicted[:k]):
        p = p.item() if isinstance(p, np.generic) else p
        if p in actual:
            hits += 1
            score += hits / (i + 1.0)
    return score / min(len(actual), k)


def map_at_k(
    user_factors: np.ndarray,
    item_factors: np.ndarray,
    test_user_items: dict[int, set],
    k: int = 10,
    exclude: Optional[dict[int, np.ndarray]] = None,
) -> float:
    """Mean AP@k over users with test items."""
    user_ids = np.asarray(sorted(test_user_items), dtype=np.int32)
    if len(user_ids) == 0:
        return float("nan")
    _, top_idx = recommend_topk(user_factors, item_factors, user_ids, k, exclude)
    aps = [
        average_precision_at_k(top_idx[i], test_user_items[int(uid)], k)
        for i, uid in enumerate(user_ids)
    ]
    return float(np.mean(aps))
