"""E-Commerce Recommendation engine template (DASE components).

Parity with the reference E-Commerce Recommendation template (SURVEY.md
§2.4 [U]): implicit ALS on view events plus business rules applied at
query time — exclude items the user has seen («seenEvents»), exclude
globally unavailable items (a `$set` on the "constraint" entity
«unavailableItems», looked up through `LEventStore` on the query hot path
— SURVEY.md §3.2 `ECommAlgorithm.predict → LEventStore.findByEntity`),
optional category/whiteList/blackList filters, and a cold-start path that
scores through the user's recent views when there is no trained user
factor.

The serve-time event lookups sit on the QPS hot path (SURVEY.md §7.3), so
they go through a small TTL cache (`_TTLCache`) instead of hitting the
store every query.

Wire shapes (kept reference-compatible):
    query:  {"user": "u1", "num": 4, "categories": [...]?,
             "whiteList": [...]?, "blackList": [...]?}
    result: {"itemScores": [{"item": "i5", "score": 1.2}, ...]}
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Optional

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource as BaseDataSource,
    Engine,
    EngineFactory,
    FirstServing,
    Params,
    Preparator as BasePreparator,
    SanityCheck,
    WorkflowContext,
)
from predictionio_tpu.data.bimap import BiMap, compress_codes
from predictionio_tpu.data.store import LEventStore, PEventStore
from predictionio_tpu.ops.als import ALSConfig, als_train, emit_train_metrics
from predictionio_tpu.storage.registry import Storage
from predictionio_tpu.telemetry.spans import span

log = logging.getLogger(__name__)

Query = dict
PredictedResult = dict


class _TTLCache:
    """Tiny thread-safe TTL cache for serve-time event lookups."""

    def __init__(self, ttl_seconds: float):
        self.ttl = ttl_seconds
        self._lock = threading.Lock()
        self._data: dict = {}

    def get(self, key, compute):
        now = time.monotonic()
        with self._lock:
            hit = self._data.get(key)
            if hit is not None and now - hit[0] < self.ttl:
                return hit[1]
        value = compute()
        with self._lock:
            self._data[key] = (now, value)
        return value

    def clear(self):
        with self._lock:
            self._data.clear()


@dataclasses.dataclass
class DataSourceParams(Params):
    appName: str = ""
    eventNames: list = dataclasses.field(
        default_factory=lambda: ["view", "buy"]
    )


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Columnar view/buy events (coded COO via BiMaps — no per-event
    Python; VERDICT r1 #4) + per-item category properties."""

    user_idx: np.ndarray  # [n] int32 codes into user_ids
    item_idx: np.ndarray  # [n] int32 codes into item_ids
    weights: np.ndarray  # [n] float32 — buy counts more than view
    user_ids: BiMap
    item_ids: BiMap
    item_categories: dict  # item id → [category]

    @property
    def users(self) -> list:
        """Decoded user id strings (debug/compat view; O(n) Python)."""
        return self.user_ids.from_index(self.user_idx)

    @property
    def items(self) -> list:
        return self.item_ids.from_index(self.item_idx)

    def sanity_check(self):
        if not len(self.user_idx):
            raise ValueError(
                "TrainingData has no view/buy events; ingest events first."
            )


class DataSource(BaseDataSource):
    params_class = DataSourceParams

    #: implicit confidence per event type (buy is a stronger signal)
    EVENT_WEIGHTS = {"view": 1.0, "buy": 4.0}

    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        store = PEventStore(ctx.storage)
        cols = store.find_columnar(
            app_name=self.params.appName,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.eventNames),
            ordered=False,  # summed per-pair confidence is order-invariant
        )
        valid = cols.target_ids >= 0
        weight_of = np.asarray(
            [self.EVENT_WEIGHTS.get(name, 1.0) for name in cols.event_names],
            dtype=np.float32,
        )
        weights = (weight_of[cols.event_codes[valid]]
                   if len(cols.event_names)
                   else np.empty(0, np.float32))
        item_props = store.aggregate_properties(
            app_name=self.params.appName, entity_type="item"
        )
        item_categories = {
            eid: list(p.get("categories", []) or [])
            for eid, p in item_props.items()
        }
        log.info(
            "DataSource: %d view/buy events, %d items with properties, app %r",
            int(valid.sum()), len(item_categories), self.params.appName,
        )
        return TrainingData(
            user_idx=cols.entity_ids[valid],
            item_idx=cols.target_ids[valid],
            weights=weights,
            user_ids=cols.entity_bimap,
            item_ids=cols.target_bimap,
            item_categories=item_categories,
        )


@dataclasses.dataclass
class PreparedData:
    user_ids: BiMap
    item_ids: BiMap
    user_idx: np.ndarray  # [n] int32 (deduped pairs)
    item_idx: np.ndarray
    confidence: np.ndarray  # [n] float32 — summed per-pair weights
    item_categories: dict


class Preparator(BasePreparator):
    """BiMap ids; sum repeated interactions into per-pair confidence."""

    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> PreparedData:
        # re-code densely over present entities
        u, user_ids = compress_codes(td.user_idx, td.user_ids)
        i, item_ids = compress_codes(td.item_idx, td.item_ids)
        n_items = max(len(item_ids), 1)
        pair = u.astype(np.int64) * n_items + i
        uniq, inverse = np.unique(pair, return_inverse=True)
        conf = np.zeros(len(uniq), dtype=np.float32)
        np.add.at(conf, inverse, td.weights)
        return PreparedData(
            user_ids=user_ids,
            item_ids=item_ids,
            user_idx=(uniq // n_items).astype(np.int32),
            item_idx=(uniq % n_items).astype(np.int32),
            confidence=conf,
            item_categories=td.item_categories,
        )


@dataclasses.dataclass
class ECommModelData:
    """Pure model state (pickled into the Models blob)."""

    user_factors: np.ndarray  # [n_users, K]
    item_factors: np.ndarray  # [n_items, K]
    item_factors_unit: np.ndarray  # [n_items, K] — for the cold-start path
    user_ids: BiMap
    item_ids: BiMap
    item_categories: dict
    app_name: str


@dataclasses.dataclass
class ECommAlgorithmParams(Params):
    appName: str = ""  # for serve-time LEventStore lookups
    rank: int = 10
    numIterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None
    seenEvents: list = dataclasses.field(
        default_factory=lambda: ["view", "buy"]
    )
    similarEvents: list = dataclasses.field(default_factory=lambda: ["view"])
    unseenOnly: bool = True
    recentNum: int = 10  # cold-start: score via this many recent views
    cacheTTLSeconds: float = 3.0

    _ALIASES = {"lambda": "lambda_"}


class ECommAlgorithm(Algorithm):
    """«ECommAlgorithm.train/predict» [U]. Serve-time business rules live
    here (not in Serving) to match the reference's shape."""

    params_class = ECommAlgorithmParams
    checkpoint_tags = ("als",)

    def __init__(self, params: ECommAlgorithmParams):
        self.params = params
        self._cache = _TTLCache(params.cacheTTLSeconds)

    # -- train -------------------------------------------------------------
    def train(self, ctx: WorkflowContext, pd: PreparedData) -> ECommModelData:
        p = self.params
        cfg = ALSConfig(
            rank=p.rank,
            iterations=p.numIterations,
            reg=p.lambda_,
            implicit=True,
            alpha=p.alpha,
            seed=ctx.seed if p.seed is None else p.seed,
        )
        result = als_train(
            pd.user_idx, pd.item_idx, pd.confidence,
            n_users=len(pd.user_ids), n_items=len(pd.item_ids),
            cfg=cfg, mesh=ctx.mesh,
            bucket_cache_dir=ctx.algorithm_cache_dir("als"),
            checkpoint_dir=ctx.algorithm_checkpoint_dir("als"),
            checkpoint_every=ctx.checkpoint_every_or(1),
        )
        emit_train_metrics(ctx.metrics, result)
        f = result.item_factors
        with span("model.unit_norm"):
            norms = np.linalg.norm(f, axis=1, keepdims=True)
            unit = np.where(norms > 0, f / np.maximum(norms, 1e-12), 0.0)
        return ECommModelData(
            user_factors=result.user_factors,
            item_factors=f,
            item_factors_unit=unit.astype(np.float32),
            user_ids=pd.user_ids,
            item_ids=pd.item_ids,
            item_categories=pd.item_categories,
            app_name=self.params.appName,
        )

    # -- serve-time lookups (cached) ---------------------------------------
    def _store(self) -> LEventStore:
        return LEventStore(Storage.get())

    def _unavailable_items(self, app_name: str) -> set:
        """Latest `$set` on constraint/unavailableItems («ECommAlgorithm.
        predict → LEventStore.findByEntity» [U])."""

        def compute():
            try:
                events = self._store().find_by_entity(
                    app_name=app_name,
                    entity_type="constraint",
                    entity_id="unavailableItems",
                    event_names=["$set"],
                    limit=1,
                    latest=True,
                )
            except Exception as e:  # storage down ≠ serving down
                log.warning("unavailableItems lookup failed: %s", e)
                return set()
            if not events:
                return set()
            return set(events[0].properties.get("items", []) or [])

        return self._cache.get(("unavailable", app_name), compute)

    def _seen_items(self, app_name: str, user: str) -> set:
        def compute():
            try:
                events = self._store().find_by_entity(
                    app_name=app_name,
                    entity_type="user",
                    entity_id=user,
                    event_names=list(self.params.seenEvents),
                    target_entity_type="item",
                )
            except Exception as e:
                log.warning("seen-items lookup failed: %s", e)
                return set()
            return {
                e.target_entity_id for e in events if e.target_entity_id
            }

        return self._cache.get(("seen", app_name, user), compute)

    def _recent_items(self, app_name: str, user: str) -> list:
        def compute():
            try:
                events = self._store().find_by_entity(
                    app_name=app_name,
                    entity_type="user",
                    entity_id=user,
                    event_names=list(self.params.similarEvents),
                    target_entity_type="item",
                    limit=self.params.recentNum,
                    latest=True,
                )
            except Exception as e:
                log.warning("recent-items lookup failed: %s", e)
                return []
            return [e.target_entity_id for e in events if e.target_entity_id]

        return self._cache.get(("recent", app_name, user), compute)

    # -- predict -----------------------------------------------------------
    def predict(self, model: ECommModelData, query: Query) -> PredictedResult:
        p = self.params
        app_name = model.app_name or p.appName
        user = str(query["user"])
        num = int(query.get("num", 10))

        if model.user_ids.contains(user):
            uvec = model.user_factors[int(model.user_ids[user])]
            scores = model.item_factors @ uvec
        else:
            # cold start: average similarity to recently viewed items
            recent = [
                i for i in self._recent_items(app_name, user)
                if model.item_ids.contains(i)
            ]
            if not recent:
                return {"itemScores": []}
            q = model.item_factors_unit[model.item_ids.to_index(recent)]
            scores = (q @ model.item_factors_unit.T).mean(axis=0)

        mask = np.ones(scores.shape[0], dtype=bool)
        if p.unseenOnly:
            seen = [
                i for i in self._seen_items(app_name, user)
                if model.item_ids.contains(i)
            ]
            if seen:
                mask[model.item_ids.to_index(seen)] = False
        unavailable = [
            i for i in self._unavailable_items(app_name)
            if model.item_ids.contains(i)
        ]
        if unavailable:
            mask[model.item_ids.to_index(unavailable)] = False
        white_list = query.get("whiteList")
        if white_list:
            wl = np.zeros_like(mask)
            have = [i for i in white_list if model.item_ids.contains(i)]
            if have:
                wl[model.item_ids.to_index(have)] = True
            mask &= wl
        black_list = query.get("blackList")
        if black_list:
            have = [i for i in black_list if model.item_ids.contains(i)]
            if have:
                mask[model.item_ids.to_index(have)] = False
        categories = query.get("categories")
        if categories:
            cats = set(categories)
            idxs = np.nonzero(mask)[0]
            for idx, item in zip(idxs, model.item_ids.from_index(idxs)):
                if not cats & set(model.item_categories.get(item, [])):
                    mask[idx] = False

        scores = np.where(mask, scores, -np.inf)
        k = min(num, int(mask.sum()))
        if k <= 0:
            return {"itemScores": []}
        top = np.argpartition(-scores, k - 1)[:k]
        top = top[np.argsort(-scores[top])]
        items = model.item_ids.from_index(top)
        return {
            "itemScores": [
                {"item": item, "score": float(scores[idx])}
                for item, idx in zip(items, top)
            ]
        }


class ECommerceEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class_map=DataSource,
            preparator_class_map=Preparator,
            algorithm_class_map={"ecomm": ECommAlgorithm},
            serving_class_map=FirstServing,
        )
