"""Engine: binds DASE component classes + params into a trainable,
deployable unit.

Parity with «core/.../controller/Engine.scala :: Engine» (SURVEY.md §2.1
[U]): holds `dataSourceClassMap`-style name→class maps, `train` runs the
DASE pipeline, `eval` runs per-fold train+batch-predict, and
`prepare_deploy` reloads persisted models for serving.
"""

from __future__ import annotations

import dataclasses
import logging
import pickle
from typing import Any, Optional, Sequence, Type

from predictionio_tpu.controller.base import (
    Algorithm,
    DataSource,
    Doer,
    FirstServing,
    PersistentModel,
    Preparator,
    Serving,
    IdentityPreparator,
    run_sanity_check,
)
from predictionio_tpu.controller.context import WorkflowContext
from predictionio_tpu.controller.params import Params, params_from_dict
from predictionio_tpu.telemetry.spans import span

log = logging.getLogger(__name__)


def resolve_component(class_map: dict, name: str, role: str) -> Type:
    """THE component-name resolution rule, shared by engine.json extraction
    and runtime lookup so the two can't drift: an empty name falls back to
    a single-entry map's only class; a non-empty name must match exactly
    (a typo'd name errors instead of silently training something else)."""
    if name in class_map:
        return class_map[name]
    if name == "" and len(class_map) == 1:
        return next(iter(class_map.values()))
    raise KeyError(f"Unknown {role} name {name!r} (have {sorted(class_map)})")


def _ckpt_suffixes(algos) -> list[str]:
    """Checkpoint-dir suffix per algorithm instance: "" for the first
    user of a checkpoint tag, ".1"/".2"/… for later ones. Checkpoint
    subdirs are keyed by a tag the algorithm CLASS hard-codes
    (`Algorithm.checkpoint_tags`), so collisions follow the TAG, not the
    class: two entries of one class — legal in engine.json, matching
    «algorithmClassMap» [U] — and equally two different classes that
    declare the same tag (e.g. ALS variants both tagged "als") would
    purge each other's saves without this. Classes declaring no tags
    fall back to per-class keying (they may still checkpoint under an
    undeclared name; same-class duplicates stay disambiguated)."""
    counts: dict = {}
    out = []
    for _, algo in algos:
        keys = tuple(getattr(algo, "checkpoint_tags", ()) or ()) or (type(algo),)
        # an instance whose class uses several tags must not reuse ANY of
        # them, so its suffix ordinal is the max across its tags
        n = max(counts.get(k, 0) for k in keys)
        for k in keys:
            counts[k] = n + 1
        out.append(f".{n}" if n else "")
    return out


@dataclasses.dataclass
class EngineParams:
    """«controller/EngineParams» [U]: per-component (name, params) selections."""

    data_source_name: str = ""
    data_source_params: Optional[Params] = None
    preparator_name: str = ""
    preparator_params: Optional[Params] = None
    # list of (algorithm name, params) — multiple algorithms train together
    # and serve together through Serving (SURVEY.md §2.6 strategy 4)
    algorithm_params_list: list[tuple[str, Optional[Params]]] = dataclasses.field(
        default_factory=lambda: [("", None)]
    )
    serving_name: str = ""
    serving_params: Optional[Params] = None


class Engine:
    def __init__(
        self,
        data_source_class_map: dict[str, Type[DataSource]] | Type[DataSource],
        preparator_class_map: dict[str, Type[Preparator]] | Type[Preparator] | None = None,
        algorithm_class_map: dict[str, Type[Algorithm]] | Type[Algorithm] = None,
        serving_class_map: dict[str, Type[Serving]] | Type[Serving] | None = None,
    ):
        if data_source_class_map is None or algorithm_class_map is None:
            raise ValueError(
                "Engine requires data_source_class_map and algorithm_class_map "
                "(preparator/serving default to identity/first)."
            )

        def as_map(x, default_cls=None):
            if x is None:
                return {"": default_cls}
            if isinstance(x, dict):
                return x
            return {"": x}

        self.data_source_class_map = as_map(data_source_class_map)
        self.preparator_class_map = as_map(preparator_class_map, IdentityPreparator)
        self.algorithm_class_map = as_map(algorithm_class_map)
        self.serving_class_map = as_map(serving_class_map, FirstServing)

    # -- component resolution ---------------------------------------------
    def components(self, engine_params: EngineParams):
        ds = Doer.apply(
            resolve_component(self.data_source_class_map,
                              engine_params.data_source_name, "data source"),
            engine_params.data_source_params,
        )
        prep = Doer.apply(
            resolve_component(self.preparator_class_map,
                              engine_params.preparator_name, "preparator"),
            engine_params.preparator_params,
        )
        algos = [
            (
                name,
                Doer.apply(
                    resolve_component(self.algorithm_class_map, name, "algorithm"),
                    params,
                ),
            )
            for name, params in engine_params.algorithm_params_list
        ]
        serving = Doer.apply(
            resolve_component(self.serving_class_map, engine_params.serving_name,
                              "serving"),
            engine_params.serving_params,
        )
        check = getattr(serving, "check_against_algorithms", None)
        if check is not None:
            # fail a serving/algorithms mismatch HERE — at train, deploy,
            # and eval entry — not as a 500 on every production query
            # (e.g. WeightedServing with N weights for M algorithms)
            check([name for name, _ in algos])
        return ds, prep, algos, serving

    # -- train (CoreWorkflow.runTrain inner loop, SURVEY.md §3.1) ----------
    def train(
        self,
        ctx: WorkflowContext,
        engine_params: EngineParams,
        sanity_check: bool = False,
    ) -> list[Any]:
        ds, prep, algos, _ = self.components(engine_params)
        log.info("Engine.train: reading training data (%s)", type(ds).__name__)
        with span("dase.read"):
            td = ds.read_training(ctx)
        if sanity_check:
            run_sanity_check(td, "training data")
        log.info("Engine.train: preparing data (%s)", type(prep).__name__)
        with span("dase.prepare"):
            pd = prep.prepare(ctx, td)
        if sanity_check:
            run_sanity_check(pd, "prepared data")
        models = []
        for (name, algo), suffix in zip(algos, _ckpt_suffixes(algos)):
            log.info("Engine.train: training algorithm %r (%s)",
                     name, type(algo).__name__)
            with ctx.algo_checkpoint_scope(suffix), span("dase.train"):
                model = algo.train(ctx, pd)
            if sanity_check:
                run_sanity_check(model, f"model[{name}]")
            models.append(model)
        return models

    # -- eval (Engine.eval, SURVEY.md §3.4) --------------------------------
    def eval(
        self, ctx: WorkflowContext, engine_params: EngineParams
    ) -> list[tuple[Any, list[tuple[Any, Any, Any]]]]:
        """Per fold: train on the fold's training split, batch-predict its
        queries. Returns [(fold_td, [(query, predicted, actual), ...])]."""
        ds, prep, algos, serving = self.components(engine_params)
        folds = ds.read_eval(ctx)
        suffixes = _ckpt_suffixes(algos)
        results = []
        for i, (td, qa_pairs) in enumerate(folds):
            log.info("Engine.eval: fold %d/%d (%d queries)",
                     i + 1, len(folds), len(qa_pairs))
            pd = prep.prepare(ctx, td)
            models = []
            for (_, algo), suffix in zip(algos, suffixes):
                with ctx.algo_checkpoint_scope(suffix):
                    models.append(algo.train(ctx, pd))
            queries = [q for q, _ in qa_pairs]
            per_algo = [
                algo.batch_predict(model, queries)
                for (_, algo), model in zip(algos, models)
            ]
            qpa = [
                (q, serving.serve(q, [preds[j] for preds in per_algo]), a)
                for j, (q, a) in enumerate(qa_pairs)
            ]
            results.append((td, qpa))
        return results

    # -- grid eval (SURVEY.md §2.6 strategy 4, TPU-native form) ------------
    def eval_grid(
        self, ctx: WorkflowContext,
        engine_params_list: Sequence[EngineParams],
    ) -> Optional[list[list[tuple[Any, list[tuple[Any, Any, Any]]]]]]:
        """Evaluate every EngineParams in one pass: folds are read and
        prepared ONCE (they're identical when the grid varies only
        algorithm params), and algorithms that implement `train_grid`
        train all grid cells as one device program. Returns per-ep fold
        results (same shape `eval` returns, one entry per ep), or None
        when the grid isn't shareable — differing data-source/preparator/
        serving selections, or mismatched algorithm name lists — in which
        case the caller runs the sequential path.

        Falls back gracefully *per algorithm*: a non-batchable algorithm
        (train_grid → None) still shares the fold read/prepare and trains
        its cells sequentially inside this pass.
        """
        if len(engine_params_list) < 2:
            return None
        base = engine_params_list[0]

        def shared_key(ep: EngineParams):
            from predictionio_tpu.controller.params import params_to_dict

            def d(p):
                return params_to_dict(p) if p else {}

            return (ep.data_source_name, d(ep.data_source_params),
                    ep.preparator_name, d(ep.preparator_params),
                    ep.serving_name, d(ep.serving_params),
                    [name for name, _ in ep.algorithm_params_list])

        if any(shared_key(ep) != shared_key(base)
               for ep in engine_params_list[1:]):
            log.info("Engine.eval_grid: grid varies beyond algorithm "
                     "params — sequential evaluation")
            return None

        ds, prep, _, serving = self.components(base)
        # per-ep algorithm instances, grouped by position in the algo list
        algos_by_ep = [self.components(ep)[2] for ep in engine_params_list]
        folds = ds.read_eval(ctx)
        n_ep = len(engine_params_list)
        # per-POSITION suffixes (duplicate classes across positions
        # collide exactly as in train). Within one position the per-ep
        # cells still share a subdir: grid-batched cells skip
        # checkpointing entirely, and sequential-fallback cells
        # checkpoint last-writer-wins (a differing-config cell's first
        # save purges the previous cell's steps) — a crash mid-grid
        # resumes only the cell that was training, same as before this
        # suffix existed
        pos_suffixes = _ckpt_suffixes(algos_by_ep[0])
        results: list[list] = [[] for _ in range(n_ep)]
        for fi, (td, qa_pairs) in enumerate(folds):
            log.info("Engine.eval_grid: fold %d/%d (%d queries, %d grid "
                     "points)", fi + 1, len(folds), len(qa_pairs), n_ep)
            pd = prep.prepare(ctx, td)
            # models[e][j] = model for ep e, algorithm position j
            models: list[list[Any]] = [[] for _ in range(n_ep)]
            for j, (name, _) in enumerate(base.algorithm_params_list):
                instances = [algos_by_ep[e][j][1] for e in range(n_ep)]
                cls = type(instances[0])
                with ctx.algo_checkpoint_scope(pos_suffixes[j]):
                    grid_models = None
                    if all(type(a) is cls for a in instances):
                        grid_models = cls.train_grid(ctx, pd, instances)
                    if grid_models is None:
                        grid_models = [a.train(ctx, pd) for a in instances]
                for e in range(n_ep):
                    models[e].append(grid_models[e])
            queries = [q for q, _ in qa_pairs]
            for e in range(n_ep):
                per_algo = [
                    algo.batch_predict(model, queries)
                    for (_, algo), model in zip(algos_by_ep[e], models[e])
                ]
                qpa = [
                    (q, serving.serve(q, [preds[j] for preds in per_algo]), a)
                    for j, (q, a) in enumerate(qa_pairs)
                ]
                results[e].append((td, qpa))
        return results

    # -- model persistence (Engine.makeSerializableModels / prepareDeploy,
    #    SURVEY.md §3.1/§3.2) ----------------------------------------------
    def serialize_models(
        self, models: Sequence[Any], instance_id: str, engine_params: EngineParams
    ) -> bytes:
        """PersistentModel models save themselves and leave a marker; all
        others are pickled into the blob."""
        out = []
        for model, (name, algo_params) in zip(models, engine_params.algorithm_params_list):
            if isinstance(model, PersistentModel):
                saved = model.save(instance_id, algo_params)
                if saved:
                    out.append(("__persistent__", type(model).__module__,
                                type(model).__qualname__))
                    continue
            out.append(("__pickled__", model, None))
        return pickle.dumps(out)

    def deserialize_models(
        self, blob: bytes, instance_id: str, engine_params: EngineParams
    ) -> list[Any]:
        import importlib

        entries = pickle.loads(blob)
        models = []
        for entry, (name, algo_params) in zip(entries, engine_params.algorithm_params_list):
            kind, a, b = entry
            if kind == "__persistent__":
                module, qualname = a, b
                cls = importlib.import_module(module)
                for part in qualname.split("."):
                    cls = getattr(cls, part)
                models.append(cls.load(instance_id, algo_params))
            else:
                models.append(a)
        return models

    # -- serving-time prediction (ServerActor route, SURVEY.md §3.2) -------
    def predict(
        self,
        engine_params: EngineParams,
        models: Sequence[Any],
        query: Any,
        components=None,
    ) -> Any:
        """Serve one query. The prediction server resolves `components`
        once at deploy time and passes them in — per-request reflective
        instantiation would put Doer overhead on the hot path."""
        if components is None:
            components = self.components(engine_params)
        _, _, algos, serving = components
        predictions = [
            algo.predict(model, query) for (_, algo), model in zip(algos, models)
        ]
        return serving.serve(query, predictions)

    def predict_batch(
        self,
        engine_params: EngineParams,
        models: Sequence[Any],
        queries: Sequence[Any],
        components=None,
    ) -> list[Any]:
        """Serve a coalesced batch of queries in one pass — the serving
        micro-batcher's dispatch target. Each algorithm scores the whole
        batch via `batch_predict` (vectorized where the template overrides
        it, a predict loop otherwise), then Serving combines per query
        exactly as `predict` does, so results are positionally identical
        to per-query `predict` calls."""
        if components is None:
            components = self.components(engine_params)
        _, _, algos, serving = components
        per_algo = [
            algo.batch_predict(model, list(queries))
            for (_, algo), model in zip(algos, models)
        ]
        return [
            serving.serve(q, [preds[i] for preds in per_algo])
            for i, q in enumerate(queries)
        ]

    def degraded_predict(
        self,
        engine_params: EngineParams,
        models: Sequence[Any],
        query: Any,
        components=None,
    ) -> Optional[Any]:
        """Serve one query through the first `degraded_capable` algorithm
        alone (bypassing Serving combination — the other algorithms did
        not run). Returns None when no algorithm volunteers; the serving
        plane then sheds normally."""
        if components is None:
            components = self.components(engine_params)
        _, _, algos, _ = components
        for (_, algo), model in zip(algos, models):
            if getattr(algo, "degraded_capable", False):
                return algo.predict(model, query)
        return None


class EngineFactory:
    """«controller/EngineFactory» [U]: subclass and implement `apply()`
    returning an Engine; referenced by dotted path in engine.json."""

    def apply(self) -> Engine:
        raise NotImplementedError

    # Engine.json shape helpers: subclasses may override to map params
    # blocks to their Params dataclasses.
    params_classes: dict[str, type] = {}
