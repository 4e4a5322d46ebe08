"""CoreWorkflow — train/eval runs with EngineInstance bookkeeping.

Parity with «core/.../workflow/{CoreWorkflow,CreateWorkflow,
EvaluationWorkflow}.scala» (SURVEY.md §3.1/§3.4 [U]): one EngineInstance
row per `pio train` (RUNNING → COMPLETED/FAILED, holding the engine params
JSON and keyed to the stored model blob), one EvaluationInstance per
`pio eval`. The idempotent re-run contract — re-running train after a
failure just creates a new instance — is the reference's failure-recovery
story and is preserved (SURVEY.md §5 'Failure detection').
"""

from __future__ import annotations

import contextlib
import logging
import time
import traceback
from datetime import datetime, timezone
from typing import Optional, Sequence

from predictionio_tpu.controller.context import WorkflowContext
from predictionio_tpu.controller.engine import Engine, EngineParams
from predictionio_tpu.controller.evaluation import (
    EngineParamsGenerator,
    Evaluation,
    EvaluationResult,
    MetricEvaluator,
)
from predictionio_tpu.storage.base import EngineInstance, EvaluationInstance, Model
from predictionio_tpu.telemetry import device as device_telemetry
from predictionio_tpu.telemetry import spans, tracing
from predictionio_tpu.telemetry.recorder import RECORDER
from predictionio_tpu.workflow.workflow_utils import (
    EngineVariant,
    engine_params_to_json,
)

log = logging.getLogger(__name__)


@contextlib.contextmanager
def tracked_instance(instances, instance, completed: str = "COMPLETED",
                     failed: str = "FAILED", label: str = "workflow"):
    """Instance-row lifecycle shared by train/eval/fake workflows: insert
    as-is (caller sets the RUNNING-style status), mark `completed` after
    the block, mark `failed` + log + re-raise on exception. Fields the
    block sets on the instance (e.g. evaluator results) persist in the
    final update."""
    instance.id = instances.insert(instance)
    log.info("%s: instance %s %s", label, instance.id, instance.status)
    try:
        yield instance
    except Exception:
        instance.status = failed
        instance.end_time = _now()
        instances.update(instance)
        log.error("%s: instance %s %s\n%s", label, instance.id, failed,
                  traceback.format_exc())
        raise
    instance.status = completed
    instance.end_time = _now()
    instances.update(instance)
    log.info("%s: instance %s %s", label, instance.id, completed)


def _now() -> datetime:
    return datetime.now(timezone.utc)


class CoreWorkflow:
    @staticmethod
    def run_train(
        engine: Engine,
        engine_params: EngineParams,
        variant: EngineVariant,
        ctx: WorkflowContext,
        engine_version: str = "1",
        sanity_check: bool = True,
    ) -> EngineInstance:
        """The `pio train` body (SURVEY.md §3.1): train → persist models →
        mark instance COMPLETED.

        Multi-host: every rank trains (the jitted step is SPMD and all
        ranks must participate in the collectives), but only ONE rank
        persists — the reference has exactly one Spark driver writing the
        EngineInstance row; N ranks each inserting their own row would
        leave `pio deploy`'s latest-COMPLETED lookup racing N instances.
        The persisting rank is `PIO_PERSIST_RANK` (default 0), which may
        differ from the coordinator (always process 0 in jax) — see
        parallel/distributed.py::persist_rank."""
        jax = device_telemetry.import_jax()

        from predictionio_tpu.parallel.distributed import persist_rank

        # the train route's first question to the runtime: the backend
        # starts here, and the compile log keeps the seconds
        with device_telemetry.first_seconds("runtime.backend_init",
                                            "workflow.train"):
            n_processes = jax.process_count()
        p_rank = persist_rank() if n_processes > 1 else 0
        if n_processes > 1 and jax.process_index() != p_rank:
            with device_telemetry.attribution("workflow.train",
                                              tier="train"):
                models = engine.train(ctx, engine_params,
                                      sanity_check=sanity_check)
            log.info("CoreWorkflow.run_train: rank %d trained %d model(s); "
                     "rank %d persists", jax.process_index(), len(models),
                     p_rank)
            # WORKER_DONE ≠ COMPLETED: this rank did its SPMD share, but
            # whether a servable instance exists is the persist rank's
            # verdict — orchestrators must watch it for the persisted id
            return EngineInstance(
                id=f"(worker rank {jax.process_index()}; "
                   f"rank {p_rank} persists)",
                status="WORKER_DONE", start_time=_now(), end_time=_now(),
                engine_id=variant.id, engine_version=engine_version,
                engine_variant=variant.variant,
                engine_factory=variant.engine_factory, batch=ctx.batch,
                env={}, **engine_params_to_json(engine_params),
            )
        storage = ctx.storage
        instances = storage.meta_engine_instances()
        instance = EngineInstance(
            id="",
            status="RUNNING",
            start_time=_now(),
            end_time=_now(),
            engine_id=variant.id,
            engine_version=engine_version,
            engine_variant=variant.variant,
            engine_factory=variant.engine_factory,
            batch=ctx.batch,
            env={},
            **engine_params_to_json(engine_params),
        )
        # Train runs get a pinned timeline too: phase durations (train /
        # serialize / persist) retrievable from any in-process server's
        # /debug/requests.json, keyed by the run's trace id.
        trace_id = tracing.current_trace_id() or tracing.new_context().trace_id
        tl, token = spans.begin("workflow", "train", "RUN", trace_id)
        tl.pinned = True
        t_wall = time.perf_counter()
        ok = False
        try:
            with tracked_instance(instances, instance,
                                  label="CoreWorkflow.run_train"):
                with spans.span("workflow.train"):
                    # device attribution: every jitted train step bills
                    # its device-seconds to the workflow.train route,
                    # tiered by stage
                    with device_telemetry.attribution("workflow.train",
                                                      tier="train"):
                        models = engine.train(ctx, engine_params,
                                              sanity_check=sanity_check)
                with spans.span("workflow.serialize"):
                    with device_telemetry.attribution("workflow.train",
                                                      tier="serialize"):
                        blob = engine.serialize_models(models, instance.id,
                                                       engine_params)
                with spans.span("workflow.persist"):
                    storage.model_data_models().insert(
                        Model(id=instance.id, models=blob))
                log.info("CoreWorkflow.run_train: instance %s trained "
                         "%d model(s), %d byte blob",
                         instance.id, len(models), len(blob))
            ok = True
        finally:
            spans.finish(tl, token, status=None,
                         duration_s=time.perf_counter() - t_wall,
                         error=not ok)
            RECORDER.offer(tl)
            # the same timeline for an operator whose process ends with
            # the train (`pio train --metrics-file`): seconds by span
            # name, a name's ` step_N` suffix left off
            phases: dict[str, float] = {}
            for name, _start, seconds, _error, _nested in tl.spans:
                name = name.partition(" ")[0]
                phases[name] = phases.get(name, 0.0) + seconds
            # and what the process paid before the timeline opened
            first_seconds = device_telemetry.phase_seconds(
                r for r in device_telemetry.COMPILE_LOG.records()
                if r.phase not in device_telemetry.JIT_PHASES)
            ctx.metrics.emit("train/phases", **phases, **first_seconds,
                             dropped_spans=tl.dropped_spans)
        return instance

    @staticmethod
    def run_evaluation(
        evaluation: Evaluation,
        generator: EngineParamsGenerator,
        ctx: WorkflowContext,
        evaluation_class: str = "",
        generator_class: str = "",
    ) -> tuple[EvaluationInstance, EvaluationResult]:
        """The `pio eval` body (SURVEY.md §3.4)."""
        storage = ctx.storage
        instances = storage.meta_evaluation_instances()
        instance = EvaluationInstance(
            id="",
            status="EVALRUNNING",
            start_time=_now(),
            end_time=_now(),
            evaluation_class=evaluation_class or type(evaluation).__name__,
            engine_params_generator_class=generator_class or type(generator).__name__,
            batch=ctx.batch,
        )
        with tracked_instance(instances, instance, completed="EVALCOMPLETED",
                              failed="EVALFAILED",
                              label="CoreWorkflow.run_evaluation"):
            result = MetricEvaluator.evaluate(
                ctx, evaluation, list(generator.engine_params_list)
            )
            instance.evaluator_results = result.summary()
            instance.evaluator_results_json = result.to_json()
        return instance, result
