"""CreateWorkflow — the `pio train` / `pio eval` executable body.

Parity with «core/.../workflow/CreateWorkflow.scala :: main» (SURVEY.md
§3.1 [U]). Where the reference spark-submits a new JVM, we run in-process:
parse the engine variant (engine.json), reflectively resolve the factory,
extract typed params, build the WorkflowContext (mesh in place of
SparkContext), and hand off to CoreWorkflow.
"""

from __future__ import annotations

import logging
from typing import Optional

from predictionio_tpu.controller.context import WorkflowContext
from predictionio_tpu.telemetry import device as device_telemetry
from predictionio_tpu.workflow.core_workflow import CoreWorkflow
from predictionio_tpu.workflow.workflow_utils import (
    EngineVariant,
    extract_engine_params,
    get_engine,
    read_engine_json,
    resolve_symbol,
)

log = logging.getLogger(__name__)


def parse_mesh_spec(spec: Optional[str]) -> Optional[dict[str, int]]:
    """'data=4,model=2' → {"data": 4, "model": 2}."""
    if not spec:
        return None
    out: dict[str, int] = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        if not size.isdigit():
            raise ValueError(f"Bad mesh spec {spec!r} (want e.g. data=4,model=2)")
        out[name.strip()] = int(size)
    return out


def run_train(
    engine_json: str = "engine.json",
    engine_version: str = "1",
    batch: str = "",
    seed: int = 0,
    mesh: Optional[str] = None,
    skip_sanity_check: bool = False,
    verbose: int = 0,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: Optional[int] = None,
    profile_dir: Optional[str] = None,
    metrics_file: Optional[str] = None,
    debug_nans: bool = False,
    check_asserts: bool = False,
):
    # the train route brings jax in here: timed, and the compile log
    # listening before the first program
    device_telemetry.import_jax()
    from predictionio_tpu.parallel.distributed import initialize_from_env
    from predictionio_tpu.utils.profiling import (
        MetricsLogger,
        maybe_trace,
        set_debug_flags,
    )

    initialize_from_env()  # multi-host bootstrap when PIO_COORDINATOR_* set
    set_debug_flags(nan_check=debug_nans, check_asserts=check_asserts)
    variant = read_engine_json(engine_json)
    with device_telemetry.first_seconds("process.import",
                                        variant.engine_factory):
        engine = get_engine(variant.engine_factory)
    engine_params = extract_engine_params(engine, variant)
    with MetricsLogger(metrics_file, run=batch or variant.id) as metrics:
        ctx = WorkflowContext(
            mesh_shape=parse_mesh_spec(mesh), seed=seed, batch=batch,
            verbose=verbose, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, metrics=metrics,
        )
        with maybe_trace(profile_dir):
            return CoreWorkflow.run_train(
                engine,
                engine_params,
                variant,
                ctx,
                engine_version=engine_version,
                sanity_check=not skip_sanity_check,
            )


def run_evaluation(
    evaluation_class: str,
    generator_class: Optional[str] = None,
    batch: str = "",
    seed: int = 0,
    mesh: Optional[str] = None,
    verbose: int = 0,
):
    eval_cls = resolve_symbol(evaluation_class)
    evaluation = eval_cls() if isinstance(eval_cls, type) else eval_cls
    if generator_class:
        gen_cls = resolve_symbol(generator_class)
        generator = gen_cls() if isinstance(gen_cls, type) else gen_cls
    elif hasattr(evaluation, "engine_params_list"):
        generator = evaluation  # Evaluation doubling as generator, like upstream
    else:
        raise ValueError(
            "No engine params generator: pass generator_class or give the "
            "Evaluation an engine_params_list."
        )
    ctx = WorkflowContext(mesh_shape=parse_mesh_spec(mesh), seed=seed, batch=batch,
                          verbose=verbose)
    return CoreWorkflow.run_evaluation(
        evaluation,
        generator,
        ctx,
        evaluation_class=evaluation_class,
        generator_class=generator_class or evaluation_class,
    )
