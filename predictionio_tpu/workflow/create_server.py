"""CreateServer — the `pio deploy` prediction server.

Parity with «core/.../workflow/CreateServer.scala :: CreateServer,
MasterActor, ServerActor» (SURVEY.md §3.2 [U]): load the latest COMPLETED
EngineInstance, rebuild typed engine params from the stored instance row,
deserialize models, and serve:

    POST /queries.json  {"user": "1", "num": 4}  → PredictedResult JSON
    GET  /              → status page (engine info, instance id)
    POST /reload        → hot-swap to the newest COMPLETED instance
    POST /stop          → shut the server down

The reference supervises ServerActor with a MasterActor and hot-reloads on
re-deploy; here the served state is one immutable tuple swapped atomically
on /reload, and components are resolved once per load (not per query — the
query path is reflection-free).
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Any, Optional

from predictionio_tpu.experiment import (
    ExperimentConfig,
    RewardTailer,
    VariantRouter,
)
from predictionio_tpu.online import (
    DeviceUnavailable,
    OnlineConfig,
    OnlinePlane,
)
from predictionio_tpu.plugins import PluginRejection
from predictionio_tpu.serving import (
    DeadlineExceeded,
    ServingConfig,
    ServingPlane,
    ShedLoad,
)
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.utils import fastjson
from predictionio_tpu.utils.faults import FaultInjected
from predictionio_tpu.utils.http import HttpService
from predictionio_tpu.utils.routing import Request, Response, Router

from predictionio_tpu.storage.base import EngineInstance
from predictionio_tpu.storage.registry import Storage
from predictionio_tpu.workflow.workflow_utils import (
    EngineVariant,
    extract_engine_params,
    get_engine,
)

log = logging.getLogger(__name__)

# The query hot path, separated from the HTTP envelope so engine time is
# distinguishable from request parsing/serialization in one scrape.
PREDICT_SECONDS = REGISTRY.histogram(
    "engine_predict_seconds",
    "Engine predict dispatch latency in seconds (one observation per "
    "batched dispatch; serving_batch_size gives queries per dispatch)")
QUERIES_FAILED = REGISTRY.counter(
    "engine_queries_failed_total", "Queries answered with a non-200 status")


class ServerConfig:
    def __init__(
        self,
        ip: str = "0.0.0.0",
        port: int = 8000,
        engine_id: str = "default",
        engine_version: str = "1",
        engine_variant: str = "default",
    ):
        self.ip = ip
        self.port = port
        self.engine_id = engine_id
        self.engine_version = engine_version
        self.engine_variant = engine_variant


class _ServedState:
    """Everything needed to answer queries — swapped atomically on reload."""

    def __init__(self, engine, engine_params, components, models,
                 instance: EngineInstance):
        self.engine = engine
        self.engine_params = engine_params
        self.components = components
        self.models = models
        self.instance = instance


def _row_block(raw: Optional[str]) -> dict:
    """Decode a stored component block. Rows written since round 5 carry
    `{"name": ..., "params": {...}}` (the component name must survive
    the round trip — see workflow_utils.engine_params_to_json); older
    rows stored the bare params dict, still decoded as an unnamed
    block."""
    d = json.loads(raw or "{}")
    if isinstance(d, dict) and "params" in d and set(d) <= {"name", "params"}:
        return d
    return {"params": d}


def variant_from_instance(instance: EngineInstance) -> EngineVariant:
    """Rebuild an EngineVariant from the params JSON stored on the
    EngineInstance row (`pio deploy` reads the row, not engine.json —
    SURVEY.md §3.2)."""
    return EngineVariant.from_dict({
        "id": instance.engine_id,
        "variant": instance.engine_variant,
        "engineFactory": instance.engine_factory,
        "datasource": _row_block(instance.data_source_params),
        "preparator": _row_block(instance.preparator_params),
        "algorithms": json.loads(instance.algorithms_params or "[]") or [{}],
        "serving": _row_block(instance.serving_params),
    })


def load_served_state(
    storage: Storage, config: ServerConfig
) -> _ServedState:
    instances = storage.meta_engine_instances()
    instance = instances.get_latest_completed(
        config.engine_id, config.engine_version, config.engine_variant
    )
    if instance is None:
        raise RuntimeError(
            f"No completed engine instance found for engine "
            f"{config.engine_id!r} v{config.engine_version} "
            f"variant {config.engine_variant!r}. Run `pio-tpu train` first."
        )
    variant = variant_from_instance(instance)
    engine = get_engine(variant.engine_factory)
    engine_params = extract_engine_params(engine, variant)
    blob = storage.model_data_models().get(instance.id)
    if blob is None:
        raise RuntimeError(f"Model blob for instance {instance.id} is missing.")
    models = engine.deserialize_models(blob.models, instance.id, engine_params)
    components = engine.components(engine_params)
    log.info("Deployed engine instance %s (trained %s)", instance.id,
             instance.start_time)
    return _ServedState(engine, engine_params, components, models, instance)


class PredictionServer(HttpService):
    """One serving process. Under `pio deploy --workers N`
    (workflow/worker_pool.py) N of these run pre-forked on one
    SO_REUSEPORT-shared port; `supervisor_pid` is then set and the
    /reload//stop verbs fan out through the supervisor's signals so one
    HTTP request reaches every worker — the «MasterActor» supervision
    role [U] (SURVEY.md §3.2) made multi-process."""

    def __init__(self, config: ServerConfig, storage: Optional[Storage] = None,
                 plugins=None, reuse_port: bool = False,
                 supervisor_pid: Optional[int] = None,
                 serving_config: Optional[ServingConfig] = None,
                 experiment: Optional[ExperimentConfig] = None,
                 online: Optional[OnlineConfig] = None):
        from predictionio_tpu.plugins import load_plugins_from_env

        self.config = config
        self.storage = storage or Storage.get()
        self.plugins = (plugins if plugins is not None
                        else load_plugins_from_env())
        self.supervisor_pid = supervisor_pid
        self._state_lock = threading.Lock()

        # Experiment posture rides PIO_EXPERIMENT_* (like PIO_SERVING_*)
        # so every pre-fork pool worker resolves the same variant set.
        self.experiment = (experiment if experiment is not None
                           else ExperimentConfig.from_env())
        self._variants = (tuple(self.experiment.variants)
                          if self.experiment is not None
                          else (config.engine_variant,))
        self._primary_variant = self._variants[0]
        self._variant_header_cache = {v: {"X-PIO-Variant": v}
                                      for v in self._variants}
        self._states = {v: load_served_state(self.storage,
                                             self._config_for(v))
                        for v in self._variants}
        worker_pid = os.getpid()
        server = self

        # The serving planes (admission + micro-batching) outlive
        # reloads: each variant's dispatch reads server._states at
        # dispatch time, so a batch coalesced across a /reload simply
        # scores on whichever state is current — same snapshot semantics
        # the single-query path had.
        def _make_dispatch(v):
            def _dispatch(queries):
                state = server._states[v]
                with spans.span("predictionserver.predict"), \
                        PREDICT_SECONDS.time():
                    return state.engine.predict_batch(
                        state.engine_params, state.models, queries,
                        components=state.components)
            return _dispatch

        def _make_degraded(v):
            def _degraded(query):
                state = server._states[v]
                return state.engine.degraded_predict(
                    state.engine_params, state.models, query,
                    components=state.components)
            return _degraded

        serving_cfg = serving_config or ServingConfig.from_env()
        self._planes = {
            v: ServingPlane(
                _make_dispatch(v), degraded_fn=_make_degraded(v),
                config=serving_cfg, name="predictionserver", variant=v,
                app=self._resolve_tenant_app(v))
            for v in self._variants
        }
        self._tailer: Optional[RewardTailer] = None
        if self.experiment is not None:
            # one router in the ServingPlane-shaped slot: same
            # handle_query contract, per-variant planes behind it
            self.serving = VariantRouter(self._planes, self.experiment)
            if self.serving.bandit is not None:
                self._tailer = RewardTailer(
                    self.storage, self.serving.bandit,
                    app_id=self.experiment.app_id,
                    interval_s=self.experiment.tail_interval_s)
                self._tailer.start()
        else:
            self.serving = self._planes[self._primary_variant]
        self._worker_pid = worker_pid

        # Online-learning plane (opt-in, PIO_ONLINE=1): tails rating
        # events out of the durable store, folds the dirty factor rows,
        # and hot-swaps the served state per variant — bandit arms keep
        # learning mid-experiment. A plane that fails to start must not
        # take serving down: the server just stays batch-fresh. The one
        # exception is a process that cannot have its device: it must
        # not come up at all (a pool worker then never reports ready)
        # rather than serve answers no fold will ever refresh.
        self.online: Optional[OnlinePlane] = None
        online_cfg = online if online is not None else OnlineConfig.from_env()
        if online_cfg is not None:
            try:
                self.online = OnlinePlane(self, online_cfg)
                self.online.start()
            except DeviceUnavailable:
                if self._tailer is not None:
                    self._tailer.stop()
                self.serving.close()
                raise
            except Exception:  # noqa: BLE001
                log.exception("online plane failed to start; serving "
                              "continues without fold-in")
                self.online = None

        # Alert watchdog (opt-in, PIO_ALERTS=1): rules run against the
        # metrics history; firing/resolve edges become $alert events
        # through a dedicated group-commit writer into the event store.
        from predictionio_tpu.ingest import GroupCommitWriter, IngestConfig
        from predictionio_tpu.telemetry import alerts
        from predictionio_tpu.telemetry import history as metrics_history
        self._alert_writer: Optional[GroupCommitWriter] = None
        self.watchdog = alerts.AlertWatchdog.from_env(
            metrics_history.ensure_started(), source="predictionserver")
        if self.watchdog is not None:
            le = self.storage.l_events()
            self._alert_writer = GroupCommitWriter(
                insert_fn=le.insert, grouped_fn=le.insert_grouped,
                config=IngestConfig.from_env(), name="alerts")
            self.watchdog.emit = alerts.ingest_emitter(
                self._alert_writer,
                app_id=int(os.environ.get("PIO_ALERT_APP_ID", "0")))
            self.watchdog.start()

        # Route dispatch table, registered once at construction. The
        # query/reload/stop handlers block (device dispatch, storage
        # load), so the event loop runs them on its worker pool.
        router = Router()
        router.get("/", self._handle_status)
        router.post("/queries.json", self._handle_query, blocking=True)
        router.post("/reload", self._handle_reload, blocking=True)
        router.post("/stop", self._handle_stop, blocking=True)

        HttpService.__init__(self, config.ip, config.port,
                             router=router,
                             reuse_port=reuse_port,
                             server_name="predictionserver")

    def _resolve_tenant_app(self, variant: str) -> str:
        """The app id this variant's engine is bound to — the serving-side
        tenant root. PIO_TENANT_APP overrides; otherwise resolved from the
        served state's DataSource appName exactly like the online plane's
        context resolution. Empty string (unattributed) when neither
        resolves — serving must not fail over a missing tenant binding."""
        override = os.environ.get("PIO_TENANT_APP", "").strip()
        if override:
            return override
        try:
            state = self._states[variant]
            dsp = state.engine_params.data_source_params
            app_name = getattr(dsp, "appName", None)
            if not app_name:
                return ""
            app = self.storage.meta_apps().get_by_name(app_name)
            return str(app.id) if app is not None else ""
        except Exception:  # noqa: BLE001 — attribution is best-effort
            return ""

    def _config_for(self, variant: str) -> ServerConfig:
        return ServerConfig(
            ip=self.config.ip, port=self.config.port,
            engine_id=self.config.engine_id,
            engine_version=self.config.engine_version,
            engine_variant=variant)

    @property
    def _state(self) -> _ServedState:
        """Primary variant's served state (the only one outside
        experiment mode)."""
        return self._states[self._primary_variant]

    # -- route handlers ------------------------------------------------------
    def _handle_status(self, req: Request) -> Response:
        state = self._state
        payload = {
            "status": "alive",
            "engineId": self.config.engine_id,
            "engineVersion": self.config.engine_version,
            "engineVariant": self.config.engine_variant,
            "engineFactory": state.instance.engine_factory,
            "engineInstanceId": state.instance.id,
            "startTime": state.instance.start_time.isoformat(),
            # which pool worker answered — the observable receipt that
            # SO_REUSEPORT is really balancing
            "workerPid": self._worker_pid,
        }
        if self.experiment is not None:
            payload["experiment"] = dict(
                self.serving.snapshot(),
                instances={v: s.instance.id
                           for v, s in self._states.items()})
        if self.online is not None:
            payload["online"] = self.online.snapshot()
        return Response.json(200, payload)

    def _variant_headers(self, extra: Optional[dict] = None) -> Optional[dict]:
        """X-PIO-Variant on every experiment-mode response (200 and
        shed/deadline alike) — the client-observable assignment, and
        what the sticky-determinism drills read back. The no-extra case
        (every plain 200) reuses one shared dict per variant."""
        if self.experiment is not None:
            chosen = self.serving.last_variant
            if chosen:
                if not extra:
                    return self._variant_header_cache.get(chosen)
                headers = dict(extra)
                headers["X-PIO-Variant"] = chosen
                return headers
        return extra or None

    def _handle_query(self, req: Request) -> Response:
        retry_after = self.serving.config.admission.retry_after_s
        try:
            query = fastjson.loads(req.body or b"{}")
            result, degraded = self.serving.handle_query(
                query, req.headers)
            state = self._state
            if self.experiment is not None:
                # credit the prediction to the instance that produced it
                state = self._states.get(self.serving.last_variant, state)
            result = self.plugins.on_prediction(
                query, result, state.instance.id)
        except ShedLoad as e:
            # saturated and no degraded answer: an explicit, immediate
            # 429 beats queueing into collapse
            QUERIES_FAILED.inc()
            return Response.message(
                429, str(e), headers=self._variant_headers(
                    {"Retry-After": f"{e.retry_after_s:g}"}))
        except DeadlineExceeded as e:
            QUERIES_FAILED.inc()
            return Response.message(
                503, str(e), headers=self._variant_headers(
                    {"Retry-After": f"{retry_after:g}"}))
        except PluginRejection as e:
            QUERIES_FAILED.inc()
            return Response.message(403, str(e))
        except FaultInjected as e:
            # chaos-drill errors are server faults, not client ones: a
            # 500 spends SLO budget (a 400 would not), which is what the
            # supervisor's error-rate rule and the chaos gate watch for
            QUERIES_FAILED.inc()
            return Response.message(500, str(e))
        except Exception as e:
            QUERIES_FAILED.inc()
            log.warning("Query failed: %s", e)
            return Response.message(400, str(e))
        if degraded:
            headers = self._variant_headers({"X-PIO-Degraded": "1"})
        elif self.experiment is not None:
            headers = self._variant_header_cache.get(
                self.serving.last_variant)
        else:
            headers = None
        return Response(
            200, payload=result, encoder=fastjson.prediction_response,
            headers=headers)

    def _handle_reload(self, req: Request) -> Response:
        if self.supervisor_pid is not None:
            # pool mode: the kernel routed this request to ONE worker;
            # SIGHUP asks the supervisor for a ROLLING reload — each
            # worker (this one included) drains and swaps in turn, so
            # the pool never answers from zero workers mid-deploy
            import signal

            os.kill(self.supervisor_pid, signal.SIGHUP)
            return Response.message(
                200, "Rolling reload signaled to all workers")
        try:
            self.reload()
        except Exception as e:
            return Response.message(500, str(e))
        return Response.json(200, {
            "message": "Reloaded",
            "engineInstanceId": self._state.instance.id,
        })

    def _handle_stop(self, req: Request) -> Response:
        if self.supervisor_pid is not None:
            import signal

            resp = Response.message(200, "Shutting down all workers.")
            resp.on_sent = lambda: os.kill(self.supervisor_pid,
                                           signal.SIGTERM)
            return resp
        resp = Response.message(200, "Shutting down.")
        resp.on_sent = lambda: threading.Thread(
            target=self.shutdown, daemon=True).start()
        return resp

    def reload(self) -> None:
        """Swap every variant to its newest COMPLETED instance
        (idempotent, atomic per variant). Called from the /reload
        handler and, in pool mode, from the worker's SIGHUP handler.
        A variant whose reload fails keeps serving its current state —
        a half-trained challenger must not take down the champion."""
        errors = []
        with self._state_lock:
            for v in self._variants:
                try:
                    self._states[v] = load_served_state(
                        self.storage, self._config_for(v))
                except Exception as e:  # noqa: BLE001
                    log.exception("Reload failed for variant %s; keeping "
                                  "its current instance", v)
                    errors.append(e)
                    continue
                plane = self._planes.get(v)
                if plane is not None and plane.result_cache is not None:
                    # answers cached against the outgoing instance are
                    # stale the moment the swap lands
                    plane.result_cache.invalidate_variant(v)
                log.info("Reloaded engine instance %s (variant %s)",
                         self._states[v].instance.id, v)
        if errors and len(errors) == len(self._variants):
            raise errors[0]
        if self.online is not None:
            # outside the state lock: a fold pass holds its own lock
            # while swapping (which takes the state lock), so rebasing
            # under the state lock would deadlock against it. A fold
            # racing this reload is refused by the swapper's stale-state
            # check and replays against the new instances.
            self.online.rebase()

    def shutdown(self) -> None:
        """Graceful drain: the HTTP server stops accepting and finishes
        in-flight handlers first (their queued queries still dispatch),
        then the batcher's dispatcher thread is joined."""
        super().shutdown()
        if self.online is not None:
            self.online.stop()
        if self._tailer is not None:
            self._tailer.stop()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._alert_writer is not None:
            self._alert_writer.close()
        self.serving.close()

    def health_check(self) -> bool:
        """The drain-then-reload re-admission check: a worker re-enters
        the SO_REUSEPORT group only if it is actually able to serve —
        a served state is loaded and the `/metrics` exposition renders
        (the supervisor runbook's probe)."""
        if not self._states:
            return False
        from predictionio_tpu.telemetry import slo as _slo

        _slo.refresh()
        return bool(REGISTRY.render())

    @property
    def instance_id(self) -> str:
        return self._state.instance.id


def create_server(config: Optional[ServerConfig] = None,
                  storage: Optional[Storage] = None) -> PredictionServer:
    return PredictionServer(config or ServerConfig(), storage)
