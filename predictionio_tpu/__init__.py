"""predictionio_tpu — a TPU-native ML-server framework.

Reproduces the capability surface of PredictionIO (reference:
chien146/PredictionIO, a fork of Apache PredictionIO — see SURVEY.md): the
DASE engine abstraction, an event server, engine.json-parameterized engine
templates, the `pio` CLI lifecycle, metadata/model/event storage, and an
HTTP prediction server — re-designed TPU-first on JAX/XLA/pjit/Pallas
instead of Scala/Spark.

Layering (bottom → top), mirroring SURVEY.md §1:

    predictionio_tpu.data       event model (Event, DataMap, PropertyMap, BiMap)
    predictionio_tpu.storage    storage registry + SQLite/memory/localfs backends
    predictionio_tpu.ops        jitted XLA/Pallas compute kernels (ALS, logreg, ...)
    predictionio_tpu.parallel   mesh / sharding / collectives / multi-host init
    predictionio_tpu.models     model pytrees + checkpoint helpers
    predictionio_tpu.controller DASE public API (Engine, DataSource, Algorithm, ...)
    predictionio_tpu.workflow   train/eval/serve runtimes (CoreWorkflow, CreateServer)
    predictionio_tpu.templates  built-in engine templates (recommendation, ...)
    predictionio_tpu.tools      `pio-tpu` CLI console, import/export, dashboard

Heavy deps (jax) are imported lazily by the modules that need them, so the
storage/event layers remain usable in processes that never touch a device.
"""

import time as _time

_T_IMPORT = _time.perf_counter()

__version__ = "0.1.0"

import os as _os

if _os.environ.get("PIO_LOCKSAN"):
    # opt-in lock-order sanitizer: patch threading.Lock/RLock before
    # any plane module creates its locks (utils/locksan.py)
    from predictionio_tpu.utils import locksan as _locksan
    _locksan.maybe_install()

from predictionio_tpu.data.events import Event  # noqa: F401
from predictionio_tpu.data.datamap import DataMap, PropertyMap  # noqa: F401
from predictionio_tpu.data.bimap import BiMap  # noqa: F401

# first line to last on `time.perf_counter()`: the compile log's first
# `process.import` record, once telemetry/device.py is imported
IMPORT_SPAN = (_T_IMPORT, _time.perf_counter())
