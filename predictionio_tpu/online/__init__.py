"""Online-learning plane: event → servable in seconds, beyond retrain.

The batch world (ROADMAP item 2's "freshness still means retrain") ends
here: a `StoreTailer` in batch mode feeds fresh rating events to each
variant's fold handles (`FoldModel` — `ALSFold` runs one `ops/als.py`
half-epoch restricted to the dirty rows with cold-start rows appended
for never-seen ids; `SessionFold` rebuilds the dirty users' session
windows and embeddings for the sessionrec family) and a `DeltaSwapper`
publishes the folded models into the serving plane's immutable
served-state table per variant, invalidating only the touched users'
cache entries. See docs/online.md for architecture, knobs, the
second-model-family contract, and the parity-drift runbook;
`quality.py --online-gate` drills freshness, crash recovery, session
folds, and full-retrain parity in CI.
"""

from predictionio_tpu.online.foldin import (  # noqa: F401
    ALSFold,
    FoldModel,
    FoldStats,
    SeenOverlay,
    fold_model,
    solve_rows,
)
from predictionio_tpu.online.plane import (  # noqa: F401
    DeviceUnavailable,
    OnlineConfig,
    OnlinePlane,
)
from predictionio_tpu.online.session import SessionFold  # noqa: F401
from predictionio_tpu.online.swap import DeltaSwapper, StaleState  # noqa: F401

__all__ = [
    "ALSFold", "DeltaSwapper", "DeviceUnavailable", "FoldModel", "FoldStats",
    "OnlineConfig", "OnlinePlane", "SeenOverlay", "SessionFold", "StaleState",
    "fold_model", "solve_rows",
]
