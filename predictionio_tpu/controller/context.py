"""WorkflowContext — the rebuild's SparkContext analogue.

Parity with «core/.../workflow/WorkflowContext» (SURVEY.md §2.1 [U]): where
the reference builds a `SparkConf`/`SparkContext` and threads it through
every DASE call, we thread a context carrying the JAX device mesh, a PRNG
seed, workflow params, and storage access. jax is imported lazily so
storage-only processes (event server, CLI metadata verbs) never pay for it.
"""

from __future__ import annotations

import contextlib
import logging
import sys
from typing import TYPE_CHECKING, Any, Optional

from predictionio_tpu.telemetry import device as device_telemetry

if TYPE_CHECKING:
    import jax

log = logging.getLogger(__name__)


class WorkflowContext:
    def __init__(
        self,
        mesh_shape: Optional[dict[str, int]] = None,
        seed: int = 0,
        batch: str = "",
        verbose: int = 0,
        storage: Optional[Any] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: Optional[int] = None,
        metrics: Optional[Any] = None,
    ):
        """Args:
        mesh_shape: axis name → size, e.g. ``{"data": 4, "model": 2}``.
            None = use all local devices on the ``data`` axis.
        seed: base PRNG seed for all algorithms in this run.
        batch: human-readable run label (the reference's `--batch`).
        verbose: debug verbosity (the reference's WorkflowParams.verbose).
        storage: Storage registry override (defaults to the process one).
        checkpoint_dir: when set, algorithms checkpoint trainer state here
            every `checkpoint_every` of their own step unit (ALS: epochs;
            W2V/LogReg: scan iterations) and resume from the latest step
            on re-run (SURVEY.md §5 'Checkpoint / resume').
        checkpoint_every: None = each algorithm picks its own default
            (ALS every epoch; step-loop trainers ~10 saves per run —
            `checkpoint_every_or`); an explicit value applies verbatim.
        metrics: a `utils.profiling.MetricsLogger` for per-epoch metric
            emission (default: log-only).
        """
        # where the caller brought jax in itself (a library's, the
        # benchmark's), programs are built from here on: the compile log
        # listens before the first
        if "jax" in sys.modules:
            device_telemetry.listen()
        self.mesh_shape = mesh_shape
        self.seed = seed
        self.batch = batch
        self.verbose = verbose
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        # set by Engine.train/eval around each algorithm's train: ".N"
        # for the N-th duplicate of an algorithm class in one engine, so
        # same-class entries (legal in engine.json, «algorithmClassMap»
        # [U]) don't share — and purge — one checkpoint subdir
        self.algo_ckpt_suffix = ""
        self._metrics = metrics
        self._storage = storage
        self._mesh: Optional["jax.sharding.Mesh"] = None

    @property
    def metrics(self):
        if self._metrics is None:
            from predictionio_tpu.utils.profiling import NullMetricsLogger

            self._metrics = NullMetricsLogger()
        return self._metrics

    def checkpoint_every_or(self, default: int) -> int:
        """`--checkpoint-every` when the user passed one, else the
        algorithm's own sensible default (its step unit varies: ALS
        epochs are seconds each so every-1 is right; a 200-iteration
        Adam scan at every-1 would be 200 dispatches + saves)."""
        return self.checkpoint_every if self.checkpoint_every else default

    @contextlib.contextmanager
    def algo_checkpoint_scope(self, suffix: str):
        """Scoped override of `algo_ckpt_suffix` — the ONE way callers
        that train algorithm instances mark which instance is running,
        so collision-freedom is structural rather than a set/reset pair
        every site must remember."""
        prev = self.algo_ckpt_suffix
        self.algo_ckpt_suffix = suffix
        try:
            yield
        finally:
            self.algo_ckpt_suffix = prev

    def algorithm_checkpoint_dir(self, algo_name: str) -> Optional[str]:
        """Per-algorithm checkpoint subdirectory (None when disabled).
        `algo_name` is the algorithm's own tag (an algorithm may use
        several — the text template checkpoints `w2v` and `w2v-head`);
        `algo_ckpt_suffix` disambiguates duplicate same-class entries."""
        if not self.checkpoint_dir:
            return None
        import os

        return os.path.join(self.checkpoint_dir,
                            algo_name + self.algo_ckpt_suffix)

    def algorithm_cache_dir(self, algo_name: str) -> Optional[str]:
        """Per-algorithm on-disk cache directory for derived training
        inputs (e.g. the ALS bucketize result — VERDICT r2 #5). Lives
        under the storage basedir so re-running `pio train` in a fresh
        process hits it; PIO_BUCKET_CACHE=0 disables."""
        import os

        from predictionio_tpu.utils.fs import fs_basedir

        if os.environ.get("PIO_BUCKET_CACHE", "1") == "0":
            return None
        return os.path.join(fs_basedir(), "cache", algo_name)

    @property
    def storage(self):
        if self._storage is None:
            from predictionio_tpu.storage.registry import Storage

            self._storage = Storage.get()
        return self._storage

    @property
    def mesh(self) -> "jax.sharding.Mesh":
        """The device mesh, built on first use (SURVEY.md §2.6/§2.7: axes
        `data` and `model` are the two parallelism dimensions PredictionIO
        capability parity needs).

        Shape resolution: the explicit `mesh_shape` (the `--mesh` flag),
        else `PIO_MESH_SHAPE` (the pod-level env contract in
        parallel/distributed.py — how config 5's data×model shape reaches
        `pio train` without per-command flags), else all devices on
        `data`."""
        if self._mesh is None:
            from predictionio_tpu.parallel.distributed import global_mesh

            self._mesh = global_mesh(self.mesh_shape)
        return self._mesh

    def rng(self, salt: int = 0) -> "jax.Array":
        import jax

        return jax.random.key(self.seed + salt)

    def __repr__(self) -> str:
        return (
            f"WorkflowContext(mesh_shape={self.mesh_shape}, seed={self.seed}, "
            f"batch={self.batch!r})"
        )
