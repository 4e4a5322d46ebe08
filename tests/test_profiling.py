"""Profiling subsystem: metrics JSONL emission, trace capture, debug
flags, and per-epoch emission through the train workflow (SURVEY.md §5
'Tracing / profiling' + 'Metrics / logging')."""

import json
import os

import numpy as np

from predictionio_tpu.utils.profiling import (
    MetricsLogger,
    NullMetricsLogger,
    maybe_trace,
    metered_jit,
)


class TestMetricsLogger:
    def test_jsonl_emission(self, tmp_path):
        path = str(tmp_path / "m" / "metrics.jsonl")
        with MetricsLogger(path, run="r1") as m:
            m.emit("train/als", step=1, rmse=0.9, epoch_time_s=0.01)
            m.emit("train/als", step=2, rmse=0.8, epoch_time_s=0.01)
            m.emit("eval", map_at_10=0.05)
        lines = [json.loads(x) for x in open(path)]
        assert len(lines) == 3
        assert lines[0]["run"] == "r1" and lines[0]["step"] == 1
        assert lines[1]["rmse"] == 0.8
        assert lines[2]["stage"] == "eval" and "step" not in lines[2]

    def test_append_across_sessions(self, tmp_path):
        path = str(tmp_path / "metrics.jsonl")
        with MetricsLogger(path) as m:
            m.emit("a", x=1)
        with MetricsLogger(path) as m:
            m.emit("b", x=2)
        assert len(open(path).readlines()) == 2

    def test_null_logger_no_file(self):
        m = NullMetricsLogger()
        rec = m.emit("train", step=1, loss=1.0)
        assert rec["loss"] == 1.0
        m.close()


class TestTrace:
    def test_noop_without_dir(self):
        with maybe_trace(None) as d:
            assert d is None

    def test_capture_creates_profile(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.telemetry.spans import span

        d = str(tmp_path / "prof")
        with maybe_trace(d):
            with span("test-span"):
                jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
        # TensorBoard layout: plugins/profile/<run>/ with at least one file
        prof_root = os.path.join(d, "plugins", "profile")
        assert os.path.isdir(prof_root)
        runs = os.listdir(prof_root)
        assert runs and os.listdir(os.path.join(prof_root, runs[0]))


class TestMeteredJit:
    def test_wrapper_exposes_the_jitted_callable(self):
        import jax

        f = metered_jit(lambda x: x + 1, label="metered_ok")
        assert hasattr(f, "jitted")
        assert int(f(jax.numpy.asarray(1))) == 2


class TestWorkflowMetricsWiring:
    def test_train_emits_per_epoch(self, memory_storage, tmp_path):
        from predictionio_tpu.controller.context import WorkflowContext
        from predictionio_tpu.templates.recommendation.engine import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            PreparedData,
        )
        from predictionio_tpu.data.bimap import BiMap

        path = str(tmp_path / "metrics.jsonl")
        users = [f"u{i}" for i in range(8)]
        items = [f"i{j}" for j in range(6)]
        rng = np.random.default_rng(0)
        n = 40
        ui = rng.integers(0, 8, n)
        ii = rng.integers(0, 6, n)
        pd = PreparedData(
            user_ids=BiMap.string_int(users),
            item_ids=BiMap.string_int(items),
            user_idx=ui.astype(np.int32),
            item_idx=ii.astype(np.int32),
            ratings=rng.uniform(1, 5, n).astype(np.float32),
        )
        with MetricsLogger(path) as metrics:
            ctx = WorkflowContext(metrics=metrics)
            algo = ALSAlgorithm(ALSAlgorithmParams(
                rank=4, numIterations=3, computeRMSE=True))
            algo.train(ctx, pd)
        lines = [json.loads(x) for x in open(path)]
        train_lines = [x for x in lines if x["stage"] == "train/als"]
        assert [x["step"] for x in train_lines] == [1, 2, 3]
        assert all("rmse" in x and "epoch_time_s" in x for x in train_lines)
