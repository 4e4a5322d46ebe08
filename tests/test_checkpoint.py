"""Checkpoint/resume: manager round-trips, ALS per-epoch checkpointing,
and resume-after-interruption equivalence (SURVEY.md §5 'Checkpoint /
resume' — the rebuild's stronger contract vs the reference's
whole-model-after-train persistence)."""

import numpy as np
import pytest

from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.workflow.checkpoint import CheckpointManager
from tests.test_als import synth_ratings
from tests.test_als_digest import cores
from tests.test_bucket_cache import _digest_before_pr45


class TestCheckpointManager:
    def test_round_trip_nested_tree(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        tree = {
            "factors": {"user": np.arange(6, dtype=np.float32).reshape(2, 3),
                        "item": np.ones((3, 3))},
            "history": [np.float32(1.5), np.float32(0.7)],
            "step_count": np.int64(2),
        }
        cm.save(2, tree, metadata={"note": "hello"})
        restored, meta = cm.restore()
        assert meta["note"] == "hello"
        np.testing.assert_array_equal(restored["factors"]["user"],
                                      tree["factors"]["user"])
        np.testing.assert_array_equal(restored["factors"]["item"],
                                      tree["factors"]["item"])
        np.testing.assert_allclose([float(x) for x in restored["history"]],
                                   [1.5, 0.7], rtol=1e-6)
        assert int(restored["step_count"]) == 2

    def test_latest_and_gc(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=2)
        for step in (1, 2, 3, 4):
            cm.save(step, {"x": np.full((2,), step, dtype=np.float32)})
        assert cm.latest_step() == 4
        assert cm.all_steps() == [3, 4]  # keep=2 garbage-collects the rest
        restored, _ = cm.restore(3)
        assert restored["x"][0] == 3.0

    def test_restore_empty_raises(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        with pytest.raises(FileNotFoundError):
            cm.restore()

    def test_tuple_and_scalar_leaves(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, {"t": (np.zeros(2), np.ones(2))})
        restored, _ = cm.restore(1)
        assert isinstance(restored["t"], tuple)
        np.testing.assert_array_equal(restored["t"][1], np.ones(2))


class TestALSCheckpointResume:
    def test_checkpointed_matches_single_dispatch(self, tmp_path):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=3)
        cfg = ALSConfig(rank=4, iterations=4, reg=0.05, seed=7)
        base = als_train(ui, ii, r, 30, 20, cfg)
        ckpt = als_train(ui, ii, r, 30, 20, cfg,
                         checkpoint_dir=str(tmp_path), checkpoint_every=2)
        np.testing.assert_allclose(base.user_factors, ckpt.user_factors,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(base.item_factors, ckpt.item_factors,
                                   rtol=1e-4, atol=1e-5)
        cm = CheckpointManager(str(tmp_path))
        assert cm.latest_step() == 4

    def test_resume_continues_from_latest(self, tmp_path):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=4)
        full_cfg = ALSConfig(rank=4, iterations=6, reg=0.05, seed=9)
        # "interrupted" run: only 3 of 6 iterations, checkpointed
        partial = als_train(ui, ii, r, 30, 20,
                            ALSConfig(rank=4, iterations=3, reg=0.05, seed=9),
                            checkpoint_dir=str(tmp_path), checkpoint_every=1)
        assert CheckpointManager(str(tmp_path)).latest_step() == 3
        # re-run asking for the full 6: must resume at step 3, not restart
        resumed = als_train(ui, ii, r, 30, 20, full_cfg,
                            checkpoint_dir=str(tmp_path), checkpoint_every=1)
        uninterrupted = als_train(ui, ii, r, 30, 20, full_cfg)
        np.testing.assert_allclose(resumed.user_factors,
                                   uninterrupted.user_factors,
                                   rtol=1e-4, atol=1e-5)
        assert CheckpointManager(str(tmp_path)).latest_step() == 6
        # the resumed run only paid for the remaining epochs
        assert len(resumed.epoch_times) == 3

    def test_resume_rmse_history_concatenates(self, tmp_path):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=5)
        als_train(ui, ii, r, 30, 20,
                  ALSConfig(rank=4, iterations=2, reg=0.05, seed=1),
                  checkpoint_dir=str(tmp_path), compute_rmse=True)
        resumed = als_train(ui, ii, r, 30, 20,
                            ALSConfig(rank=4, iterations=5, reg=0.05, seed=1),
                            checkpoint_dir=str(tmp_path), compute_rmse=True)
        assert len(resumed.rmse_history) == 5
        # converging: later rmse no worse than the first
        assert resumed.rmse_history[-1] <= resumed.rmse_history[0] + 1e-6

    def test_changed_data_retrains_from_scratch(self, tmp_path):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=8)
        cfg = ALSConfig(rank=4, iterations=2, reg=0.05, seed=3)
        stale = als_train(ui, ii, r, 30, 20, cfg, checkpoint_dir=str(tmp_path))
        # nightly retrain with new ratings into the same dir: the completed
        # checkpoint must NOT be returned as the new model
        r2 = r.copy()
        r2[0] += 2.0
        fresh = als_train(ui, ii, r2, 30, 20, cfg, checkpoint_dir=str(tmp_path))
        direct = als_train(ui, ii, r2, 30, 20, cfg)
        np.testing.assert_allclose(fresh.user_factors, direct.user_factors,
                                   rtol=1e-4, atol=1e-5)
        assert not np.allclose(fresh.user_factors, stale.user_factors)
        assert len(fresh.epoch_times) == 2

    def test_a_checkpoint_fingerprinted_by_an_older_builds_digest_is_not_resumed(
            self, tmp_path, caplog):
        """PR 45's tree digest changed every fingerprint: a checkpoint an
        older build wrote reads as another run's and the train starts from
        iteration 0, the safe side, and says why."""
        import hashlib
        import json
        import logging

        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=9)
        cfg = ALSConfig(rank=4, iterations=2, reg=0.05, seed=4)
        first = als_train(ui, ii, r, 30, 20, cfg, checkpoint_dir=str(tmp_path))
        meta_path = tmp_path / "step_2" / "meta.json"
        meta = json.loads(meta_path.read_text())
        todays = meta["metadata"]["fingerprint"]
        meta["metadata"]["fingerprint"] = hashlib.blake2b(
            (_digest_before_pr45(ui, ii, r)
             + repr((30, 20, cfg.rank, cfg.reg, cfg.weighted_reg,
                     cfg.implicit, cfg.alpha, cfg.seed, cfg.dtype))).encode(),
            digest_size=8).hexdigest()
        assert meta["metadata"]["fingerprint"] != todays
        meta_path.write_text(json.dumps(meta))
        with caplog.at_level(logging.WARNING, "predictionio_tpu.ops.als"):
            again = als_train(ui, ii, r, 30, 20, cfg,
                              checkpoint_dir=str(tmp_path))
        assert any("older build" in m and "training from scratch" in m
                   for m in caplog.messages)
        assert len(again.epoch_times) == 2  # both iterations run anew
        np.testing.assert_allclose(first.user_factors, again.user_factors,
                                   rtol=1e-4, atol=1e-5)
        # and the checkpoint it then wrote is today's again
        assert json.loads(meta_path.read_text())["metadata"][
            "fingerprint"] == todays

    @pytest.mark.parametrize("wrote_on,resumed_on", [(1, 8), (8, 1), (2, 13)])
    def test_a_checkpoint_resumes_on_a_host_of_another_core_count(
            self, tmp_path, monkeypatch, wrote_on, resumed_on):
        """The fingerprint holds the data digest, whose value no thread
        or core count enters: leaves of 64 bytes here, so that the hosts
        with cores to spare do hash them on threads."""
        from predictionio_tpu.ops import als

        monkeypatch.setattr(als, "_DIGEST_LEAF_BYTES", 64)
        counted = als.DIGEST_CALLS.labels(path="parallel")
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=4)
        paths = []
        for n_cores, iterations in ((wrote_on, 3), (resumed_on, 5)):
            cores(monkeypatch, n_cores)
            before = counted.value
            out = als_train(ui, ii, r, 30, 20,
                            ALSConfig(rank=4, iterations=iterations,
                                      reg=0.05, seed=9),
                            checkpoint_dir=str(tmp_path), checkpoint_every=1)
            paths.append(counted.value - before)
        assert paths == [float(wrote_on > 1), float(resumed_on > 1)]
        assert len(out.epoch_times) == 2  # resumed at step 3
        assert CheckpointManager(str(tmp_path)).latest_step() == 5

    def test_fully_resumed_run_returns_model_without_training(self, tmp_path):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=9)
        cfg = ALSConfig(rank=4, iterations=2, reg=0.05, seed=4)
        first = als_train(ui, ii, r, 30, 20, cfg, checkpoint_dir=str(tmp_path))
        again = als_train(ui, ii, r, 30, 20, cfg, checkpoint_dir=str(tmp_path))
        np.testing.assert_allclose(first.user_factors, again.user_factors)
        assert again.epoch_times == []  # no iterations executed

    def test_checkpoint_every_zero_does_not_hang(self, tmp_path):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=10)
        out = als_train(ui, ii, r, 30, 20,
                        ALSConfig(rank=4, iterations=2, reg=0.05, seed=5),
                        checkpoint_dir=str(tmp_path), checkpoint_every=0)
        assert np.isfinite(out.user_factors).all()

    def test_stale_higher_steps_purged_on_data_change(self, tmp_path):
        # a previous 6-iter run's leftovers must not shadow a new shorter
        # run's saves (the retention GC keeps the HIGHEST steps)
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=11)
        als_train(ui, ii, r, 30, 20,
                  ALSConfig(rank=4, iterations=6, reg=0.05, seed=6),
                  checkpoint_dir=str(tmp_path))
        r2 = r.copy()
        r2[0] += 1.0
        als_train(ui, ii, r2, 30, 20,
                  ALSConfig(rank=4, iterations=3, reg=0.05, seed=6),
                  checkpoint_dir=str(tmp_path))
        cm = CheckpointManager(str(tmp_path))
        assert cm.all_steps() == [1, 2, 3]  # old 4..6 gone, new saves kept
        # an interrupted re-run of the new config can actually resume
        resumed = als_train(ui, ii, r2, 30, 20,
                            ALSConfig(rank=4, iterations=3, reg=0.05, seed=6),
                            checkpoint_dir=str(tmp_path))
        assert resumed.epoch_times == []

    def test_fewer_iterations_than_checkpoint_retrains_to_target(self, tmp_path):
        # completed 6-iter checkpoint; asking for 3 must NOT return the
        # over-trained 6-iter factors
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=12)
        als_train(ui, ii, r, 30, 20,
                  ALSConfig(rank=4, iterations=6, reg=0.05, seed=7),
                  checkpoint_dir=str(tmp_path), checkpoint_every=2)
        shorter = als_train(ui, ii, r, 30, 20,
                            ALSConfig(rank=4, iterations=3, reg=0.05, seed=7),
                            checkpoint_dir=str(tmp_path), checkpoint_every=2)
        direct = als_train(ui, ii, r, 30, 20,
                           ALSConfig(rank=4, iterations=3, reg=0.05, seed=7))
        np.testing.assert_allclose(shorter.user_factors, direct.user_factors,
                                   rtol=1e-4, atol=1e-5)

    def test_resumed_metric_steps_continue_numbering(self, tmp_path):
        # start_epoch lets callers label resumed epochs correctly
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=13)
        als_train(ui, ii, r, 30, 20,
                  ALSConfig(rank=4, iterations=2, reg=0.05, seed=8),
                  checkpoint_dir=str(tmp_path))
        resumed = als_train(ui, ii, r, 30, 20,
                            ALSConfig(rank=4, iterations=5, reg=0.05, seed=8),
                            checkpoint_dir=str(tmp_path))
        assert resumed.start_epoch == 2
        assert len(resumed.epoch_times) == 3

    def test_mismatched_shapes_ignored(self, tmp_path):
        ui, ii, r, _ = synth_ratings(n_users=30, n_items=20, seed=6)
        als_train(ui, ii, r, 30, 20, ALSConfig(rank=4, iterations=1, seed=2),
                  checkpoint_dir=str(tmp_path))
        # different rank: stale checkpoint must not be loaded
        out = als_train(ui, ii, r, 30, 20, ALSConfig(rank=6, iterations=2, seed=2),
                        checkpoint_dir=str(tmp_path))
        assert out.user_factors.shape == (30, 6)


class TestWorkflowCheckpointWiring:
    def test_context_algorithm_dir(self, tmp_path):
        from predictionio_tpu.controller.context import WorkflowContext

        ctx = WorkflowContext(checkpoint_dir=str(tmp_path))
        d = ctx.algorithm_checkpoint_dir("als")
        assert d is not None and d.endswith("als")
        assert WorkflowContext().algorithm_checkpoint_dir("als") is None


class TestSegmentedTrainers:
    """VERDICT r4 missing #1: the ALS checkpoint contract generalized to
    the W2V SGNS loop and LogReg's Adam scan (workflow/segmented.py).
    The bar is IDENTITY: chunked, killed-and-resumed, and extended runs
    must reproduce the single-dispatch result bit for bit — the carry
    (params+opt state / embeddings+PRNG key) fully captures trainer
    state."""

    def _xy(self, seed=0, n=240, d=12, c=3):
        rng = np.random.default_rng(seed)
        return (rng.normal(size=(n, d)).astype(np.float32),
                rng.integers(0, c, n))

    def _docs(self):
        return [["the", "cat", "sat", "on", "mat"],
                ["dog", "ate", "cat", "food"],
                ["the", "dog", "sat"]] * 15

    def _w2v_cfg(self):
        from predictionio_tpu.ops.text import Word2VecConfig

        return Word2VecConfig(dim=8, steps=30, batch_size=32, negatives=3,
                              seed=3)

    def test_logreg_chunked_matches_single_dispatch(self, tmp_path):
        from predictionio_tpu.ops.classify import logreg_train

        x, y = self._xy()
        base = logreg_train(x, y, 3, iterations=40)
        chunked = logreg_train(x, y, 3, iterations=40,
                               checkpoint_dir=str(tmp_path),
                               checkpoint_every=7)
        np.testing.assert_array_equal(chunked.weights, base.weights)
        np.testing.assert_array_equal(chunked.bias, base.bias)
        assert chunked.loss_history == base.loss_history

    def test_logreg_resume_and_extend(self, tmp_path):
        from predictionio_tpu.ops.classify import logreg_train

        x, y = self._xy(1)
        base = logreg_train(x, y, 3, iterations=40)
        # partial run (20 iters) then an extended re-run to 40: resumes
        # at 20 and lands exactly on the uninterrupted 40-iter result
        logreg_train(x, y, 3, iterations=20,
                     checkpoint_dir=str(tmp_path), checkpoint_every=10)
        got = logreg_train(x, y, 3, iterations=40,
                           checkpoint_dir=str(tmp_path), checkpoint_every=10)
        np.testing.assert_array_equal(got.weights, base.weights)
        assert got.loss_history == base.loss_history  # prefix restored

    def test_logreg_changed_data_retrains(self, tmp_path, caplog):
        import logging

        from predictionio_tpu.ops.classify import logreg_train

        x, y = self._xy(2)
        logreg_train(x, y, 3, iterations=10,
                     checkpoint_dir=str(tmp_path), checkpoint_every=5)
        x2 = x + 1.0  # new data, same shapes
        base = logreg_train(x2, y, 3, iterations=10)
        with caplog.at_level(logging.WARNING):
            got = logreg_train(x2, y, 3, iterations=10,
                               checkpoint_dir=str(tmp_path),
                               checkpoint_every=5)
        np.testing.assert_array_equal(got.weights, base.weights)
        assert any("different data/config" in r.message
                   for r in caplog.records)

    def test_logreg_default_saves_once_at_end(self, tmp_path):
        from predictionio_tpu.ops.classify import logreg_train

        logreg_train(*self._xy(3), 3, iterations=12,
                     checkpoint_dir=str(tmp_path))
        assert CheckpointManager(str(tmp_path)).all_steps() == [12]

    def test_w2v_chunked_matches_single_dispatch(self, tmp_path):
        from predictionio_tpu.ops.text import word2vec_train

        docs, cfg = self._docs(), self._w2v_cfg()
        base = word2vec_train(docs, cfg)
        chunked = word2vec_train(docs, cfg, checkpoint_dir=str(tmp_path),
                                 checkpoint_every=7)
        np.testing.assert_array_equal(chunked.vectors, base.vectors)
        assert chunked.vocab == base.vocab

    def test_w2v_resume_continues_sampling_sequence(self, tmp_path):
        """The checkpointed carry includes the step PRNG key, so a
        resumed run samples the exact batches the uninterrupted run
        would have — asserted by bitwise identity of the final
        embeddings."""
        import dataclasses as dc

        from predictionio_tpu.ops.text import word2vec_train

        docs, cfg = self._docs(), self._w2v_cfg()
        base = word2vec_train(docs, cfg)
        partial = dc.replace(cfg, steps=14)
        word2vec_train(docs, partial, checkpoint_dir=str(tmp_path),
                       checkpoint_every=7)
        got = word2vec_train(docs, cfg, checkpoint_dir=str(tmp_path),
                             checkpoint_every=7)
        np.testing.assert_array_equal(got.vectors, base.vectors)

    def test_w2v_changed_config_retrains(self, tmp_path):
        import dataclasses as dc

        from predictionio_tpu.ops.text import word2vec_train

        docs, cfg = self._docs(), self._w2v_cfg()
        word2vec_train(docs, cfg, checkpoint_dir=str(tmp_path),
                       checkpoint_every=10)
        cfg2 = dc.replace(cfg, learning_rate=0.01)
        base = word2vec_train(docs, cfg2)
        got = word2vec_train(docs, cfg2, checkpoint_dir=str(tmp_path),
                             checkpoint_every=10)
        np.testing.assert_array_equal(got.vectors, base.vectors)


class TestSegmentedFuzz:
    """Property fuzz of the generic segmented-dispatch machinery
    (workflow/segmented.py, round 5): for RANDOM (total_steps,
    checkpoint_every, interruption point) the resumed run must land on
    the uninterrupted result exactly, with the metric history covering
    every absolute step exactly once. The toy trainer is a blake2 hash
    chain — any skipped, repeated, or re-ordered step changes the final
    digest, so identity is a strict execution-order proof."""

    @staticmethod
    def _toy(fingerprint="toyfp"):
        import hashlib

        def init_state():
            return b"genesis"

        def run_chunk(state, n_steps, done):
            metrics = []
            for k in range(n_steps):
                state = hashlib.blake2b(
                    state + str(done + k).encode(), digest_size=16).digest()
                metrics.append(float(state[0]))
            return state, metrics

        return dict(
            init_state=init_state,
            run_chunk=run_chunk,
            state_to_host=lambda s: {"state": np.frombuffer(s, np.uint8)},
            state_from_host=lambda t: t["state"].tobytes(),
            fingerprint=fingerprint,
        )

    def test_random_interruptions_resume_to_identity(self, tmp_path):
        from predictionio_tpu.workflow.segmented import segmented_train

        rng = np.random.default_rng(42)
        for trial in range(25):
            total = int(rng.integers(1, 13))
            every = int(rng.integers(1, total + 3))
            partial = int(rng.integers(0, total + 1))
            ckpt = str(tmp_path / f"t{trial}")
            toy = self._toy()
            want, want_hist, _ = segmented_train(
                total_steps=total, checkpoint_dir=None, **toy)
            if partial:
                segmented_train(total_steps=partial, checkpoint_dir=ckpt,
                                checkpoint_every=every, **toy)
            got, hist, start = segmented_train(
                total_steps=total, checkpoint_dir=ckpt,
                checkpoint_every=every, **toy)
            label = (f"trial {trial}: total={total} every={every} "
                     f"partial={partial} start={start}")
            assert got == want, label
            assert len(hist) == total, label
            assert hist == want_hist, label
            # a third, fully-resumed run returns without recomputing
            again, hist2, start2 = segmented_train(
                total_steps=total, checkpoint_dir=ckpt,
                checkpoint_every=every, **toy)
            assert again == want and start2 == total, label
            assert hist2 == want_hist, label

    def test_fingerprint_change_restarts(self, tmp_path):
        from predictionio_tpu.workflow.segmented import segmented_train

        toy_a = self._toy("fpA")
        segmented_train(total_steps=6, checkpoint_dir=str(tmp_path),
                        checkpoint_every=2, **toy_a)
        toy_b = self._toy("fpB")
        want, _, _ = segmented_train(total_steps=6, checkpoint_dir=None,
                                     **toy_b)
        got, hist, start = segmented_train(
            total_steps=6, checkpoint_dir=str(tmp_path),
            checkpoint_every=2, **toy_b)
        assert got == want and start == 0 and len(hist) == 6
