"""Failure-path e2e, elastic recovery (moved whole from
`test_failure_paths.py`, whose docstring says what the drills are): a rank
killed mid-train, the world re-formed at its size; a rank's and the
coordinator's death seen by the rest."""

import sqlite3

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

    def test_kill_worker_reform_world_resume_matches(self, tmp_path):
        # reference: uninterrupted 4-rank world on identically-seeded data
        db_ref = tmp_path / "ref.db"
        _seed_world_db(db_ref, "ElasticApp")
        ej_ref = tmp_path / "engine_ref.json"
        _world_engine_json(ej_ref, "ElasticApp", "elastic")
        rcs, outs = _four_rank_train(tmp_path, db_ref, ej_ref,
                                     tmp_path / "ckpt_ref")
        assert rcs == [0, 0, 0, 0], outs
        ref_uf, ref_if = _load_model_factors(db_ref, ej_ref)

        # crash world: rank 2 hard-dies at the 2nd epoch boundary
        db = tmp_path / "crash.db"
        _seed_world_db(db, "ElasticApp")
        ej = tmp_path / "engine.json"
        _world_engine_json(ej, "ElasticApp", "elastic")
        ckpt = tmp_path / "ckpt"
        rcs, outs = _four_rank_train(
            tmp_path, db, ej, ckpt,
            faults_by_rank={2: "als.epoch_boundary:2"})
        assert rcs[2] == 137, outs[2]  # the injected death
        for pid in (0, 1, 3):  # survivors fail FAST and nonzero — no hang
            assert rcs[pid] != 0, outs[pid]

        # rank 0 published steps 1 and 2 before the world died
        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        assert CheckpointManager(str(ckpt / "als")).latest_step() == 2

        # re-form the world: resumes from step 2, completes, and matches
        # the uninterrupted reference exactly
        rcs, outs = _four_rank_train(tmp_path, db, ej, ckpt)
        assert rcs == [0, 0, 0, 0], outs
        assert "resumed from checkpoint step 2" in outs[0]
        got_uf, got_if = _load_model_factors(db, ej)
        np.testing.assert_allclose(got_uf, ref_uf, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got_if, ref_if, rtol=1e-5, atol=1e-6)

    def test_eight_process_rank_death_fails_world_fast(self, tmp_path):
        """The failure matrix at EIGHT processes (VERDICT r3 #7): rank 5
        of an 8-rank CLI train hard-dies at the first epoch boundary;
        all seven survivors must exit nonzero in bounded time — no hangs
        at the doubled world size."""
        db = tmp_path / "oct.db"
        _seed_world_db(db, "OctFailApp")
        ej = tmp_path / "engine.json"
        _world_engine_json(ej, "OctFailApp", "octfail")
        from tests.test_distributed_multihost import _run_world_train

        rcs, outs = _run_world_train(
            ej, db, tmp_path, n_ranks=8, dev_per_rank=1,
            extra_env={"PIO_LOG_LEVEL": "INFO",
                       "PIO_COORDINATOR_TIMEOUT_S": "60"},
            faults_by_rank={5: "als.epoch_boundary:1"},
            extra_args=("--checkpoint-dir", str(tmp_path / "ckpt"),
                        "--checkpoint-every", "1"),
            check=False, timeout=600)
        assert rcs[5] == 137, outs[5]
        for pid in (0, 1, 2, 3, 4, 6, 7):
            assert rcs[pid] != 0, f"rank {pid} exited 0: {outs[pid][-300:]}"

    def test_coordinator_death_releases_world(self, tmp_path):
        """Rank 0 hosts the jax.distributed coordinator AND is the only
        persisting rank; its death must fail every non-zero rank within
        bounded time (heartbeat loss), not strand them."""
        db = tmp_path / "coord.db"
        _seed_world_db(db, "CoordApp")
        ej = tmp_path / "engine.json"
        _world_engine_json(ej, "CoordApp", "coord")
        rcs, outs = _four_rank_train(
            tmp_path, db, ej, tmp_path / "ckpt_c",
            faults_by_rank={0: "als.epoch_boundary:2"}, timeout=240)
        assert rcs[0] == 137, outs[0]
        for pid in (1, 2, 3):
            assert rcs[pid] != 0, outs[pid]
        # no COMPLETED instance exists — rank 0 died before persisting
        conn = sqlite3.connect(db)
        n = conn.execute("SELECT count(*) FROM engine_instances "
                         "WHERE status='COMPLETED'").fetchone()[0]
        conn.close()
        assert n == 0
