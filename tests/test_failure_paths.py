"""Failure-path e2e (VERDICT r1 #7; SURVEY.md §5 'Failure detection /
recovery / fault injection'): real processes hard-killed at the worst
moments via `utils/faults.py`, then recovery asserted.

- checkpoint crash: die between writing a checkpoint and publishing it;
  the previous step must survive and a resumed train must finish with
  factors identical to an uninterrupted run.
- batch-ingest crash: die between a batch INSERT's executemany and its
  commit; zero rows may land, and an identical replay must ingest exactly
  once.
- rank death: a missing rank must fail the surviving rank's bootstrap
  within the configured timeout, not hang.
"""

import json
import os
import pathlib
import socket
import sqlite3
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

TRAIN_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["PIO_TEST_REPO"])
    import numpy as np
    from predictionio_tpu.ops.als import ALSConfig, als_train

    rng = np.random.default_rng(0)
    ui = rng.integers(0, 60, 2000).astype(np.int32)
    ii = rng.integers(0, 40, 2000).astype(np.int32)
    r = rng.uniform(1, 5, 2000).astype(np.float32)
    res = als_train(ui, ii, r, 60, 40,
                    ALSConfig(rank=6, iterations=6, reg=0.1, seed=7),
                    checkpoint_dir=os.environ["PIO_TEST_CKPT"],
                    checkpoint_every=1)
    np.savez(os.environ["PIO_TEST_OUT"],
             uf=res.user_factors, itf=res.item_factors,
             start_epoch=res.start_epoch)
""")


def _run_train_worker(tmp_path, ckpt_dir, out_name, faults=""):
    worker = tmp_path / "train_worker.py"
    worker.write_text(TRAIN_WORKER)
    env = dict(os.environ)
    env.pop("PIO_CONF_DIR", None)
    env.update(PIO_TEST_REPO=str(REPO), PIO_TEST_CKPT=str(ckpt_dir),
               PIO_TEST_OUT=str(tmp_path / out_name), JAX_PLATFORMS="cpu")
    if faults:
        env["PIO_FAULTS"] = faults
    else:
        env.pop("PIO_FAULTS", None)
    return subprocess.run([sys.executable, str(worker)], env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.e2e
class TestCheckpointCrash:
    def test_kill_mid_train_then_resume_matches_uninterrupted(self, tmp_path):
        ckpt = tmp_path / "ckpt"
        # reference: uninterrupted run (separate dir)
        ref = _run_train_worker(tmp_path, tmp_path / "ckpt_ref", "ref.npz")
        assert ref.returncode == 0, ref.stderr

        # crash at the 3rd save attempt → steps 1 and 2 are on disk
        crashed = _run_train_worker(tmp_path, ckpt, "crash.npz",
                                    faults="checkpoint.pre_replace:3")
        assert crashed.returncode == 137, crashed.stderr
        assert "dying at checkpoint.pre_replace" in crashed.stderr

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        mgr = CheckpointManager(str(ckpt))
        assert mgr.latest_step() == 2  # step 3's tmp never published
        # the unpublished temp dir is litter, not a step
        assert any(n.startswith(".tmp_step_3") for n in os.listdir(ckpt))

        # resume: must start at epoch 2 and converge to the same factors
        resumed = _run_train_worker(tmp_path, ckpt, "resumed.npz")
        assert resumed.returncode == 0, resumed.stderr
        got = np.load(tmp_path / "resumed.npz")
        want = np.load(tmp_path / "ref.npz")
        assert int(got["start_epoch"]) == 2
        np.testing.assert_allclose(got["uf"], want["uf"], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(got["itf"], want["itf"], rtol=1e-5,
                                   atol=1e-6)

    def test_crash_on_first_save_restarts_clean(self, tmp_path):
        ckpt = tmp_path / "ckpt1"
        crashed = _run_train_worker(tmp_path, ckpt, "c1.npz",
                                    faults="checkpoint.pre_replace:1")
        assert crashed.returncode == 137

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        assert CheckpointManager(str(ckpt)).latest_step() is None

        ref = _run_train_worker(tmp_path, tmp_path / "ckpt1_ref", "r1.npz")
        resumed = _run_train_worker(tmp_path, ckpt, "f1.npz")
        assert resumed.returncode == 0, resumed.stderr
        got, want = np.load(tmp_path / "f1.npz"), np.load(tmp_path / "r1.npz")
        assert int(got["start_epoch"]) == 0  # nothing to resume from
        np.testing.assert_allclose(got["uf"], want["uf"], rtol=1e-5,
                                   atol=1e-6)
        assert ref.returncode == 0


OVERWRITE_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["PIO_TEST_REPO"])
    import numpy as np
    from predictionio_tpu.workflow.checkpoint import CheckpointManager

    mgr = CheckpointManager(os.environ["PIO_TEST_CKPT"])
    mgr.save(1, {"w": np.full(4, 1.0)})   # clean
    os.environ["PIO_FAULTS"] = "checkpoint.pre_replace"
    mgr.save(1, {"w": np.full(4, 2.0)})   # dies between aside and publish
""")


@pytest.mark.e2e
def test_overwrite_crash_salvages_old_step(tmp_path):
    """save() over an existing step renames it aside before publishing; a
    crash in that window must not lose the old step — the next manager
    init salvages it (r2 review: rmtree-then-replace had a loss window)."""
    worker = tmp_path / "ow.py"
    worker.write_text(OVERWRITE_WORKER)
    ckpt = tmp_path / "ckpt_ow"
    env = dict(os.environ)
    env.pop("PIO_FAULTS", None)
    env.update(PIO_TEST_REPO=str(REPO), PIO_TEST_CKPT=str(ckpt),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(worker)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 137, proc.stderr
    assert not (ckpt / "step_1" / "meta.json").exists()  # publish never ran
    assert (ckpt / "step_1.old" / "meta.json").exists()

    from predictionio_tpu.workflow.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(ckpt))  # salvage on init
    tree, _ = mgr.restore(1)
    np.testing.assert_array_equal(tree["w"], np.full(4, 1.0))
    assert not (ckpt / "step_1.old").exists()


SERVER_CMD = "predictionio_tpu.tools.console"


def _start_event_server(tmp_path, db, faults=""):
    env = dict(os.environ)
    env.pop("PIO_CONF_DIR", None)
    env.update(
        PIO_STORAGE_SOURCES_SQL_TYPE="sqlite",
        PIO_STORAGE_SOURCES_SQL_PATH=str(db),
        PIO_STORAGE_REPOSITORIES_METADATA_SOURCE="SQL",
        PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE="SQL",
        PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE="SQL",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=f"{REPO}{os.pathsep}" + os.environ.get("PYTHONPATH", ""),
    )
    if faults:
        env["PIO_FAULTS"] = faults
    else:
        env.pop("PIO_FAULTS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", SERVER_CMD, "eventserver", "--ip",
         "127.0.0.1", "--port", "0"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    import selectors

    port = None
    seen = []
    deadline = time.time() + 60
    assert proc.stdout is not None
    sel = selectors.DefaultSelector()
    sel.register(proc.stdout, selectors.EVENT_READ)
    while time.time() < deadline:
        # bounded wait: a server that stays alive without printing must
        # fail the test at the deadline, not hang readline() forever
        if not sel.select(timeout=min(1.0, max(0.0, deadline - time.time()))):
            if proc.poll() is not None:
                break
            continue
        line = proc.stdout.readline()
        if line == "" and proc.poll() is not None:  # died during startup
            break
        seen.append(line)
        if "listening on" in line:
            port = int(line.rsplit(":", 1)[1])
            break
    sel.close()
    assert port, ("event server never reported its port; output:\n"
                  + "".join(seen))
    return proc, port


@pytest.mark.e2e
class TestBatchIngestCrash:
    def test_server_death_mid_batch_leaves_no_partial_writes(self, tmp_path):
        import http.client

        db = tmp_path / "events.db"
        # seed app + access key straight through the storage layer (the
        # server creates its schema lazily on first use)
        from predictionio_tpu.storage.base import AccessKey, App
        from predictionio_tpu.storage.sqlite import SQLiteBackend

        backend = SQLiteBackend(str(db))
        app_id = backend.apps().insert(App(id=0, name="CrashApp"))
        backend.access_keys().insert(AccessKey(key="ck", app_id=app_id))
        backend.close()

        batch = [{"event": "rate", "entityType": "user",
                  "entityId": f"u{i}", "targetEntityType": "item",
                  "targetEntityId": str(i),
                  "properties": {"rating": 4.0},
                  "eventId": f"client-id-{i:04d}"} for i in range(20)]
        body = json.dumps(batch).encode()

        # armed server: dies between executemany and commit
        proc, port = _start_event_server(tmp_path, db,
                                         faults="events.batch.pre_commit:1")
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            with pytest.raises((http.client.HTTPException, OSError)):
                conn.request("POST", "/batch/events.json?accessKey=ck", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                # if a response DID come back it must not be a success
                assert resp.status >= 500
                raise http.client.HTTPException("server errored")
        finally:
            proc.wait(timeout=30)  # the fault killed it
        assert proc.returncode == 137

        rows = sqlite3.connect(db).execute(
            "SELECT count(*) FROM events").fetchone()[0]
        assert rows == 0, f"partial batch visible after crash: {rows} rows"

        # replay against a healthy server: exactly-once via client eventIds
        proc, port = _start_event_server(tmp_path, db)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("POST", "/batch/events.json?accessKey=ck", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert resp.status == 200
            assert all(r["status"] in (201, 200) for r in out)
            conn.close()
        finally:
            proc.terminate()
            proc.wait(timeout=30)
        rows = sqlite3.connect(db).execute(
            "SELECT count(*) FROM events").fetchone()[0]
        assert rows == 20


MIDRUN_WORKER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, os.environ["PIO_TEST_REPO"])
    from predictionio_tpu.parallel import distributed
    distributed.initialize_from_env()
    import jax, jax.numpy as jnp
    import numpy as np
    mesh = distributed.global_mesh()
    if jax.process_index() == 1:
        time.sleep(3)
        os._exit(9)  # hard death mid-run (SIGKILL-like, no shutdown)
    time.sleep(5)  # let the peer die first
    try:
        garr = distributed.make_global_array(mesh,
                                             np.ones((8, 4), np.float32))
        float(jax.jit(jnp.sum)(garr))
        print("COLLECTIVE_OK", flush=True)
        sys.exit(0)
    except BaseException as e:
        print("COLLECTIVE_FAILED:", type(e).__name__, flush=True)
        sys.exit(5)
""")


RANK0_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["PIO_TEST_REPO"])
    from predictionio_tpu.parallel import distributed
    try:
        distributed.initialize_from_env()
    except Exception as e:
        print("BOOTSTRAP_FAILED:", type(e).__name__, str(e)[:200])
        sys.exit(3)
    print("BOOTSTRAP_OK")
    sys.exit(0)
""")


def _four_rank_train(tmp_path, db, engine_json, ckpt_dir,
                     faults_by_rank=None, timeout=300, n_ranks=4,
                     extra_env=None):
    """n-process `bin/pio train` world (2 CPU devices per rank) through
    the shared pod-contract launcher. Despite the historical name, the
    world size is a parameter — the shrunk-world drills re-form with
    fewer ranks against the same db + checkpoint dir."""
    from tests.test_distributed_multihost import _run_world_train

    return _run_world_train(
        engine_json, db, tmp_path, n_ranks=n_ranks, dev_per_rank=2,
        extra_env={"PIO_LOG_LEVEL": "INFO",
                   "PIO_COORDINATOR_TIMEOUT_S": "30",
                   **(extra_env or {})},
        faults_by_rank=faults_by_rank,
        extra_args=("--checkpoint-dir", str(ckpt_dir),
                    "--checkpoint-every", "1"),
        check=False, timeout=timeout)


def _seed_world_db(db, app_name):
    from tests.test_distributed_multihost import _seed_ratings

    _seed_ratings(db, app_name, 2000, 48, 32, seed=21)


def _world_engine_json(path, app_name, engine_id):
    from tests.test_distributed_multihost import _write_engine_json

    _write_engine_json(path, app_name, engine_id, rank=8, iters=4)


def _load_model_factors(db, engine_json):
    """The persisted COMPLETED model's (user_factors, item_factors)."""
    from tests.test_distributed_multihost import _load_completed_model

    _, _, models = _load_completed_model(db, engine_json)
    return (np.asarray(models[0].user_factors),
            np.asarray(models[0].item_factors))


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


@pytest.mark.e2e
class TestRankDeath:
    def test_missing_rank_fails_bootstrap_within_timeout(self, tmp_path):
        """2-process world, rank 1 never shows up: rank 0 must error out
        within PIO_COORDINATOR_TIMEOUT_S, not hang on jax's long default."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        worker = tmp_path / "rank0.py"
        worker.write_text(RANK0_WORKER)
        env = dict(os.environ)
        env.pop("PIO_CONF_DIR", None)
        env.update(
            JAX_PLATFORMS="cpu",
            PIO_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            PIO_NUM_PROCESSES="2",
            PIO_PROCESS_ID="0",
            PIO_COORDINATOR_TIMEOUT_S="10",
            PIO_TEST_REPO=str(REPO),
        )
        t0 = time.time()
        proc = subprocess.run([sys.executable, str(worker)], env=env,
                              capture_output=True, text=True, timeout=120)
        elapsed = time.time() - t0
        # the exact exit path varies (the error may also fire from jax's
        # shutdown hook); the contract is: nonzero exit, deadline error
        # surfaced, and bounded time — NOT a hang on jax's long default
        all_out = proc.stdout + proc.stderr
        assert proc.returncode != 0, all_out
        assert ("BOOTSTRAP_FAILED" in proc.stdout
                or "DEADLINE_EXCEEDED" in all_out), all_out
        assert "BOOTSTRAP_OK" not in proc.stdout
        assert elapsed < 60, f"detection took {elapsed:.0f}s"

    def test_rank_death_mid_run_fails_survivor_not_hangs(self, tmp_path):
        """Rank 1 hard-dies after bootstrap; rank 0's next cross-host
        collective must raise (JaxRuntimeError via the gloo transport
        deadline, ~30 s) instead of hanging forever — the failure-
        detection half of the recovery story (re-launch is the operator's
        move, as with a dead Spark executor [U])."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        worker = tmp_path / "midrun.py"
        worker.write_text(MIDRUN_WORKER)
        procs = []
        for pid in range(2):
            env = dict(os.environ)
            env.pop("PIO_CONF_DIR", None)
            env.update(
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                PIO_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                PIO_NUM_PROCESSES="2",
                PIO_PROCESS_ID=str(pid),
                PIO_TEST_REPO=str(REPO),
            )
            procs.append(subprocess.Popen(
                [sys.executable, str(worker)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        try:
            outs = [p.communicate(timeout=180)[0] for p in procs]
        finally:
            # on the hang this test guards against, don't leak live workers
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
        assert procs[1].returncode == 9  # the injected death
        # detection races between two valid paths: (a) the collective
        # raises JaxRuntimeError (gloo transport deadline) and our handler
        # exits 5, or (b) the coordination-service heartbeat notices the
        # dead peer first and jax's distributed client terminates the
        # survivor itself. Either way: nonzero exit, death named, NO hang.
        assert procs[0].returncode != 0, outs[0]
        assert ("COLLECTIVE_FAILED" in outs[0]
                or "heartbeat timeout" in outs[0]
                or "another task died" in outs[0]), outs[0]
        assert "COLLECTIVE_OK" not in outs[0]


def _seed_docs(db, app_name, n_docs=60, seed=5):
    """App + $set content entities (text + category) straight through the
    storage layer — the text template's training shape."""
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.events import Event
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.storage.sqlite import SQLiteBackend

    words = {"a": ["alpha", "beta", "gamma", "delta", "epsilon"],
             "b": ["one", "two", "three", "four", "five"]}
    rng = np.random.default_rng(seed)
    backend = SQLiteBackend(str(db))
    app_id = backend.apps().insert(App(id=0, name=app_name))
    backend.events().insert_batch(
        [Event(event="$set", entity_type="content", entity_id=f"d{i}",
               properties=DataMap({
                   "text": " ".join(rng.choice(words[c], size=8)),
                   "category": c}))
         for i, c in ((i, "a" if i % 2 == 0 else "b")
                      for i in range(n_docs))],
        app_id=app_id)
    backend.close()


def _text_engine_json(path, app_name, engine_id):
    path.write_text(json.dumps({
        "id": engine_id,
        "engineFactory": "predictionio_tpu.templates.textclassification."
                         "TextClassificationEngine",
        "datasource": {"params": {"appName": app_name}},
        "algorithms": [{"name": "word2vec", "params": {
            "dim": 8, "steps": 40, "batchSize": 64, "negatives": 3,
            "iterations": 30, "seed": 11}}],
    }))


def _run_text_train(tmp_path, db, engine_json, ckpt_dir, faults="",
                    n_devices=2):
    from tests.test_distributed_multihost import _train_env

    env = _train_env(db, tmp_path, n_devices, PIO_LOG_LEVEL="INFO")
    env.pop("PIO_FAULTS", None)
    if faults:
        env["PIO_FAULTS"] = faults
    return subprocess.run(
        [str(REPO / "bin" / "pio"), "train",
         "--engine-json", str(engine_json),
         "--checkpoint-dir", str(ckpt_dir), "--checkpoint-every", "10"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=600)


def _text_model(db, engine_json):
    from tests.test_distributed_multihost import _load_completed_model

    _, _, models = _load_completed_model(db, engine_json)
    return models[0]  # W2VClassifierModel


@pytest.mark.e2e
class TestTextTemplateCheckpointCrash:
    """VERDICT r4 missing #1 closed: the checkpoint/elastic contract
    extended beyond ALS. Kill a real `bin/pio train` of the text
    template (W2V SGNS + LogReg head, both segmented through
    workflow/segmented.py) at the worst moment, resume, and match the
    uninterrupted model — the same bar as TestCheckpointCrash/
    TestElasticRecovery hold for ALS."""

    def test_kill_mid_w2v_then_resume_matches(self, tmp_path):
        db_ref = tmp_path / "ref.db"
        _seed_docs(db_ref, "TextApp")
        ej_ref = tmp_path / "engine_ref.json"
        _text_engine_json(ej_ref, "TextApp", "text-ref")
        ref = _run_text_train(tmp_path, db_ref, ej_ref, tmp_path / "ck_ref")
        assert ref.returncode == 0, ref.stdout
        want = _text_model(db_ref, ej_ref)

        # crash: die between the 2nd computed SGNS chunk and its save
        # (the worst moment — chunk 2's work is lost) → step 10 on disk
        db = tmp_path / "crash.db"
        _seed_docs(db, "TextApp")
        ej = tmp_path / "engine.json"
        _text_engine_json(ej, "TextApp", "text-crash")
        ckpt = tmp_path / "ck"
        crashed = _run_text_train(tmp_path, db, ej, ckpt,
                                  faults="w2v.step_boundary:2")
        assert crashed.returncode == 137, crashed.stdout
        assert "dying at w2v.step_boundary" in crashed.stdout

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        assert CheckpointManager(str(ckpt / "w2v")).latest_step() == 10
        # the head never started — no stray checkpoint dirs
        assert not (ckpt / "w2v-head").exists()

        resumed = _run_text_train(tmp_path, db, ej, ckpt)
        assert resumed.returncode == 0, resumed.stdout
        assert "word2vec_train: resumed from checkpoint step 10" \
            in resumed.stdout
        got = _text_model(db, ej)
        np.testing.assert_array_equal(got.w2v.vectors, want.w2v.vectors)
        np.testing.assert_array_equal(got.lr.weights, want.lr.weights)
        assert got.classes == want.classes

    def test_kill_mid_head_resumes_without_retraining_w2v(self, tmp_path):
        """A crash during the LogReg HEAD phase must not re-run the SGNS
        loop: embeddings restore fully from their completed checkpoint
        and the head resumes from its own."""
        db_ref = tmp_path / "ref.db"
        _seed_docs(db_ref, "TextApp2")
        ej_ref = tmp_path / "engine_ref.json"
        _text_engine_json(ej_ref, "TextApp2", "t2-ref")
        ref = _run_text_train(tmp_path, db_ref, ej_ref, tmp_path / "ck_ref")
        assert ref.returncode == 0, ref.stdout
        want = _text_model(db_ref, ej_ref)

        db = tmp_path / "crash.db"
        _seed_docs(db, "TextApp2")
        ej = tmp_path / "engine.json"
        _text_engine_json(ej, "TextApp2", "t2-crash")
        ckpt = tmp_path / "ck"
        crashed = _run_text_train(tmp_path, db, ej, ckpt,
                                  faults="logreg.step_boundary:2")
        assert crashed.returncode == 137, crashed.stdout

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        assert CheckpointManager(str(ckpt / "w2v")).latest_step() == 40
        assert CheckpointManager(str(ckpt / "w2v-head")).latest_step() == 10
        # chunk 2 of the head was computed but died pre-save — lost

        resumed = _run_text_train(tmp_path, db, ej, ckpt)
        assert resumed.returncode == 0, resumed.stdout
        assert "word2vec_train: resumed from checkpoint step 40" \
            in resumed.stdout
        assert "logreg_train: resumed from checkpoint step 10" \
            in resumed.stdout
        got = _text_model(db, ej)
        np.testing.assert_array_equal(got.w2v.vectors, want.w2v.vectors)
        np.testing.assert_array_equal(got.lr.weights, want.lr.weights)

    def test_multiprocess_w2v_kill_rank_reform_resume(self, tmp_path):
        """The multi-process variant: a 2-rank world (2 CPU devices each,
        batch sharded over data=4 through the sharded SGNS loop) loses
        rank 1 at a step boundary; the re-formed world resumes from the
        persisted checkpoint and matches the uninterrupted 2-rank run."""
        from tests.test_distributed_multihost import _run_world_train

        def world(db, ej, ckpt, faults_by_rank=None):
            return _run_world_train(
                ej, db, tmp_path, n_ranks=2, dev_per_rank=2,
                extra_env={"PIO_LOG_LEVEL": "INFO",
                           "PIO_COORDINATOR_TIMEOUT_S": "30"},
                faults_by_rank=faults_by_rank,
                extra_args=("--checkpoint-dir", str(ckpt),
                            "--checkpoint-every", "10"),
                check=False, timeout=600)

        db_ref = tmp_path / "ref.db"
        _seed_docs(db_ref, "TextW")
        ej_ref = tmp_path / "engine_ref.json"
        _text_engine_json(ej_ref, "TextW", "tw-ref")
        rcs, outs = world(db_ref, ej_ref, tmp_path / "ck_ref")
        assert rcs == [0, 0], outs
        want = _text_model(db_ref, ej_ref)

        db = tmp_path / "crash.db"
        _seed_docs(db, "TextW")
        ej = tmp_path / "engine.json"
        _text_engine_json(ej, "TextW", "tw-crash")
        ckpt = tmp_path / "ck"
        rcs, outs = world(db, ej, ckpt,
                          faults_by_rank={1: "w2v.step_boundary:2"})
        assert rcs[1] == 137, outs[1]
        assert rcs[0] != 0, outs[0]  # survivor fails fast, no hang

        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        # rank 1 died pre-save of ITS step-20 boundary, but the persist
        # rank (0) had everything it needed locally (replicated factors)
        # and published step 20 before its next chunk's collective failed
        assert CheckpointManager(str(ckpt / "w2v")).latest_step() == 20

        rcs, outs = world(db, ej, ckpt)
        assert rcs == [0, 0], outs
        assert "word2vec_train: resumed from checkpoint step 20" in outs[0]
        got = _text_model(db, ej)
        np.testing.assert_array_equal(got.w2v.vectors, want.w2v.vectors)
        np.testing.assert_array_equal(got.lr.weights, want.lr.weights)
