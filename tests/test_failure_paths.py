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

The long drills sit a class a file so that the suite's workers share them:
rank death in `test_failure_rank_death.py`, elastic recovery in
`test_failure_elastic_reform.py` and `test_failure_elastic_shrunk.py`, the
text template's in `test_failure_text_checkpoint.py`; what they share is
`failure_paths_helpers.py`.
"""

import json
import os
import sqlite3
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from tests.failure_paths_helpers import (
    REPO,
    _run_train_worker,
    _start_event_server,
)


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
