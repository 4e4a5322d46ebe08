"""What the failure-path drills share (`tests/test_failure_*.py`): the worker
scripts, the event server on a port of its own, the n-process `pio train`
world and its database, the text template's corpus and train. One module,
so that the drills can sit a class a file and `--dist loadfile` can hand
them to different workers (822 s on one worker while they shared a file)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import numpy as np

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
