"""Multi-host control plane e2e: 2 real processes × 4 CPU devices each
federate into one 8-device world via `jax.distributed` and assemble a
correct global sharded array — the TPU-native replacement for the
reference's Spark driver↔executor bootstrap (SURVEY.md §2.7). Runs the
same `PIO_COORDINATOR_ADDRESS`/`PIO_NUM_PROCESSES`/`PIO_PROCESS_ID`
contract `pio train` uses on a real pod."""

import json
import os
import pathlib
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.environ["PIO_TEST_REPO"])
    import numpy as np
    from predictionio_tpu.parallel import distributed

    assert distributed.initialize_from_env()
    import jax
    import jax.numpy as jnp

    mesh = distributed.global_mesh()
    lo, hi = distributed.process_row_range(16)
    local = (np.arange(lo, hi, dtype=np.float32).reshape(-1, 1)
             * np.ones((1, 4), np.float32))
    garr = distributed.make_global_array(mesh, local)
    total = float(jax.jit(jnp.sum)(garr))
    out = {
        "pid": jax.process_index(),
        "devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
        "sum": total,
        "rows": [int(lo), int(hi)],
        "mesh": dict(mesh.shape),
    }
    with open(os.environ["PIO_TEST_OUT"], "w") as f:
        json.dump(out, f)
""")


def _run_global_mesh_world(tmp_path, n_procs, dev_per_proc):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER)
    procs = []
    for pid in range(n_procs):
        env = dict(os.environ)
        env.pop("PIO_CONF_DIR", None)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={dev_per_proc}",
            PIO_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            PIO_NUM_PROCESSES=str(n_procs),
            PIO_PROCESS_ID=str(pid),
            PIO_TEST_REPO=str(REPO),
            PIO_TEST_OUT=str(tmp_path / f"out{pid}.json"),
        )
        procs.append(subprocess.Popen(
            [sys.executable, str(worker_py)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=180)[0] for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, o
    return [json.loads((tmp_path / f"out{i}.json").read_text())
            for i in range(n_procs)]


@pytest.mark.e2e
def test_two_process_global_mesh(tmp_path):
    results = _run_global_mesh_world(tmp_path, 2, 4)
    expected_sum = float(sum(range(16)) * 4)
    for pid, r in enumerate(results):
        assert r["pid"] == pid
        assert r["devices"] == 8 and r["local_devices"] == 4
        assert r["sum"] == expected_sum  # every rank sees the global sum
        assert r["mesh"] == {"data": 8, "model": 1}
    # the two ranks fed disjoint halves of the global rows
    assert results[0]["rows"] == [0, 8] and results[1]["rows"] == [8, 16]


@pytest.mark.e2e
def test_four_process_global_mesh(tmp_path):
    """4-process world (VERDICT r2 #9): bootstrap, global mesh, and
    disjoint host row-feeding still hold past the 2-process special
    case (coordinator + 3 remote clients)."""
    results = _run_global_mesh_world(tmp_path, 4, 2)
    expected_sum = float(sum(range(16)) * 4)
    for pid, r in enumerate(results):
        assert r["pid"] == pid
        assert r["devices"] == 8 and r["local_devices"] == 2
        assert r["sum"] == expected_sum
        assert r["mesh"] == {"data": 8, "model": 1}
    assert [r["rows"] for r in results] == [[0, 4], [4, 8], [8, 12],
                                            [12, 16]]



TRAIN_ENV_KEYS = dict(
    PIO_STORAGE_REPOSITORIES_METADATA_SOURCE="SQL",
    PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE="SQL",
    PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE="SQL",
    PIO_STORAGE_SOURCES_SQL_TYPE="sqlite",
)


def _seed_ratings(db, app_name, n_events, n_users, n_items, seed):
    """App + random rate events straight through the storage layer."""
    import numpy as np

    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.events import Event
    from predictionio_tpu.storage.base import App
    from predictionio_tpu.storage.sqlite import SQLiteBackend

    backend = SQLiteBackend(str(db))
    app_id = backend.apps().insert(App(id=0, name=app_name))
    rng = np.random.default_rng(seed)
    backend.events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id=str(u),
               target_entity_type="item", target_entity_id=str(i),
               properties=DataMap({"rating": float(r)}))
         for u, i, r in zip(rng.integers(0, n_users, n_events),
                            rng.integers(0, n_items, n_events),
                            rng.integers(1, 6, n_events))],
        app_id=app_id)
    backend.close()


def _write_engine_json(path, app_name, engine_id, rank, iters, **algo_params):
    params = {"rank": rank, "numIterations": iters, "lambda": 0.05, "seed": 1}
    params.update(algo_params)
    path.write_text(json.dumps({
        "id": engine_id, "engineFactory":
            "predictionio_tpu.templates.recommendation.RecommendationEngine",
        "datasource": {"params": {"appName": app_name}},
        "algorithms": [{"name": "als", "params": params}],
    }))


def _train_env(db, basedir, n_local_devices, **extra):
    """THE pod-contract env (storage + CPU mesh + PYTHONPATH) shared by
    every CLI-train harness; tests state only what differs."""
    env = dict(os.environ)
    env.pop("PIO_CONF_DIR", None)
    env.update(
        TRAIN_ENV_KEYS,
        PIO_STORAGE_SOURCES_SQL_PATH=str(db),
        PIO_FS_BASEDIR=str(basedir),
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_local_devices}",
        PYTHONPATH=f"{REPO}{os.pathsep}" + os.environ.get("PYTHONPATH", ""),
    )
    env.update(extra)
    return env


def _run_world_train(engine_json, db, basedir, n_ranks=2, dev_per_rank=4,
                     extra_env=None, faults_by_rank=None, extra_args=(),
                     check=True, timeout=300):
    """Launch an n-rank `bin/pio train` world federated via
    PIO_COORDINATOR_* — THE pod-contract launcher shared with the
    failure-path suite. `faults_by_rank` arms PIO_FAULTS on chosen ranks;
    `check=False` returns (returncodes, outputs) without asserting."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(n_ranks):
        env = _train_env(
            db, basedir, dev_per_rank,
            PIO_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            PIO_NUM_PROCESSES=str(n_ranks),
            PIO_PROCESS_ID=str(pid),
            **(extra_env or {}),
        )
        env.pop("PIO_FAULTS", None)
        if faults_by_rank and pid in faults_by_rank:
            env["PIO_FAULTS"] = faults_by_rank[pid]
        procs.append(subprocess.Popen(
            [str(REPO / "bin" / "pio"), "train",
             "--engine-json", str(engine_json), *extra_args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    try:
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    if check:
        for p, o in zip(procs, outs):
            assert p.returncode == 0, o
        return outs
    return [p.returncode for p in procs], outs


def _run_two_rank_train(engine_json, db, basedir, extra_env=None):
    return _run_world_train(engine_json, db, basedir, extra_env=extra_env)


@pytest.mark.e2e
def test_two_process_pio_train_cli(tmp_path):
    """The real pod contract end-to-end: TWO `bin/pio train` processes
    federate via PIO_COORDINATOR_* into one 8-device world over a shared
    file store; every rank trains (collectives need all of them), rank 0
    alone persists the model + COMPLETED instance, and the persisted
    model loads and answers a query."""
    import sqlite3

    db = tmp_path / "pio.db"
    _seed_ratings(db, "MHApp", 3000, 48, 32, seed=3)
    engine_json = tmp_path / "engine.json"
    _write_engine_json(engine_json, "MHApp", "mh", rank=8, iters=3)

    outs = _run_two_rank_train(engine_json, db, tmp_path)

    conn = sqlite3.connect(db)
    completed = conn.execute(
        "SELECT id FROM engine_instances WHERE status='COMPLETED'"
    ).fetchall()
    assert len(completed) == 1  # rank 0 only — no duplicate instances
    models = conn.execute("SELECT count(*) FROM models").fetchone()[0]
    assert models == 1
    conn.close()
    # rank 0 reported the REAL persisted instance id (rank 1 prints a
    # worker placeholder)
    assert f"Engine instance ID: {completed[0][0]}" in outs[0]

    # the persisted model must load and answer a query (single process);
    # seen-item exclusion may leave fewer than `num` candidates — the
    # claim is that the persisted model answers, not the exact count
    engine, ep, models_obj = _load_completed_model(db, engine_json)
    r = engine.predict(ep, models_obj, {"user": "1", "num": 3})
    assert 1 <= len(r["itemScores"]) <= 3


def _load_completed_model(db, engine_json):
    """Load the single COMPLETED instance's persisted model back through
    the engine; returns (engine, engine_params, model)."""
    import sqlite3

    from predictionio_tpu.storage.registry import (
        SourceConfig, Storage, StorageConfig,
    )
    from predictionio_tpu.workflow.workflow_utils import (
        EngineVariant, extract_engine_params, get_engine,
    )

    conn = sqlite3.connect(db)
    completed = conn.execute(
        "SELECT id FROM engine_instances WHERE status='COMPLETED'"
    ).fetchall()
    conn.close()
    assert len(completed) == 1, completed
    src = SourceConfig(name="SQL", type="sqlite", path=str(db))
    storage = Storage(StorageConfig(metadata=src, modeldata=src,
                                    eventdata=src))
    try:
        variant = EngineVariant.from_dict(
            json.loads(pathlib.Path(engine_json).read_text()))
        engine = get_engine(variant.engine_factory)
        ep = extract_engine_params(engine, variant)
        blob = storage.model_data_models().get(completed[0][0]).models
        models = engine.deserialize_models(blob, completed[0][0], ep)
        return engine, ep, models
    finally:
        storage.close()


SHARDED_LOG = "training factors model-sharded ('model', None)"


def _run_single_pio_train(engine_json, db, basedir, mesh_shape, metrics_file):
    """One `bin/pio train` subprocess on the 8-virtual-device CPU mesh with
    the pod-level PIO_MESH_SHAPE env contract; returns its merged output."""
    env = _train_env(db, basedir, 8,
                     PIO_MESH_SHAPE=mesh_shape, PIO_LOG_LEVEL="INFO")
    proc = subprocess.run(
        [str(REPO / "bin" / "pio"), "train",
         "--engine-json", str(engine_json),
         "--metrics-file", str(metrics_file)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout
    return proc.stdout


def _split_counts(out):
    """Hot-row segment counts from the als_train bucketize log line
    ('... (N buckets, caps [...], S split) ...') — [users_split,
    items_split]."""
    import re

    m = re.findall(r"(\d+) split\)", out)
    assert len(m) >= 2, f"no als_train bucketize log in output:\n{out[-2000:]}"
    return [int(x) for x in m[:2]]


def _final_rmse(metrics_file):
    rmses = [json.loads(line)["rmse"]
             for line in pathlib.Path(metrics_file).read_text().splitlines()
             if "rmse" in json.loads(line)]
    assert rmses, f"no rmse records in {metrics_file}"
    return rmses[-1]


@pytest.mark.e2e
def test_pio_train_cli_model_axis_rank128(tmp_path):
    """Config 5's capability through the USER-FACING path (VERDICT r2 #1):
    `bin/pio train` with PIO_MESH_SHAPE=data=4,model=2 at rank 128 with
    hot-row segmentation forced. The in-product invariant in als_train
    raises unless the training factors really shard P('model'), its INFO
    log proves which mesh served the run, and the final RMSE matches a
    data-only-mesh train of the same data to MLlib-parity tolerance."""
    db = tmp_path / "pio.db"
    # 40 users × 24 items × 3000 events: after the Preparator's (user,
    # item) dedup most of the 960 pairs survive (~38 ratings/item, ~23/
    # user), so splitCap=16 forces hot-row segments on BOTH half-steps
    _seed_ratings(db, "C5App", 3000, 40, 24, seed=7)
    engine_json = tmp_path / "engine.json"
    _write_engine_json(engine_json, "C5App", "c5", rank=128, iters=2,
                       computeRMSE=True, splitCap=16)

    out_m = _run_single_pio_train(engine_json, db, tmp_path,
                                  "data=4,model=2", tmp_path / "m.jsonl")
    assert SHARDED_LOG in out_m
    assert "'data': 4, 'model': 2" in out_m
    assert "Training completed" in out_m
    u_split, i_split = _split_counts(out_m)
    assert u_split > 0 and i_split > 0, (u_split, i_split)

    out_d = _run_single_pio_train(engine_json, db, tmp_path,
                                  "data=8,model=1", tmp_path / "d.jsonl")
    assert SHARDED_LOG not in out_d  # data-only mesh: replicated factors

    rmse_m = _final_rmse(tmp_path / "m.jsonl")
    rmse_d = _final_rmse(tmp_path / "d.jsonl")
    assert rmse_m == pytest.approx(rmse_d, rel=1e-3)

    import sqlite3

    conn = sqlite3.connect(db)
    completed = conn.execute(
        "SELECT count(*) FROM engine_instances WHERE status='COMPLETED'"
    ).fetchone()[0]
    conn.close()
    assert completed == 2


@pytest.mark.e2e
def test_pio_train_bucket_cache_across_processes(tmp_path):
    """Re-running `pio train` on unchanged events skips the host
    bucketize via the on-disk cache under PIO_FS_BASEDIR (VERDICT r2 #5);
    ingesting one more event invalidates it."""
    db = tmp_path / "pio.db"
    _seed_ratings(db, "CacheApp", 1200, 32, 24, seed=13)
    engine_json = tmp_path / "engine.json"
    _write_engine_json(engine_json, "CacheApp", "cache", rank=8, iters=2)

    env = _train_env(db, tmp_path, 8, PIO_LOG_LEVEL="INFO",
                     PIO_BUCKET_CACHE="1")  # conftest disables globally
    cmd = [str(REPO / "bin" / "pio"), "train",
           "--engine-json", str(engine_json)]

    def train():
        proc = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stdout
        return proc.stdout

    assert "bucket cache miss" in train()
    assert "bucket cache hit" in train()  # fresh process, same events

    # one new event from a NEW user → the prepared COO grows a row and a
    # user code → fingerprint changes → rebucketize
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.events import Event
    from predictionio_tpu.storage.sqlite import SQLiteBackend

    backend = SQLiteBackend(str(db))
    app_id = backend.apps().get_by_name("CacheApp").id
    backend.events().insert_batch(
        [Event(event="rate", entity_type="user", entity_id="99",
               target_entity_type="item", target_entity_id="2",
               properties=DataMap({"rating": 5.0}))], app_id=app_id)
    backend.close()
    out = train()
    assert "bucket cache miss" in out and "bucket cache hit" not in out


@pytest.mark.e2e
def test_two_process_pio_train_model_axis(tmp_path):
    """The 2-process pod world with model>1 (VERDICT r2 #1/weak #1): two
    `bin/pio train` ranks federate into a (data=4, model=2) global mesh
    from PIO_MESH_SHAPE alone; every rank's training factors shard
    P('model') across the world, rank 0 persists, and the model loads."""
    import sqlite3

    db = tmp_path / "pio.db"
    # post-dedup: ~29 ratings/item, ~22/user → splitCap=16 segments both
    _seed_ratings(db, "MHC5App", 2000, 32, 24, seed=11)
    engine_json = tmp_path / "engine.json"
    _write_engine_json(engine_json, "MHC5App", "mhc5", rank=16, iters=2,
                       splitCap=16)

    outs = _run_two_rank_train(engine_json, db, tmp_path, extra_env={
        "PIO_MESH_SHAPE": "data=4,model=2",
        "PIO_LOG_LEVEL": "INFO",
    })
    for o in outs:  # BOTH ranks trained on the model-sharded mesh
        assert SHARDED_LOG in o, o
        assert "'data': 4, 'model': 2" in o
        u_split, i_split = _split_counts(o)
        assert u_split > 0 and i_split > 0, (u_split, i_split)

    conn = sqlite3.connect(db)
    models = conn.execute("SELECT count(*) FROM models").fetchone()[0]
    assert models == 1
    conn.close()

    # the persisted model answers a query (single process reload)
    engine, ep, models_obj = _load_completed_model(db, engine_json)
    r = engine.predict(ep, models_obj, {"user": "1", "num": 3})
    assert 1 <= len(r["itemScores"]) <= 3


@pytest.mark.e2e
def test_two_process_train_persists_to_object_store(tmp_path):
    """Multi-host deployments without a shared filesystem point MODELDATA
    at the s3 source (docs/operations.md); rank 0's model blob must land
    in the object store and load back."""
    import sqlite3

    from predictionio_tpu.storage.objectstore import S3Client
    from predictionio_tpu.storage.objectstore_server import ObjectStoreServer

    srv = ObjectStoreServer(str(tmp_path / "objects")).start()
    try:
        db = tmp_path / "pio.db"
        _seed_ratings(db, "MHS3App", 1500, 32, 24, seed=5)
        engine_json = tmp_path / "engine.json"
        _write_engine_json(engine_json, "MHS3App", "mhs3", rank=6, iters=2)

        _run_two_rank_train(engine_json, db, tmp_path, extra_env={
            "PIO_STORAGE_SOURCES_OBJ_TYPE": "s3",
            "PIO_STORAGE_SOURCES_OBJ_PATH":
                f"s3://pio/models?endpoint=http://127.0.0.1:{srv.port}",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "OBJ",
        })

        conn = sqlite3.connect(db)
        (instance_id,) = conn.execute(
            "SELECT id FROM engine_instances WHERE status='COMPLETED'"
        ).fetchone()
        conn.close()
        # exactly one model object, named by the instance, fetchable
        blobs = os.listdir(tmp_path / "objects" / "pio" / "models")
        assert blobs == [f"{instance_id}.model"]
        data = S3Client(f"http://127.0.0.1:{srv.port}", "pio").get_object(
            f"models/{instance_id}.model")
        assert data and len(data) > 1000
    finally:
        srv.shutdown()


@pytest.mark.e2e
def test_eight_process_train_with_nonzero_persist_rank(tmp_path):
    """VERDICT r3 #7: (a) an EIGHT-process `bin/pio train` world — double
    the previous drill ceiling — and (b) the persister/coordinator SPLIT:
    the jax coordinator is pinned to process 0, but PIO_PERSIST_RANK=3
    moves model/instance persistence to rank 3. Exactly one COMPLETED
    instance (written by rank 3), workers print placeholders, and the
    persisted model answers a query."""
    import sqlite3

    db = tmp_path / "pio.db"
    _seed_ratings(db, "OctApp", 2000, 48, 32, seed=8)
    engine_json = tmp_path / "engine.json"
    _write_engine_json(engine_json, "OctApp", "oct", rank=8, iters=2)

    outs = _run_world_train(
        engine_json, db, tmp_path, n_ranks=8, dev_per_rank=1,
        extra_env={"PIO_PERSIST_RANK": "3",
                   "PIO_COORDINATOR_TIMEOUT_S": "60"},
        timeout=600)

    conn = sqlite3.connect(db)
    completed = conn.execute(
        "SELECT id FROM engine_instances WHERE status='COMPLETED'"
    ).fetchall()
    assert len(completed) == 1  # ONE writer — no duplicate instances
    assert conn.execute("SELECT count(*) FROM models").fetchone()[0] == 1
    conn.close()
    # rank 3 (not the rank-0 coordinator) reported the persisted id;
    # every other rank printed the worker placeholder naming rank 3
    assert f"Engine instance ID: {completed[0][0]}" in outs[3]
    for pid in (0, 1, 2, 4, 5, 6, 7):
        assert "rank 3 persists" in outs[pid], outs[pid][-500:]

    engine, ep, models_obj = _load_completed_model(db, engine_json)
    r = engine.predict(ep, models_obj, {"user": "1", "num": 3})
    assert 1 <= len(r["itemScores"]) <= 3


@pytest.mark.e2e
def test_persist_rank_out_of_range_fails_loud(tmp_path):
    """PIO_PERSIST_RANK >= world size must fail the job with a clear
    error, not silently persist nowhere."""
    db = tmp_path / "pio.db"
    _seed_ratings(db, "BadRankApp", 500, 16, 12, seed=9)
    engine_json = tmp_path / "engine.json"
    _write_engine_json(engine_json, "BadRankApp", "badrank", rank=4,
                       iters=1)
    rcs, outs = _run_world_train(
        engine_json, db, tmp_path, n_ranks=2, dev_per_rank=1,
        extra_env={"PIO_PERSIST_RANK": "5"}, check=False, timeout=300)
    assert all(rc != 0 for rc in rcs), rcs
    assert any("PIO_PERSIST_RANK=5 out of range" in o for o in outs), (
        outs[0][-500:])
