"""The train route's own instrumentation: host spans from `Engine.train`
down to `als_train`'s phases, the named scopes of the train loop's
program, the bucket gauges, `train/phases` and the loop-stamp epoch
times. Stamps are compared with each other, never with a wall clock."""

import contextlib
import dataclasses
import json
import os
import re
import threading

import numpy as np
import pytest

from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.ops import als
from predictionio_tpu.ops.als import (
    ALSConfig,
    LoopChunk,
    als_train,
    epoch_times_of,
)
from predictionio_tpu.telemetry import spans
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.utils.profiling import MetricsLogger
from predictionio_tpu.workflow.core_workflow import CoreWorkflow
from predictionio_tpu.workflow.workflow_utils import (
    EngineVariant,
    extract_engine_params,
    get_engine,
)
from tests.test_als import _placed_buckets
from tests.test_als_digest import cores
from tests.test_ecommerce_template import ingest, variant_dict

N_USERS, N_ITEMS = 12, 9
SCOPES = ("als.gather_gram", "als.yty", "als.solve", "als.split_merge",
          "als.scatter", "als.rmse")
MISS = ["als.train", "als.digest", "als.bucket_cache.load", "als.bucketize",
        "als.bucketize", "als.bucket_cache.save", "als.put_buckets",
        "als.init_factors", "als.loop.dispatch", "als.loop.wait",
        "als.readback", "als.bucket_cache.join"]
# what a miss runs on threads of its own: the two sides beside each
# other, then the save behind the rest of the train
BESIDE = ("als.bucketize", "als.bucket_cache.save")
HIT = [n for n in MISS if n not in BESIDE + ("als.bucket_cache.join",)]


def ratings(seed=0):
    """A tiny COO set in which user 0 rated every item (so a split cap
    of 4 splits its row)."""
    rng = np.random.default_rng(seed)
    u = np.concatenate([np.zeros(N_ITEMS, np.int32),
                        rng.integers(1, N_USERS, 40).astype(np.int32)])
    i = np.concatenate([np.arange(N_ITEMS, dtype=np.int32),
                        rng.integers(0, N_ITEMS, 40).astype(np.int32)])
    return u, i, rng.uniform(1, 5, len(u)).astype(np.float32)


CFG = ALSConfig(rank=4, iterations=2, reg=0.1, seed=3)


def under_timeline(fn):
    """(result of fn(), the finished timeline it ran under)."""
    tl, token = spans.begin("test", "train", "RUN", "t-1")
    try:
        out = fn()
    finally:
        spans.finish(tl, token, status=None, duration_s=0.0)
    return out, tl


PATHS = "als.bucketize."  # + the path a bucketizer call took
DIGEST_PATHS = "als.digest."  # + the path the data digest took


def als_spans(tl):
    """The timeline's `als.*` spans as (name, start, end), by start; the
    path records of the bucketizer and the digest (`paths`) left out."""
    return sorted(((n, s, s + d) for n, s, d, _e, _nested in tl.spans
                   if n.startswith("als.")
                   and not n.startswith((PATHS, DIGEST_PATHS))),
                  key=lambda x: x[1])


def paths(tl, prefix):
    """(path, start, end) of every record under the prefix, by start."""
    return sorted(((n[len(prefix):], s, s + d)
                   for n, s, d, _e, _nested in tl.spans
                   if n.startswith(prefix)), key=lambda x: x[1])


@pytest.mark.parametrize("leaf_bytes,digest_path", [(8 << 20, "inline"),
                                                    (64, "parallel")])
@pytest.mark.parametrize("warm_cache,expected", [(False, MISS), (True, HIT)],
                         ids=["cache_miss", "cache_hit"])
def test_als_train_records_its_phases_in_order_under_als_train(
        tmp_path, monkeypatch, warm_cache, expected, leaf_bytes,
        digest_path):
    # ratings under one leaf are hashed by the caller; cut into leaves of
    # 64 bytes they go to three workers, and the spans read the same
    monkeypatch.setattr(als, "_DIGEST_LEAF_BYTES", leaf_bytes)
    cores(monkeypatch, 3)
    u, i, r = ratings()
    cache = str(tmp_path / "buckets")
    if warm_cache:
        als_train(u, i, r, N_USERS, N_ITEMS, CFG, bucket_cache_dir=cache)
    _, tl = under_timeline(lambda: als_train(
        u, i, r, N_USERS, N_ITEMS, CFG, bucket_cache_dir=cache))
    got = als_spans(tl)
    assert sorted(n for n, _, _ in got) == sorted(expected)
    (first, lo, hi), inner = got[0], got[1:]
    assert first == "als.train"
    assert all(lo <= s and e <= hi for _, s, e in inner)
    # the calling thread's phases, one after the other: none starts
    # before the one before it ended
    serial = [x for x in inner if x[0] not in BESIDE]
    assert [n for n, _, _ in serial] == [n for n in expected[1:]
                                         if n not in BESIDE]
    assert all(a[2] <= b[1] for a, b in zip(serial, serial[1:]))
    assert tl.dropped_spans == 0
    # recorded from another thread or not, a phase is nested in `als.train`
    nested = {n: flag for n, _s, _d, _e, flag in tl.spans
              if n.startswith("als.")}
    assert nested.pop("als.train") is False and all(nested.values())
    builds = [(s, e) for n, s, e in got if n == "als.bucketize"]
    saves = [(s, e) for n, s, e in got if n == "als.bucket_cache.save"]
    when = {n: (s, e) for n, s, e in serial}
    if not warm_cache:
        # the two sides may overlap each other and nothing else: both lie
        # between the look-up that missed and the first transfer
        assert all(when["als.bucket_cache.load"][1] <= s
                   and e <= when["als.put_buckets"][0] for s, e in builds)
        # the save starts when both stand and the transfers are done,
        # and is over when the join is
        (save,) = saves
        assert max(e for _, e in builds) <= save[0]
        assert when["als.init_factors"][1] <= save[0]
        assert save[1] <= when["als.bucket_cache.join"][1] <= hi
        assert when["als.readback"][1] <= when["als.bucket_cache.join"][0]
    # a side that was built says which way, inside its `als.bucketize`
    built = paths(tl, PATHS)
    assert len(built) == len(builds)
    assert all(path in ("native_counting", "native_comparison", "numpy")
               and sum(lo <= s and e <= hi for lo, hi in builds) >= 1
               for path, s, e in built)
    # so does the one digest, hit or miss, inside its `als.digest`: a wall
    # on the calling thread, over before the look-up starts
    ((path, s, e),) = paths(tl, DIGEST_PATHS)
    assert path == digest_path
    assert when["als.digest"][0] <= s and e <= when["als.digest"][1]
    assert when["als.digest"][1] <= when["als.bucket_cache.load"][0]


def test_without_a_timeline_nothing_is_recorded_and_the_factors_are_the_same():
    u, i, r = ratings()
    traced, tl = under_timeline(
        lambda: als_train(u, i, r, N_USERS, N_ITEMS, CFG))
    recorded = len(tl.spans)
    assert spans.current() is None
    plain = als_train(u, i, r, N_USERS, N_ITEMS, CFG)
    assert len(tl.spans) == recorded and spans.current() is None
    assert np.array_equal(plain.user_factors, traced.user_factors)
    assert np.array_equal(plain.item_factors, traced.item_factors)


@pytest.fixture()
def loop_text(monkeypatch):
    """Lowered text, with locations, of the train loop's program as one
    `als_train` (implicit, a split row, RMSE on) enters it."""
    entered = {}
    real = als._get_train_loop

    def spy(*key, **kw):
        loop = real(*key, **kw)

        def call(*args):
            entered["text"] = loop.jitted.lower(*args).as_text(
                debug_info=True)
            return loop(*args)
        return call

    monkeypatch.setattr(als, "_get_train_loop", spy)
    u, i, r = ratings()
    als_train(u, i, r, N_USERS, N_ITEMS,
              dataclasses.replace(CFG, implicit=True, alpha=2.0, split_cap=4),
              compute_rmse=True)
    return entered["text"]


@pytest.mark.parametrize("scope", SCOPES)
def test_the_train_loops_program_carries_every_scope(loop_text, scope):
    # a location names its ops `<outer scopes>/<scope>/<primitive>`
    assert re.search(rf'["/]{re.escape(scope)}/', loop_text)
    assert "@jit_run" in loop_text  # the benchmark finds the loop by it


def test_bucket_gauges_count_entries_and_cells_per_side(tmp_path):
    u, i, r = ratings()
    als_train(u, i, r, N_USERS, N_ITEMS, CFG)
    ub, _, ib, _ = als.bucketize_cached(
        u, i, r, N_USERS, N_ITEMS, 8, CFG.split_cap, CFG.cap_growth, None)
    entries = dict(REGISTRY.get("als_bucket_entries").collect())
    cells = dict(REGISTRY.get("als_bucket_cells").collect())
    for side, buckets in (("user", ub), ("item", ib)):
        assert entries[(side,)] == len(r)
        assert entries[(side,)] == sum(b.mask.sum() for b in buckets)
        assert cells[(side,)] == sum(b.mask.size for b in buckets)
        assert cells[(side,)] >= entries[(side,)]


@pytest.mark.parametrize("budget,chunked", [(1 << 30, False),
                                            (3 << 10, True)])
def test_walk_cells_gauge_counts_the_rows_placed(monkeypatch, budget,
                                                 chunked):
    """`als_bucket_walk_cells` is what the loop is entered with, chunk
    padding included; `als_bucket_cells` stops before that padding."""
    loops = als._get_train_loop
    monkeypatch.setattr(als, "_CHUNK_BUDGET_BYTES", budget)
    placed = _placed_buckets(monkeypatch)
    loops.cache_clear()
    rng = np.random.default_rng(1)
    u = np.repeat(np.arange(40, dtype=np.int32), 3)
    i = rng.integers(0, N_ITEMS, len(u)).astype(np.int32)
    als_train(u, i, rng.uniform(1, 5, len(u)).astype(np.float32), 40,
              N_ITEMS, CFG)
    loops.cache_clear()
    cells = dict(REGISTRY.get("als_bucket_cells").collect())
    walk = dict(REGISTRY.get("als_bucket_walk_cells").collect())
    for side, buckets in zip(("user", "item"), placed[0]):
        assert walk[(side,)] == sum(b[1].shape[0] * b[1].shape[1]
                                    for b in buckets)
        assert walk[(side,)] >= cells[(side,)]
    # 40 rows of cap 8 at rank 4, 128 bytes a row, 24 rows in 3 KiB: two
    # trips of 24
    assert cells[("user",)] == 40 * 8
    assert walk[("user",)] == (48 * 8 if chunked else 40 * 8)
    if not chunked:
        assert walk == cells


def test_the_benchmarks_fill_metrics_name_gauges_the_program_has():
    """`train.bucket_fill` and `train.walk_fill` are data files of the
    benchmark that name the program's gauges: a rename here would make
    them fall silent there."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, cells in (("train.bucket_fill", "als_bucket_cells"),
                        ("train.walk_fill", "als_bucket_walk_cells")):
        with open(os.path.join(root, "perf", "layers", name + ".json")) as f:
            spec = json.load(f)
        assert (spec["reader"], spec["numerator"], spec["denominator"]) == (
            "gauge_ratio", "als_bucket_entries", cells)
        assert REGISTRY.get(spec["numerator"]) is not None
        assert REGISTRY.get(spec["denominator"]) is not None
        entry = per_layer[name]
        assert (entry["unit"], entry["moves"], entry["layer"]) == (
            spec["unit"], spec["moves"], spec["layer"])
        assert entry["workloads"] == ["als64.train10", "als128i.train10"]


def test_the_benchmarks_join_metric_names_a_span_the_program_opens(tmp_path):
    """`train.save_join_s` is a data file of the benchmark that names the
    span round `als_train`'s wait for its cache save: a rename here would
    make it fall silent there."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[
            "train.save_join_s"]
    with open(os.path.join(root, "perf", "layers",
                           "train.save_join_s.json")) as f:
        spec = json.load(f)
    assert (spec["reader"], spec["spans"]) == (
        "program_span", ["als.bucket_cache.join"])
    assert (entry["unit"], entry["moves"], entry["layer"]) == (
        spec["unit"], spec["moves"], spec["layer"]) == (
        "s", "train_call_s", "training read, host")
    assert entry["workloads"] == ["als64.train10", "als128i.train10"]
    u, i, r = ratings()
    _, tl = under_timeline(lambda: als_train(
        u, i, r, N_USERS, N_ITEMS, CFG,
        bucket_cache_dir=str(tmp_path / "buckets")))
    assert [n for n, *_ in tl.spans].count(spec["spans"][0]) == 1


def _stage(name, inner=None):
    with spans.span(name):
        if inner:
            spans.record(inner, 0.0)
        return threading.get_ident()


@pytest.mark.parametrize("inside", [False, True],
                         ids=["joined_at_depth_0", "joined_inside_a_span"])
def test_a_workers_spans_reach_the_starters_timeline_at_the_join(inside):
    """A `spans.Worker` records on a timeline of its own and `join`
    copies the records over: true intervals, nested where the worker
    nested them or where the join stands inside a live span."""
    before = set(threading.enumerate())

    def run():
        with (spans.span("outer") if inside else contextlib.nullcontext()):
            workers = [spans.Worker(f"w{k}", _stage, f"stage{k}", "leaf")
                       for k in range(2)]
            idents = [w.join() for w in workers]
            assert spans.current().depth == int(inside)
            return idents
    idents, tl = under_timeline(run)
    assert threading.get_ident() not in idents  # (a thread's may be reused)
    assert set(threading.enumerate()) == before
    got = {n: (s, s + d, nested) for n, s, d, _e, nested in tl.spans}
    assert sorted(n for n, *_ in tl.spans) == sorted(
        ["leaf", "leaf", "stage0", "stage1"] + ["outer"] * inside)
    assert got["leaf"][2] is True
    assert got["stage0"][2] is got["stage1"][2] is inside
    if inside:
        lo, hi, _ = got["outer"]
        assert all(lo <= s and e <= hi for s, e, _ in got.values())
    assert all(0.0 <= s <= e for s, e, _ in got.values())
    assert tl.dropped_spans == 0 and tl.depth == 0


def test_many_workers_at_once_lose_no_record_and_leave_the_depth_alone():
    """More workers than cores under a short switch interval: each has
    its own timeline, so no `depth` update or record can be lost."""
    import sys

    n = 4 * (os.cpu_count() or 2)
    per = max(1, (spans.MAX_SPANS - 8) // (2 * n))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def stages(k):
            for j in range(per):
                _stage(f"stage{k}.{j}", "leaf")

        def run():
            with spans.span("outer"):
                workers = [spans.Worker(f"w{k}", stages, k) for k in range(n)]
                for w in workers:
                    w._thread.join(timeout=60)
                    assert not w._thread.is_alive()
                    w.join()
                assert spans.current().depth == 1
        _, tl = under_timeline(run)
    finally:
        sys.setswitchinterval(interval)
    names = [s[0] for s in tl.spans]
    assert names.count("leaf") == n * per and tl.dropped_spans == 0
    assert {x for x in names if x.startswith("stage")} == {
        f"stage{k}.{j}" for k in range(n) for j in range(per)}
    assert tl.depth == 0 and all(s[4] for s in tl.spans if s[0] != "outer")


def test_a_worker_raises_in_the_starter_and_records_nothing_without_a_timeline():
    def boom():
        with spans.span("stage"):
            raise KeyError("lost")
    worker = spans.Worker("w", boom)
    worker.wait()
    with pytest.raises(KeyError, match="lost"):
        worker.join()
    assert not worker._thread.is_alive() and spans.current() is None
    # under a timeline the failed stage is on it, marked
    def run():
        with pytest.raises(KeyError):
            spans.Worker("w", boom).join()
    _, tl = under_timeline(run)
    assert [(n, e) for n, _s, _d, e, _n in tl.spans] == [("stage", True)]


@pytest.mark.parametrize("chunks,expected", [
    # the first chunk compiled: left out, the two warm ones make the mean
    ([(1, 9.0, 0.5, True), (1, 0.25, 0.75, False), (1, 0.5, 0.5, False)],
     [1.0, 1.0, 1.0]),
    # every chunk compiled: the fences alone, over all the steps
    ([(4, 30.0, 2.0, True)], [0.5] * 4),
    ([(2, 0.5, 1.5, False), (1, 0.25, 0.75, False)], [1.0] * 3),
    ([], []),
], ids=["first_compiled", "all_compiled", "all_warm", "fully_resumed"])
def test_epoch_times_come_from_the_loop_stamps_of_warm_chunks(
        chunks, expected):
    assert epoch_times_of([LoopChunk(*c) for c in chunks]) == expected


def test_a_checkpointed_trains_epoch_times_are_built_from_its_loop_spans(
        tmp_path):
    als._get_train_loop.cache_clear()  # the first chunk compiles
    u, i, r = ratings()
    cfg = dataclasses.replace(CFG, iterations=3)
    result, tl = under_timeline(lambda: als_train(
        u, i, r, N_USERS, N_ITEMS, cfg,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1))
    chunks = result.loop_chunks
    assert [c.steps for c in chunks] == [1, 1, 1]
    assert [c.compiled for c in chunks] == [True, False, False]
    assert result.epoch_times == epoch_times_of(chunks)
    assert result.epoch_times == [
        (chunks[1].dispatch_s + chunks[1].wait_s
         + chunks[2].dispatch_s + chunks[2].wait_s) / 2] * 3
    # the stamps are those around the loop's spans: one pair a chunk, each
    # stamp no shorter than the span inside it; saves lie outside them
    by_name = {}
    for n, s, e in als_spans(tl):
        by_name.setdefault(n, []).append(e - s)
    for k, name in ((1, "als.loop.dispatch"), (2, "als.loop.wait")):
        assert len(by_name[name]) == 3
        assert all(c[k] >= d for c, d in zip(chunks, by_name[name]))
    saves = [n for n, *_ in tl.spans if n.startswith("checkpoint.save")]
    assert len(saves) == 3 and len(by_name["als.readback"]) == 3


@pytest.fixture()
def ecommerce(memory_storage):
    ingest(memory_storage)
    variant = EngineVariant.from_dict(variant_dict({"numIterations": 2}))
    engine = get_engine(variant.engine_factory)
    return engine, extract_engine_params(engine, variant), variant


def test_engine_train_records_the_dase_stages(memory_storage, ecommerce):
    engine, ep, _ = ecommerce
    ctx = WorkflowContext(storage=memory_storage, seed=1)
    _, tl = under_timeline(lambda: engine.train(ctx, ep))
    starts = {n: s for n, s, _d, _e, _nested in tl.spans}
    assert (starts["dase.read"] < starts["dase.prepare"]
            < starts["dase.train"] < starts["als.train"]
            < starts["model.unit_norm"])


def test_run_train_writes_one_train_phases_record(memory_storage, ecommerce,
                                                  tmp_path):
    engine, ep, variant = ecommerce
    path = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(path) as metrics:
        ctx = WorkflowContext(storage=memory_storage, seed=1,
                              metrics=metrics)
        CoreWorkflow.run_train(engine, ep, variant, ctx)
    records = [json.loads(x) for x in open(path)]
    phases = [x for x in records if x["stage"] == "train/phases"]
    assert len(phases) == 1
    assert {"workflow.train", "dase.read", "dase.prepare", "dase.train",
            "als.train", "als.bucketize", "als.put_buckets",
            "als.init_factors", "als.loop.dispatch", "als.loop.wait",
            "als.readback", "model.unit_norm", "workflow.serialize",
            "workflow.persist", "dropped_spans"} <= set(phases[0])
    assert phases[0]["dropped_spans"] == 0
    assert sum(k.startswith(PATHS) for k in phases[0]) == 1  # both sides' path
    assert phases[0]["workflow.train"] >= phases[0]["dase.train"] >= (
        phases[0]["als.train"])
    assert [x["step"] for x in records if x["stage"] == "train/als"] == [1, 2]


@pytest.mark.parametrize("leaf_bytes,path", [(8 << 20, "inline"),
                                             (64, "parallel")])
def test_run_train_with_a_bucket_cache_writes_the_digest_as_a_wall(
        memory_storage, ecommerce, tmp_path, monkeypatch, leaf_bytes, path):
    """`train/phases` carries `als.digest` once a train, the calling
    thread's one span whatever hashed the leaves, and the path's record
    inside it; the workers leave no name of their own."""
    monkeypatch.setattr(WorkflowContext, "algorithm_cache_dir",
                        lambda self, name: str(tmp_path / "cache" / name))
    monkeypatch.setattr(als, "_DIGEST_LEAF_BYTES", leaf_bytes)
    cores(monkeypatch, 3)
    engine, ep, variant = ecommerce
    metrics_file = str(tmp_path / "metrics.jsonl")
    before = set(threading.enumerate())
    with MetricsLogger(metrics_file) as metrics:
        ctx = WorkflowContext(storage=memory_storage, seed=1,
                              metrics=metrics)
        CoreWorkflow.run_train(engine, ep, variant, ctx)
    assert set(threading.enumerate()) == before
    (phases,) = [x for x in map(json.loads, open(metrics_file))
                 if x["stage"] == "train/phases"]
    assert sorted(k for k in phases if k.startswith("als.digest")) == [
        "als.digest", f"als.digest.{path}"]
    assert phases["als.digest"] >= phases[f"als.digest.{path}"] > 0
    assert phases["als.train"] >= phases["als.digest"]
    assert phases["dropped_spans"] == 0
    assert not any(k.startswith("als-digest") for k in phases)


def test_run_train_writes_the_process_first_seconds_beside_the_phases(
        memory_storage, ecommerce, tmp_path):
    """From the compile log: what the process paid before the timeline
    opened, and the phases of a first call inside its `jit.compile`."""
    from predictionio_tpu.ops import als
    from predictionio_tpu.telemetry import device

    als._get_train_loop.cache_clear()   # the loop is built in this train
    device.COMPILE_LOG.clear()   # a long test process may have filled it
    device.COMPILE_LOG.add("process.import", "an.import", 1.0, 1.25)
    engine, ep, variant = ecommerce
    path = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(path) as metrics:
        ctx = WorkflowContext(storage=memory_storage, seed=1,
                              metrics=metrics)
        CoreWorkflow.run_train(engine, ep, variant, ctx)
    (phases,) = [x for x in map(json.loads, open(path))
                 if x["stage"] == "train/phases"]
    assert "runtime.backend_init" in phases
    assert phases["process.import"] >= 0.25
    (init,) = [r for r in device.COMPILE_LOG.records()
               if r.phase == "runtime.backend_init"][-1:]
    assert init.fn == "workflow.train"
    loop = "als.train_steps"
    assert {f"jit.compile.{loop}", f"jit.trace.{loop}", f"jit.lower.{loop}",
            f"jit.backend.{loop}"} <= set(phases)
    assert phases[f"jit.compile.{loop}"] >= (
        phases[f"jit.trace.{loop}"] + phases[f"jit.lower.{loop}"]
        + phases[f"jit.backend.{loop}"]) - 1e-3
    assert phases["als.loop.dispatch"] >= phases[f"jit.compile.{loop}"] - 1e-3


def test_run_train_writes_the_phases_of_a_failed_run(memory_storage,
                                                     ecommerce, tmp_path,
                                                     monkeypatch):
    engine, ep, variant = ecommerce
    from predictionio_tpu.templates.ecommerce import engine as template

    def boom(*a, **k):
        raise RuntimeError("no chip")
    monkeypatch.setattr(template, "als_train", boom)
    path = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(path) as metrics:
        ctx = WorkflowContext(storage=memory_storage, seed=1,
                              metrics=metrics)
        with pytest.raises(RuntimeError, match="no chip"):
            CoreWorkflow.run_train(engine, ep, variant, ctx)
    phases = [json.loads(x) for x in open(path)]
    assert [x["stage"] for x in phases] == ["train/phases"]
    assert "dase.train" in phases[0] and "als.train" not in phases[0]


def _prepared(module):
    u, i, r = ratings()
    fields = {"user_ids": BiMap.string_int([f"u{k}" for k in range(N_USERS)]),
              "item_ids": BiMap.string_int([f"i{k}" for k in range(N_ITEMS)]),
              "user_idx": u, "item_idx": i, "item_categories": {}}
    value = ({"confidence": r} if "confidence" in
             {f.name for f in dataclasses.fields(module.PreparedData)}
             else {"counts": r})
    return module.PreparedData(**fields, **value)


@pytest.mark.parametrize("template,algorithm", [
    ("ecommerce", "ECommAlgorithm"), ("similarproduct", "ALSAlgorithm")])
def test_the_implicit_templates_emit_train_als(tmp_path, template, algorithm):
    import importlib

    module = importlib.import_module(
        f"predictionio_tpu.templates.{template}.engine")
    algo_cls = getattr(module, algorithm)
    algo = algo_cls(algo_cls.params_class(rank=4, numIterations=3, seed=1))
    path = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(path) as metrics:
        _, tl = under_timeline(lambda: algo.train(
            WorkflowContext(metrics=metrics), _prepared(module)))
    lines = [json.loads(x) for x in open(path)]
    assert [x["step"] for x in lines if x["stage"] == "train/als"] == [1, 2, 3]
    assert all(x["epoch_time_s"] > 0 for x in lines)
    assert "model.unit_norm" in [s[0] for s in tl.spans]


# -- the encoder as a Mamba-2 hybrid: what the benchmark's seven read -----------

SSD_METRICS = ("fit.ssd_s", "fit.ssd_scan_s", "fit.ssd_scan_roofline",
               "fit.gqa_attn_s", "fit.ffn_s", "fit.ssd_step_mfu",
               "fit.ssd_boundary_chunk_share")


@pytest.fixture(scope="module")
def hybrid_step_text():
    """Lowered text, with locations, of one train step of the tiny
    Mamba-2 hybrid the benchmark's tests run."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import encoder as enc

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = enc.EncoderConfig.from_json(os.path.join(
        root, "perf", "tests", "tiny", "granite_4_0_h_micro_1of8.json"))
    state = jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len), jnp.int32)
    return jax.jit(enc.train_step(cfg, 1e-3)).lower(
        state, batch, batch, batch).as_text(debug_info=True)


@pytest.mark.parametrize("name", SSD_METRICS)
def test_the_benchmarks_ssd_metrics_read_what_the_program_has(
        hybrid_step_text, name):
    """Each of the seven is a data file of the benchmark that names the
    program's scopes, its step's module or its gauges: a rename here
    would make it fall silent there. Every `known` list names all the
    scopes the step opens, so that no op falls to an enclosing scope by
    omission."""
    from predictionio_tpu.templates.sessionrec import engine  # noqa: F401

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    with open(os.path.join(root, "perf", "layers", name + ".json")) as f:
        spec = json.load(f)
    assert (entry["unit"], entry["moves"], entry["layer"]) == (
        spec["unit"], spec["moves"], spec["layer"])
    assert entry["workloads"] == ["granite4h.fit16_pack8k"]
    if spec["reader"] == "gauge_ratio":
        for gauge in (spec["numerator"], spec["denominator"]):
            assert REGISTRY.get(gauge) is not None, gauge
        assert REGISTRY.get("encoder_ssd_resets_total") is not None
        return
    assert "@jit_sessionrec_train_step" in hybrid_step_text
    # a location names its ops `<outer scopes>/<scope>/<primitive>`, or
    # `jvp(<scope>)` / `transpose(jvp(<scope>))` round an outermost one
    opened = set(re.findall(r'["/(](enc\.[a-z_.]+)[/)]', hybrid_step_text))
    if "known" in spec:
        assert set(spec["known"]) == opened
        assert set(spec["scopes"]) <= opened
    else:  # the whole step's share of the peak: its operations' file
        assert os.path.exists(os.path.join(root, "perf", "ops",
                                           spec["ops"] + ".py"))


# -- the encoder that routes before it mixes: what the benchmark's nine read --------

ST_METRICS = ("fit.st_router_s", "fit.st_experts_s", "fit.st_attn_swa_s",
              "fit.st_attn_full_s", "fit.st_head_loss_s", "fit.st_adam_s",
              "fit.st_step_mfu", "fit.st_expert_load_max_over_mean",
              "fit.st_moe_block_fill")


@pytest.fixture(scope="module")
def routed_step_text():
    """Lowered text, with locations, of one train step of the tiny
    configuration in SmallThinker's key names the benchmark's tests run."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import encoder as enc

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = enc.EncoderConfig.from_json(os.path.join(
        root, "perf", "tests", "tiny", "smallthinker_21b_1of4.json"))
    state = jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len), jnp.int32)
    return jax.jit(enc.train_step(cfg, 1e-3)).lower(
        state, batch, batch, batch).as_text(debug_info=True)


@pytest.mark.parametrize("name", ST_METRICS)
def test_the_benchmarks_st_metrics_read_what_the_program_has(
        routed_step_text, name):
    """Each of the nine is a data file of the benchmark that names the
    program's scopes, its step's module or its gauges: a rename here
    would make it fall silent there. Every `known` list names all the
    scopes the step opens (the router's, the plan's below the experts',
    the windowed and the full attention's apart), so that no op falls to
    an enclosing scope by omission."""
    from predictionio_tpu.templates.sessionrec import engine  # noqa: F401

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    with open(os.path.join(root, "perf", "layers", name + ".json")) as f:
        spec = json.load(f)
    assert (entry["unit"], entry["moves"], entry["layer"]) == (
        spec["unit"], spec["moves"], spec["layer"])
    assert entry["workloads"] == ["smallthinker.fit8_pack8k"]
    if spec["reader"] in ("gauge_ratio", "gauge_max_over_mean"):
        for key in ("gauge", "numerator", "denominator"):
            assert key not in spec or REGISTRY.get(spec[key]) is not None
        return
    assert "@jit_sessionrec_train_step" in routed_step_text
    # the plan's scope stands right below the experts': a lookahead, so
    # that one slash can end a scope and begin the next
    opened = set(re.findall(r'["/(](enc\.[a-z_.]+)(?=[/)])',
                            routed_step_text))
    if "known" in spec:
        assert set(spec["known"]) == opened
        assert set(spec["scopes"]) <= opened
    else:  # the whole step's share of the peak: its operations' file
        assert os.path.exists(os.path.join(root, "perf", "ops",
                                           spec["ops"] + ".py"))


# -- the encoder whose layers are one sublayer each: what the benchmark's twelve read --

NH_METRICS = ("fit.nh_ssd_s", "fit.nh_ssd_scan_s", "fit.nh_ssd_scan_roofline",
              "fit.nh_attn_s", "fit.nh_router_s", "fit.nh_experts_s",
              "fit.nh_shared_s", "fit.nh_head_loss_s", "fit.nh_adam_s",
              "fit.nh_step_mfu", "fit.nh_expert_load_max_over_mean",
              "fit.nh_moe_block_fill")


def _tiny_step_text(config: str) -> str:
    """Lowered text, with locations, of one train step of the tiny
    configuration `config` the benchmark's tests run."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import encoder as enc

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = enc.EncoderConfig.from_json(os.path.join(
        root, "perf", "tests", "tiny", config + ".json"))
    state = jax.eval_shape(
        lambda k: enc.init_state(cfg, cfg.vocab_size, k), jax.random.key(0))
    batch = jax.ShapeDtypeStruct((cfg.seqs_per_step, cfg.pack_len), jnp.int32)
    return jax.jit(enc.train_step(cfg, 1e-3)).lower(
        state, batch, batch, batch).as_text(debug_info=True)


def _reads_what_the_program_has(text: str, name: str, cell: str,
                                scopes: int, ops: str) -> None:
    """The metric `name` is a data file of the benchmark that names the
    program's scopes, its step's module or its gauges; its `known` list
    names all `scopes` scopes the step `text` opens; it lists `cell`
    alone; a file without `ops` is read with the step's `ops` file."""
    from predictionio_tpu.templates.sessionrec import engine  # noqa: F401

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    with open(os.path.join(root, "perf", "layers", name + ".json")) as f:
        spec = json.load(f)
    assert (entry["unit"], entry["moves"], entry["layer"]) == (
        spec["unit"], spec["moves"], spec["layer"])
    assert entry["workloads"] == [cell]
    if spec["reader"] in ("gauge_ratio", "gauge_max_over_mean"):
        for key in ("gauge", "numerator", "denominator"):
            assert key not in spec or REGISTRY.get(spec[key]) is not None
        return
    assert "@jit_sessionrec_train_step" in text
    opened = set(re.findall(r'["/(](enc\.[a-z_.]+)(?=[/)])', text))
    if "known" in spec:
        assert set(spec["known"]) == opened and len(opened) == scopes
        assert set(spec["scopes"]) <= opened
    assert os.path.exists(os.path.join(
        root, "perf", "ops", spec.get("ops", ops) + ".py"))


@pytest.fixture(scope="module")
def sublayer_step_text():
    return _tiny_step_text("nemotron3_nano_30b_1of16")


@pytest.mark.parametrize("name", NH_METRICS)
def test_the_benchmarks_nh_metrics_read_what_the_program_has(
        sublayer_step_text, name):
    """Each of the twelve is a data file of the benchmark that names the
    program's scopes, its step's module or its gauges: a rename here
    would make it fall silent there. Every `known` list names all
    seventeen scopes the step opens (the scan's six below `enc.ssd`,
    attention's three, the router's, the plan's below the experts', the
    shared expert's), so that no op falls to an enclosing scope by
    omission."""
    _reads_what_the_program_has(sublayer_step_text, name,
                                "nemotron3nano.fit16_pack8k", 17,
                                "nemotron_h_step")


# -- the encoder of short convolutions and attention: what the benchmark's thirteen read --

LFM_METRICS = ("fit.lfm_sconv_s", "fit.lfm_sconv_gated_s",
               "fit.lfm_sconv_gated_roofline", "fit.lfm_attn_s",
               "fit.lfm_qk_norm_s", "fit.lfm_dense_ffn_s", "fit.lfm_router_s",
               "fit.lfm_experts_s", "fit.lfm_head_loss_s", "fit.lfm_adam_s",
               "fit.lfm_step_mfu", "fit.lfm_expert_load_max_over_mean",
               "fit.lfm_moe_block_fill")


@pytest.fixture(scope="module")
def conv_step_text():
    return _tiny_step_text("lfm2_24b_a2b_1of8")


@pytest.mark.parametrize("name", LFM_METRICS)
def test_the_benchmarks_lfm_metrics_read_what_the_program_has(
        conv_step_text, name):
    """The thirteen, likewise. Every `known` list names all sixteen
    scopes the step opens (the mixer's four below `enc.sconv`,
    attention's four below `enc.gqa_full` with the norm a head on q and
    k among them, the dense feed-forward's, the router's, the plan's
    below the experts')."""
    _reads_what_the_program_has(conv_step_text, name, "lfm2.fit8_pack8k",
                                16, "lfm2_step")
