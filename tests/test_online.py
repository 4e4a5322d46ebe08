"""Online-learning plane (round 11): tailer extraction, fold-in math,
hot delta-swap, and the event→servable loop end to end.

Covers the receipts `quality.py --online-gate` drills operationally:

- StoreTailer extraction — RewardTailer is a thin subclass that only
  supplies the $reward filter and posterior update; the watermark +
  overlap + dedup loop is inherited, with streaming (at-most-once per
  event) and batch (at-least-once, crash-replayed) delivery modes.
- Fold-in math — a single-row fold is bitwise one ALS half-epoch
  restricted to that row; cold-start ids append rows without disturbing
  existing codes; replaying a fold against fixed opposing factors is
  bit-identical (what makes at-least-once delivery safe).
- Delta-swap — per-user cache invalidation: a fold drops exactly the
  touched users' result-cache entries (cross-user survival), while a
  full /reload still drops the whole variant; a swap computed against a
  replaced state is refused (StaleState) instead of clobbering it.
- End to end — a never-seen user becomes servable after one poll; a
  crash between fold-in and watermark advance replays to bit-identical
  factors with zero events lost; the plane-wide parity check bounds
  drift against a fresh half-epoch.
"""

import contextlib
import threading
from datetime import datetime, timedelta, timezone
from types import SimpleNamespace

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.events import Event
from predictionio_tpu.experiment.rewards import RewardTailer
from predictionio_tpu.ingest.tailer import OVERLAP, StoreTailer
from predictionio_tpu.models.als_model import ALSModel
from predictionio_tpu.online import (
    DeltaSwapper,
    OnlineConfig,
    SeenOverlay,
    StaleState,
    fold_model,
    solve_rows,
)
from predictionio_tpu.online.foldin import extend_bimap
from predictionio_tpu.ops.als import ALSConfig
from predictionio_tpu.serving.plane import ServingConfig, ServingPlane
from predictionio_tpu.serving.result_cache import MISS, ResultCache
from predictionio_tpu.utils.faults import FaultInjected
from predictionio_tpu.workflow.create_server import (
    PredictionServer,
    ServerConfig,
)
from tests.test_experiment import train_variant
from tests.test_recommendation_template import ingest_ratings

T0 = datetime(2026, 3, 1, tzinfo=timezone.utc)


def _event(user, item, t, event="rate", rating=5.0):
    return Event(event=event, entity_type="user", entity_id=user,
                 target_entity_type="item", target_entity_id=item,
                 properties=DataMap({"rating": rating}), event_time=t)


class _Recorder(StoreTailer):
    """Streaming-mode consumer that records what it was handed."""

    def __init__(self, storage, **kw):
        super().__init__(storage, **kw)
        self.applied = []

    def _apply(self, e) -> bool:
        self.applied.append(e.target_entity_id)
        return True


class TestStoreTailer:
    def test_reward_tailer_is_a_thin_subclass(self, memory_storage):
        assert issubclass(RewardTailer, StoreTailer)
        # the tail machinery is inherited, not re-implemented: the
        # subclass only supplies the filter and the apply hook
        for inherited in ("poll_once", "_collect", "_process", "_mark",
                          "start", "stop", "_run"):
            assert getattr(RewardTailer, inherited) is \
                getattr(StoreTailer, inherited)

        class _Bandit:
            def __init__(self):
                self.rewards = []

            def reward(self, variant, r):
                self.rewards.append((variant, r))
                return True

            def posterior_mean(self, variant):
                return 0.5

        bandit = _Bandit()
        rt = RewardTailer(memory_storage, bandit)
        assert rt.event_names == ["$reward"]
        assert rt.name == "reward-tailer"
        le = memory_storage.l_events()
        le.insert(Event(event="$reward", entity_type="user", entity_id="u1",
                        properties=DataMap({"variant": "a", "reward": 1.0}),
                        event_time=T0), 1)
        le.insert(_event("u1", "i1", T0), 1)  # filtered by event_names
        assert rt.poll_once() == 1
        assert bandit.rewards == [("a", 1.0)]

    def test_streaming_delivery_in_time_order(self, memory_storage):
        le = memory_storage.l_events()
        # inserted out of event-time order; delivery must sort
        le.insert(_event("u1", "i2", T0 + timedelta(seconds=2)), 1)
        le.insert(_event("u1", "i0", T0), 1)
        le.insert(_event("u1", "i1", T0 + timedelta(seconds=1)), 1)
        t = _Recorder(memory_storage)
        assert t.poll_once() == 3
        assert t.applied == ["i0", "i1", "i2"]
        assert t.poll_once() == 0  # dedup: nothing re-applied

    def test_overlap_catches_late_arrivals_without_redelivery(
            self, memory_storage):
        le = memory_storage.l_events()
        le.insert(_event("u1", "i0", T0), 1)
        t = _Recorder(memory_storage)
        assert t.poll_once() == 1
        # a group-commit straggler lands with an event_time BEHIND the
        # watermark but inside the overlap window: it must be delivered
        # exactly once, and i0 must not come back with it
        late = T0 - OVERLAP + timedelta(seconds=0.5)
        le.insert(_event("u1", "late", late), 1)
        assert t.poll_once() == 1
        assert t.applied == ["i0", "late"]

    def test_event_name_filter_and_max_batch(self, memory_storage):
        le = memory_storage.l_events()
        for i in range(3):
            le.insert(_event("u1", f"i{i}", T0 + timedelta(seconds=i)), 1)
        le.insert(_event("u1", "bought", T0, event="buy"), 1)
        t = _Recorder(memory_storage, event_names=["rate"], max_batch=2)
        assert t.poll_once() == 2  # capped
        assert t.poll_once() == 1  # the remainder, next pass
        assert t.applied == ["i0", "i1", "i2"]  # "buy" never delivered

    def test_streaming_is_at_most_once_per_event(self, memory_storage):
        """The original RewardTailer contract: each event is marked
        consumed BEFORE _apply runs, so a consumer that throws does not
        get the same event twice (a bandit reward must not double)."""
        class _Flaky(_Recorder):
            def _apply(self, e):
                if e.target_entity_id == "i1":
                    raise RuntimeError("consumer died mid-batch")
                return super()._apply(e)

        le = memory_storage.l_events()
        for i in range(3):
            le.insert(_event("u1", f"i{i}", T0 + timedelta(seconds=i)), 1)
        t = _Flaky(memory_storage)
        with pytest.raises(RuntimeError, match="mid-batch"):
            t.poll_once()
        # i0 applied, i1 marked-but-lost (at most once), i2 still fresh
        assert t.poll_once() == 1
        assert t.applied == ["i0", "i2"]

    def test_batch_mode_replays_the_whole_batch_after_a_crash(
            self, memory_storage):
        """The online plane's mode: nothing is marked until _process
        returns, so a crash between fold and watermark advance replays
        the complete batch (at-least-once; fold-in idempotence makes
        the replay free)."""
        class _Batcher(StoreTailer):
            def __init__(self, storage, **kw):
                super().__init__(storage, **kw)
                self.batches = []
                self.crash_next = False

            def _process(self, fresh):
                if fresh and self.crash_next:
                    self.crash_next = False
                    raise RuntimeError("died before the watermark")
                self.batches.append([e.target_entity_id for e in fresh])
                for e in fresh:
                    self._mark(e)
                return len(fresh)

        le = memory_storage.l_events()
        for i in range(3):
            le.insert(_event("u1", f"i{i}", T0 + timedelta(seconds=i)), 1)
        t = _Batcher(memory_storage)
        t.crash_next = True
        with pytest.raises(RuntimeError, match="watermark"):
            t.poll_once()
        assert t.batches == []  # nothing acked before the crash
        assert t.poll_once() == 3  # the SAME batch, replayed whole
        assert t.batches == [["i0", "i1", "i2"]]
        assert t.poll_once() == 0


class TestFoldInMath:
    # rank-4 explicit config; "chol" pinned so auto-resolution can never
    # change the parity reference out from under the bitwise asserts
    CFG = ALSConfig(rank=4, reg=0.1, solver="chol")

    @staticmethod
    def _entries(rng, n_rows=8, n_opposing=8, nnz=4):
        # every row gets the SAME nnz so single-row and batched solves
        # land in identically-shaped buckets: the batched CPU
        # Cholesky/triangular-solve picks kernels by batch shape, so
        # bitwise equality only holds at matched shapes (bucket_ragged
        # pads rows to a multiple of 8 — 8 rows with one cap match a
        # 1-row fold padded to the same [8, cap] bucket)
        out = []
        for _ in range(n_rows):
            cols = np.sort(rng.choice(n_opposing, size=nnz,
                                      replace=False)).astype(np.int32)
            vals = (1.0 + 4.0 * rng.random(nnz)).astype(np.float32)
            out.append((cols, vals))
        return out

    def test_single_row_fold_bitwise_matches_the_batched_half_epoch(self):
        rng = np.random.default_rng(7)
        opposing = rng.standard_normal((8, 4)).astype(np.float32)
        entries = self._entries(rng)
        full = solve_rows(opposing, entries, self.CFG)
        assert full.shape == (8, 4)
        for u in range(8):
            single = solve_rows(opposing, [entries[u]], self.CFG)
            assert np.array_equal(single[0], full[u]), (
                f"row {u}: a lone fold diverged from the same row solved "
                f"inside the full half-epoch")

    def test_fold_solves_the_weighted_normal_equations(self):
        rng = np.random.default_rng(11)
        opposing = rng.standard_normal((8, 4)).astype(np.float32)
        entries = self._entries(rng)
        solved = solve_rows(opposing, entries, self.CFG)
        for (cols, vals), x in zip(entries, solved):
            yc = opposing[cols].astype(np.float64)
            # ALS-WR: (YᵀY + λ·n·I) x = Yᵀ r with n = this row's nnz
            a = yc.T @ yc + self.CFG.reg * len(cols) * np.eye(4)
            ref = np.linalg.solve(a, yc.T @ vals.astype(np.float64))
            np.testing.assert_allclose(x, ref, rtol=1e-3, atol=1e-4)

    def test_fold_bucketizes_on_the_comparison_path_bitwise_as_numpy(self):
        # a few histories against a catalog-wide column range: the native
        # bucketizer must not build a histogram over the catalog for
        # them, and what it builds is the numpy path's, so the solve is
        import unittest.mock as mock

        from predictionio_tpu import native
        from predictionio_tpu.ops.als import BUCKETIZE_CALLS

        if not native.native_available():
            pytest.skip("no C++ toolchain")
        rng = np.random.default_rng(5)
        opposing = rng.standard_normal((200_000, 4)).astype(np.float32)
        entries = self._entries(rng, n_rows=24, n_opposing=200_000, nnz=9)
        entries[3] = (np.zeros(0, np.int32), np.zeros(0, np.float32))

        def calls(path):
            return BUCKETIZE_CALLS.labels(side="fold", path=path).value

        before = {p: calls(p) for p in
                  ("native_comparison", "native_counting", "numpy")}
        solved = solve_rows(opposing, entries, self.CFG)
        assert calls("native_comparison") == before["native_comparison"] + 1
        assert calls("native_counting") == before["native_counting"]
        assert calls("numpy") == before["numpy"]
        with mock.patch.object(native, "bucket_ragged_native",
                               return_value=None):
            reference = solve_rows(opposing, entries, self.CFG)
        assert calls("numpy") == before["numpy"] + 1
        assert solved.tobytes() == reference.tobytes()

    def test_empty_history_rows_solve_to_zeros(self):
        rng = np.random.default_rng(3)
        opposing = rng.standard_normal((8, 4)).astype(np.float32)
        empty = (np.zeros(0, np.int32), np.zeros(0, np.float32))
        solved = solve_rows(opposing, [*self._entries(rng, n_rows=2),
                                       empty], self.CFG)
        assert np.array_equal(solved[2], np.zeros(4, np.float32))
        assert solved[:2].any(axis=1).all()

    @staticmethod
    def _model(rng):
        return ALSModel(
            user_factors=rng.standard_normal((5, 4)).astype(np.float32),
            item_factors=rng.standard_normal((6, 4)).astype(np.float32),
            user_ids=BiMap.string_int([f"u{i}" for i in range(5)]),
            item_ids=BiMap.string_int([f"i{i}" for i in range(6)]),
            seen={0: np.asarray([1, 2], np.int32)})

    def test_cold_start_appends_rows_without_disturbing_existing(self):
        rng = np.random.default_rng(5)
        model = self._model(rng)
        folded, stats = fold_model(
            model, self.CFG, {"newu": [("i1", 5.0), ("newi", 3.0)]})
        assert (stats.new_users, stats.new_items) == (1, 1)
        assert (stats.folded_users, stats.folded_items) == (1, 0)
        # never-seen ids take the next dense codes; old codes keep rows
        assert folded.user_ids["newu"] == 5
        assert folded.item_ids["newi"] == 6
        uf = np.asarray(folded.user_factors)
        itf = np.asarray(folded.item_factors)
        assert np.array_equal(uf[:5], np.asarray(model.user_factors))
        assert np.array_equal(itf[:6], np.asarray(model.item_factors))
        assert uf[5].any()  # the cold user's row actually solved
        # the cold ITEM was only referenced, never folded: zero row
        assert np.array_equal(itf[6], np.zeros(4, np.float32))
        # seen overlay: the folded user excludes their rated items; the
        # untouched user's base seen set survives
        assert set(folded.seen.get(5)) == {1, 6}
        assert np.array_equal(folded.seen.get(0),
                              np.asarray([1, 2], np.int32))
        # and the input model was never mutated (serving reads it until
        # the swap lands)
        assert model.user_ids.get("newu") is None
        assert np.asarray(model.user_factors).shape == (5, 4)

    def test_fold_is_bitwise_idempotent_against_fixed_opposing(self):
        # the crash-replay guarantee: same history + same opposing
        # factors → byte-identical factors (item folds off; with them on
        # a replay is one extra alternation half-step — convergent, not
        # byte-stable, see docs/online.md)
        rng = np.random.default_rng(13)
        model = self._model(rng)
        hist = {"u1": [("i0", 4.0), ("i3", 2.0)], "u4": [("i5", 5.0)]}
        once, _ = fold_model(model, self.CFG, hist)
        twice, _ = fold_model(once, self.CFG, hist)
        assert np.array_equal(np.asarray(once.user_factors),
                              np.asarray(twice.user_factors))
        assert np.array_equal(np.asarray(once.item_factors),
                              np.asarray(twice.item_factors))

    def test_seen_overlay_flattens_and_layers(self):
        base = {0: np.asarray([1], np.int32)}
        one = SeenOverlay(base, {1: np.asarray([2], np.int32)})
        two = SeenOverlay(one, {0: np.asarray([9], np.int32)})
        assert two._base is base  # overlay-on-overlay flattens
        assert np.array_equal(two.get(0), [9])  # newest delta wins
        assert np.array_equal(two.get(1), [2])
        assert two.get(7) is None
        assert bool(SeenOverlay(None, {}))  # truthy even when empty

    def test_extend_bimap_appends_and_preserves(self):
        bm = BiMap.string_int(["a", "b"])
        grown, added = extend_bimap(bm, ["b", "c", "d"])
        assert added == ["c", "d"]
        assert (grown["a"], grown["b"], grown["c"], grown["d"]) \
            == (0, 1, 2, 3)
        same, none_added = extend_bimap(grown, ["a", "d"])
        assert same is grown and none_added == []


class TestFoldModelProtocol:
    """PR receipt: generalizing the plane beyond ALS (foldin.FoldModel)
    left ALS fold-in byte-for-byte intact — ALSFold is a thin adapter
    that strips event times off the protocol's history triples and
    calls the original fold_model, mirroring the StoreTailer extraction
    receipt above."""

    def test_alsfold_is_a_thin_adapter(self):
        from predictionio_tpu.online import ALSFold, FoldModel

        assert issubclass(ALSFold, FoldModel)
        assert ALSFold.family == "als"
        # the adapter adds no solve logic of its own: fold_model is
        # still the one entry point (parity/gate callers keep using it)
        import inspect
        src = inspect.getsource(ALSFold.fold)
        assert "fold_model" in src

    def test_alsfold_fold_is_bit_identical_to_fold_model(self):
        # the same histories, once as the protocol's timed triples and
        # once as fold_model's untimed pairs: byte-equal factors, same
        # appended codes, same stats — the extraction changed nothing
        from predictionio_tpu.online import ALSFold

        rng = np.random.default_rng(17)
        model = TestFoldInMath._model(rng)
        cfg = TestFoldInMath.CFG
        user_pairs = {"u1": [("i0", 4.0), ("i3", 2.0)],
                      "newu": [("i5", 5.0), ("newi", 3.0)]}
        item_pairs = {"i0": [("u1", 4.0), ("u2", 1.0)]}

        def timed(hists):
            return {k: [(o, v, T0 + timedelta(seconds=j))
                        for j, (o, v) in enumerate(pairs)]
                    for k, pairs in hists.items()}

        via_handle, st1 = ALSFold(cfg).fold(
            model, timed(user_pairs), timed(item_pairs))
        direct, st2 = fold_model(model, cfg, user_pairs, item_pairs)
        assert np.array_equal(np.asarray(via_handle.user_factors),
                              np.asarray(direct.user_factors))
        assert np.array_equal(np.asarray(via_handle.item_factors),
                              np.asarray(direct.item_factors))
        assert via_handle.user_ids.to_dict() == direct.user_ids.to_dict()
        assert via_handle.item_ids.to_dict() == direct.item_ids.to_dict()
        assert (st1.folded_users, st1.folded_items, st1.new_users,
                st1.new_items) == (st2.folded_users, st2.folded_items,
                                   st2.new_users, st2.new_items)

    def test_plane_context_keeps_the_als_compat_view(self, memory_storage):
        # parity_check and the gate drills read ctx.als as (idx, config)
        # pairs; the property must recover them from the fold handles
        ingest_ratings(memory_storage)
        train_variant(memory_storage, iters=2)
        with online_server(memory_storage, interval_s=0.05) as server:
            ctx = server.online._contexts[0]
            assert ctx.folds, "variant resolved no fold handles"
            assert [f for _, f in ctx.als] and all(
                isinstance(cfg, ALSConfig) for _, cfg in ctx.als)
            assert [i for i, _ in ctx.als] == \
                [i for i, h in ctx.folds if h.family == "als"]


class TestDeltaSwapper:
    class _Bus:
        def __init__(self):
            self.published = []

        def publish(self, entity_ids, variant=None):
            self.published.append((list(entity_ids), variant))

    def test_swap_replaces_state_and_publishes_touched_users(self):
        state = SimpleNamespace(models=["old"], instance="inst-1")
        states = {"v": state}
        bus = self._Bus()
        swapper = DeltaSwapper(states, threading.Lock(), bus=bus)
        new_state = swapper.swap("v", state, ["new"],
                                 touched_users={"u2", "u1"})
        assert states["v"] is new_state and new_state is not state
        assert new_state.models == ["new"]
        assert new_state.instance == "inst-1"  # everything else copied
        assert state.models == ["old"]  # old immutable state untouched
        assert bus.published == [(["u1", "u2"], "v")]  # sorted, scoped

    def test_stale_swap_is_refused(self):
        state = SimpleNamespace(models=["old"])
        states = {"v": state}
        bus = self._Bus()
        swapper = DeltaSwapper(states, threading.Lock(), bus=bus)
        reloaded = SimpleNamespace(models=["reloaded"])
        states["v"] = reloaded  # a full /reload landed mid-fold
        with pytest.raises(StaleState):
            swapper.swap("v", state, ["folded"], touched_users=["u1"])
        assert states["v"] is reloaded  # the reload was NOT clobbered
        assert bus.published == []  # no invalidation for a refused swap

    def test_per_user_invalidation_spares_other_users_and_variants(self):
        """Satellite receipt: a delta-swap must drop exactly the touched
        users' cache entries — not the whole variant (that's /reload's
        job) and never another variant's."""
        from predictionio_tpu.ingest.invalidation import BUS

        planes = {
            v: ServingPlane(lambda qs: [{"v": q["user"]} for q in qs],
                            config=ServingConfig(batching=False),
                            result_cache=ResultCache(max_entries=64,
                                                     ttl_s=600.0),
                            variant=v)
            for v in ("a", "b")
        }
        try:
            q1, q2 = {"user": "u1", "num": 3}, {"user": "u2", "num": 3}
            for plane in planes.values():
                plane.handle_query(q1, {})
                plane.handle_query(q2, {})
            for v, plane in planes.items():
                assert plane.result_cache.get(q1, v) is not MISS
                assert plane.result_cache.get(q2, v) is not MISS

            state = SimpleNamespace(models=["m"])
            swapper = DeltaSwapper({"a": state}, threading.Lock(), bus=BUS)
            swapper.swap("a", state, ["m2"], touched_users=["u1"])
            cache_a, cache_b = (planes[v].result_cache for v in ("a", "b"))
            assert cache_a.get(q1, "a") is MISS  # folded user dropped
            assert cache_a.get(q2, "a") is not MISS  # cross-user survival
            assert cache_b.get(q1, "b") is not MISS  # other variant intact
            assert cache_b.get(q2, "b") is not MISS
            # the full-reload path still drops the whole variant
            cache_a.invalidate_variant("a")
            assert cache_a.get(q2, "a") is MISS
        finally:
            for plane in planes.values():
                BUS.unsubscribe(plane._invalidate)


@contextlib.contextmanager
def online_server(storage, **online_kw):
    config = ServerConfig(ip="127.0.0.1", port=0, engine_id="rec-test",
                          engine_variant="rec-test")
    server = PredictionServer(config, storage, plugins=None,
                              online=OnlineConfig(**online_kw))
    try:
        # polls are driven by hand in every test: deterministic batches
        server.online.stop()
        yield server
    finally:
        server.shutdown()


def _rate(storage, user, item, rating=5.0):
    app_id = storage.meta_apps().get_by_name("RecApp").id
    storage.l_events().insert(Event(
        event="rate", entity_type="user", entity_id=user,
        target_entity_type="item", target_entity_id=item,
        properties=DataMap({"rating": rating})), app_id)


class TestOnlinePlaneEndToEnd:
    def test_never_seen_user_is_servable_after_one_poll(
            self, memory_storage):
        ingest_ratings(memory_storage)
        train_variant(memory_storage, iters=15)
        with online_server(memory_storage, interval_s=0.05) as server:
            assert server.online is not None
            # u99 lands in the odd-item block; i7 is the odd item they
            # have not rated yet
            for i in (1, 3, 5):
                _rate(memory_storage, "u99", f"i{i}")
            assert server.online.poll_once() == 3
            result, degraded = server.serving.handle_query(
                {"user": "u99", "num": 3}, {})
            assert not degraded
            items = [s["item"] for s in result["itemScores"]]
            assert items, "folded user got no recommendations"
            assert "i7" in items, f"expected the unrated odd item, got {items}"
            assert not {"i1", "i3", "i5"} & set(items), \
                "seen-exclusion lost the folded ratings"
            assert server.online.poll_once() == 0  # watermark advanced
            snap = server.online.snapshot()
            assert snap["variants"] == ["rec-test"]
            assert snap["eventsFolded"] == 3
            assert snap["watermark"] is not None

    def test_crash_between_fold_and_watermark_replays_idempotently(
            self, memory_storage, monkeypatch):
        ingest_ratings(memory_storage)
        train_variant(memory_storage, iters=15)
        # item folds OFF: the opposing factors are fixed across the
        # replay, so recovered factors must be bit-identical (see
        # TestFoldInMath.test_fold_is_bitwise_idempotent_...)
        with online_server(memory_storage, interval_s=0.05,
                           fold_items=False) as server:
            for i in (1, 3, 5):
                _rate(memory_storage, "crash1", f"i{i}")
            monkeypatch.setenv("PIO_FAULTS", "online.pre_watermark=error")
            with pytest.raises(FaultInjected):
                server.online.poll_once()
            # the fold and swap landed BEFORE the crash window...
            model = server._states["rec-test"].models[0]
            row0 = model.user_ids.get("crash1")
            assert row0 is not None, "fold did not land before the crash"
            pre = np.array(np.asarray(model.user_factors)[row0], copy=True)
            # ...and the watermark did not: recovery replays the batch
            monkeypatch.setenv("PIO_FAULTS", "")
            assert server.online.poll_once() == 3
            model2 = server._states["rec-test"].models[0]
            row = model2.user_ids.get("crash1")
            assert np.array_equal(np.asarray(model2.user_factors)[row], pre)
            assert server.online.poll_once() == 0  # settled
            result, _ = server.serving.handle_query(
                {"user": "crash1", "num": 3}, {})
            assert result["itemScores"], "event lost across the crash"

    def test_delta_swap_invalidates_only_the_folded_user(
            self, memory_storage, monkeypatch):
        """The satellite receipt, through the REAL wiring: fold →
        DeltaSwapper → InvalidationBus → ServingPlane subscription →
        per-user drop; /reload keeps its full-variant drop."""
        monkeypatch.setenv("PIO_HTTP_RESULT_CACHE", "1")
        # a fold pass (first one jit-compiles) can outlive the default
        # 5 s TTL; pin it high so expiry can't fake the invalidation
        monkeypatch.setenv("PIO_HTTP_RESULT_CACHE_TTL_S", "600")
        ingest_ratings(memory_storage)
        train_variant(memory_storage, iters=15)
        with online_server(memory_storage, interval_s=0.05) as server:
            cache = server.serving.result_cache
            assert cache is not None
            q0, q2 = {"user": "u0", "num": 3}, {"user": "u2", "num": 3}
            server.serving.handle_query(q0, {})
            server.serving.handle_query(q2, {})
            assert cache.get(q0, "rec-test") is not MISS
            assert cache.get(q2, "rec-test") is not MISS
            _rate(memory_storage, "u0", "i6")
            assert server.online.poll_once() == 1
            assert cache.get(q0, "rec-test") is MISS, \
                "folded user's cached answer survived the swap"
            assert cache.get(q2, "rec-test") is not MISS, \
                "delta-swap dropped an untouched user's entry"
            # full /reload: EVERY answer changed, whole variant drops
            server.serving.handle_query(q0, {})
            server.reload()
            assert cache.get(q0, "rec-test") is MISS
            assert cache.get(q2, "rec-test") is MISS

    def test_reload_rebases_the_plane_and_folding_continues(
            self, memory_storage):
        ingest_ratings(memory_storage)
        train_variant(memory_storage, iters=15)
        with online_server(memory_storage, interval_s=0.05) as server:
            _rate(memory_storage, "u50", "i2")
            assert server.online.poll_once() == 1
            server.reload()  # rebases tailers onto the new instance
            # the replaced state no longer holds the fold, but the plane
            # must keep folding against the NEW state
            _rate(memory_storage, "u51", "i3")
            assert server.online.poll_once() >= 1
            result, _ = server.serving.handle_query(
                {"user": "u51", "num": 3}, {})
            assert result["itemScores"]

    def test_parity_check_bounds_drift(self, memory_storage):
        ingest_ratings(memory_storage)
        train_variant(memory_storage, iters=15)
        with online_server(memory_storage, interval_s=0.05,
                           fold_items=False) as server:
            _rate(memory_storage, "u1", "i7", rating=4.0)
            server.online.poll_once()
            stats = server.online.parity_check()
            assert "rec-test" in stats
            s = stats["rec-test"]
            assert s["rows"] > 0
            assert s["rel_max"] <= 0.05, (
                f"served factors drift {s['rel_max']:.3f} (rel max) from "
                f"a fresh half-epoch")


class TestOnlineConfig:
    def test_env_gating_and_knobs(self, monkeypatch):
        monkeypatch.delenv("PIO_ONLINE", raising=False)
        assert OnlineConfig.from_env() is None
        monkeypatch.setenv("PIO_ONLINE", "1")
        assert OnlineConfig.from_env() == OnlineConfig()
        monkeypatch.setenv("PIO_ONLINE_INTERVAL_S", "0.1")
        monkeypatch.setenv("PIO_ONLINE_MAX_BATCH", "256")
        monkeypatch.setenv("PIO_ONLINE_FOLD_ITEMS", "0")
        monkeypatch.setenv("PIO_ONLINE_PARITY_EVERY_S", "30")
        monkeypatch.setenv("PIO_ONLINE_APP_ID", "7")
        cfg = OnlineConfig.from_env()
        assert cfg == OnlineConfig(interval_s=0.1, max_batch=256,
                                   fold_items=False, parity_every_s=30.0,
                                   app_id=7)

    def test_telemetry_families_render(self):
        from predictionio_tpu.telemetry.registry import REGISTRY

        text = REGISTRY.render()
        for family in ("online_events_folded_total",
                       "online_rows_folded_total",
                       "online_cold_start_rows_total",
                       "online_swaps_total",
                       "online_event_to_servable_seconds",
                       "online_lag_seconds",
                       "online_parity_drift"):
            assert f"# TYPE {family} " in text
