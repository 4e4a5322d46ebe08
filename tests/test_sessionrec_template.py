"""Sessionrec template: DASE train end to end, the sequence-tier
ladder, and the parity contract docs/serving.md points here for —
a history scores bitwise-identically at every tier that fits it,
because pads are exact no-ops (masked attention, last-real-position
readout), and to a few ulp in every batch that carries it (the backend
picks its dot kernel by the batch's shape). Also holds the compile-count
discipline: after warmup, repeat traffic adds zero compiles and the
warmed executable space is bounded by (batch tiers × sequence tiers).
"""

import numpy as np
import pytest

from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.serving.batcher import (
    pad_to_seq_tier,
    seq_tier_ladder,
    seq_tiers_from_env,
)
from predictionio_tpu.templates.sessionrec.engine import (
    DataSource,
    DataSourceParams,
    TrainingData,
    _pad_batch_tier,
    _serve_tiers,
)
from predictionio_tpu.workflow.core_workflow import CoreWorkflow
from predictionio_tpu.workflow.workflow_utils import (
    EngineVariant,
    extract_engine_params,
    get_engine,
)
from tests.test_online_session import ingest_views

FACTORY = "predictionio_tpu.templates.sessionrec.SessionRecEngine"


def variant_dict(app_name="SessApp", max_seq_len=16, epochs=4):
    return {
        "id": "sess-test",
        "engineFactory": FACTORY,
        "datasource": {"params": {"appName": app_name}},
        "algorithms": [{"name": "attention", "params": {
            "embedDim": 8, "numBlocks": 1, "numHeads": 2,
            "maxSeqLen": max_seq_len, "epochs": epochs, "stepSize": 0.05,
            "seed": 1}}],
    }


@pytest.fixture(scope="module")
def trained():
    """One trained sessionrec engine shared by the module (training is
    the expensive part; every test below only reads the model)."""
    from predictionio_tpu.storage.registry import (
        SourceConfig,
        Storage,
        StorageConfig,
    )

    src = SourceConfig(name="SESSREC_TEST", type="memory")
    storage = Storage(StorageConfig(metadata=src, modeldata=src,
                                    eventdata=src))
    Storage.reset(storage)
    try:
        ingest_views(storage)
        variant = EngineVariant.from_dict(variant_dict())
        engine = get_engine(variant.engine_factory)
        ep = extract_engine_params(engine, variant)
        ctx = WorkflowContext(storage=storage, seed=1)
        instance = CoreWorkflow.run_train(engine, ep, variant, ctx)
        assert instance.status == "COMPLETED"
        blob = storage.model_data_models().get(instance.id).models
        models = engine.deserialize_models(blob, instance.id, ep)
        yield engine, ep, models
    finally:
        storage.close()
        Storage.reset(None)


def _scores(result):
    return [(s["item"], s["score"]) for s in result["itemScores"]]


class TestSeqTierHelpers:
    def test_ladder_is_powers_of_two_covering_max(self):
        assert seq_tier_ladder(32) == (8, 16, 32)
        assert seq_tier_ladder(20) == (8, 16, 32)
        assert seq_tier_ladder(8) == (8,)
        assert seq_tier_ladder(2) == (8,)

    def test_env_override_sorted_deduped_covering(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVING_SEQ_TIERS", "32, 8,8")
        assert seq_tiers_from_env(32) == (8, 32)
        # a ladder that undercuts the window length grows a top tier
        monkeypatch.setenv("PIO_SERVING_SEQ_TIERS", "8")
        assert seq_tiers_from_env(32) == (8, 32)
        monkeypatch.setenv("PIO_SERVING_SEQ_TIERS", "garbage")
        assert seq_tiers_from_env(32) == seq_tier_ladder(32)

    def test_pad_to_seq_tier(self):
        assert pad_to_seq_tier(3, (8, 16)) == 8
        assert pad_to_seq_tier(9, (8, 16)) == 16
        assert pad_to_seq_tier(40, (8, 16)) == 16  # callers truncate

    def test_batch_tier_is_power_of_two(self):
        assert [_pad_batch_tier(n) for n in (1, 2, 3, 5, 8)] == \
            [1, 2, 4, 8, 8]


class TestServeTiers:
    def test_env_ladder_clamped_to_positional_table(self, trained,
                                                    monkeypatch):
        _, ep, models = trained
        model = models[0]
        monkeypatch.setenv("PIO_SERVING_SEQ_TIERS", "4,16,64")
        # 64 exceeds the trained positional table (16 rows): dropped
        assert _serve_tiers(model) == (4, 16)
        monkeypatch.setenv("PIO_SERVING_SEQ_TIERS", "64")
        # nothing servable survives the clamp → default ladder fallback
        assert _serve_tiers(model) == seq_tier_ladder(model.max_seq_len)


class TestTrainAndServe:
    def test_trained_model_serves_next_items(self, trained):
        engine, ep, models = trained
        result = engine.predict(ep, models, {"user": "u0", "num": 3})
        scores = result["itemScores"]
        assert scores
        window = set(models[0].user_windows["u0"])
        assert all(s["item"] not in window for s in scores)
        vals = [s["score"] for s in scores]
        assert vals == sorted(vals, reverse=True)

    def test_explicit_items_query_matches_served_window(self, trained):
        engine, ep, models = trained
        window = list(models[0].user_windows["u2"])
        by_user = engine.predict(ep, models, {"user": "u2", "num": 4})
        by_items = engine.predict(ep, models,
                                  {"items": window, "num": 4})
        assert _scores(by_user) == _scores(by_items)

    def test_unknown_user_and_empty_history_answer_empty(self, trained):
        engine, ep, models = trained
        assert engine.predict(ep, models,
                              {"user": "nobody", "num": 3}) == \
            {"itemScores": []}
        assert engine.predict(ep, models, {"items": [], "num": 3}) == \
            {"itemScores": []}


class TestTierParity:
    """The docs/serving.md promise: a history's scores do not depend on
    the sequence tier it pads to (bitwise), nor, to the last bits, on
    the batch it rides in."""

    def _histories(self, model):
        items = [f"i{k}" for k in range(8)]
        # lengths chosen to land on BOTH default tiers (8 and 16)
        return [items[:2], items[:5], items + items[:3]]

    def test_batched_vs_single_agree_at_every_tier(self, trained):
        """Across batch shapes the scores agree to a few ulp, not to the
        bit: XLA's CPU backend picks a dot kernel by the operands'
        shapes (a vector product, its own small-matrix loop or Eigen)
        and their sums differ in the last bit. Bit equality holds where
        the batch shape is the same: the next test."""
        engine, ep, models = trained
        model = models[0]
        queries = [{"items": h, "num": 4} for h in self._histories(model)]
        tiers = {pad_to_seq_tier(len(h), _serve_tiers(model))
                 for h in self._histories(model)}
        assert len(tiers) > 1, "histories must span several tiers"
        singles = [engine.predict(ep, models, q) for q in queries]
        batched = engine.predict_batch(ep, models, queries)
        for s, b in zip(singles, batched):
            assert [i for i, _ in _scores(s)] == [i for i, _ in _scores(b)]
            np.testing.assert_array_almost_equal_nulp(
                np.float32([v for _, v in _scores(s)]),
                np.float32([v for _, v in _scores(b)]), nulp=16)

    def test_same_history_scores_bitwise_on_a_different_ladder(
            self, trained, monkeypatch):
        # the tier a history pads to is a serving knob, not part of the
        # answer: re-rung the ladder so the SAME 2-item history pads to
        # 16 instead of 8 — scores must not move by a single bit
        engine, ep, models = trained
        q = {"items": ["i1", "i4"], "num": 5}
        default = engine.predict(ep, models, q)
        monkeypatch.setenv("PIO_SERVING_SEQ_TIERS", "16")
        rerung = engine.predict(ep, models, q)
        assert _scores(default) == _scores(rerung)

    def test_repeat_traffic_adds_zero_compiles(self, trained):
        from predictionio_tpu.utils.profiling import JIT_COMPILES

        engine, ep, models = trained
        queries = [{"items": h, "num": 3}
                   for h in self._histories(models[0])]
        engine.predict_batch(ep, models, queries)  # warm every tier
        for q in queries:
            engine.predict(ep, models, q)
        child = JIT_COMPILES.labels(fn="sessionrec.score")
        warmed = child.value
        for _ in range(3):  # steady state: same shapes, no compiles
            engine.predict_batch(ep, models, queries)
            for q in queries:
                engine.predict(ep, models, q)
        assert child.value == warmed


class TestEvaluation:
    def test_read_eval_leaves_last_item_out(self, memory_storage):
        ingest_views(memory_storage)
        ds = DataSource(DataSourceParams(appName="SessApp", evalK=2))
        ctx = WorkflowContext(storage=memory_storage, seed=1)
        full = ds.read_training(ctx).sequences
        folds = ds.read_eval(ctx)
        assert len(folds) == 2
        held_total = 0
        for td, qa in folds:
            assert qa
            held_total += len(qa)
            for q, actual in qa:
                prefix, (target,) = q["items"], actual["items"]
                u = next(u for u, s in full.items()
                         if s[:-1] == prefix and s[-1] == target)
                # the held-out user's training sequence dropped its last
                assert td.sequences[u] == prefix
        eligible = sum(1 for s in full.values() if len(s) >= 2)
        assert held_total == eligible  # every 2+ user held out once

    def test_sanity_check_requires_a_transition(self):
        with pytest.raises(ValueError):
            TrainingData(sequences={"u": ["i1"]}).sanity_check()
        TrainingData(sequences={"u": ["i1", "i2"]}).sanity_check()

    def test_canonical_rule_is_shared_with_training(self, memory_storage):
        # the DataSource's sequences ARE recent_window over the event
        # fold — the same rule the online SessionFold applies
        ingest_views(memory_storage, n_users=1, n_items=4, per_user=6)
        ds = DataSource(DataSourceParams(appName="SessApp"))
        seqs = ds.read_training(
            WorkflowContext(storage=memory_storage, seed=1)).sequences
        # user 0 views i0,i1,i2,i3,i0,i1 → keep-last: i2,i3,i0,i1
        assert seqs["u0"] == ["i2", "i3", "i0", "i1"]
