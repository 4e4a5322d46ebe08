"""Session-based next-item engine template (DASE components).

The scenario-diversity frontier (ROADMAP item 4): every other served
template is factor- or frequency-based; this one is a causal
self-attention next-item model — the config-driven encoder of
`models/encoder.py` (a mixer a layer as its configuration says, dense
or sparse-expert feed-forward, an optional multi-token-prediction
module), by default
one 16-wide dense block — trained through the normal DataSource →
Preparator → Algorithm path over per-user event sequences from
`data/view.py`'s ordered aggregation, packed into fixed sequences with
attention held inside each history, and served through the existing
MicroBatcher. The algorithm's `encoderConfig` names the encoder's
configuration file; `engine.json` names `encoder-16.json`.

Serving pads over TWO ragged axes on fixed ladders: the batcher's
power-of-two bucket ladder bounds the batch dimension, and the
sequence-tier ladder (`serving.batcher.seq_tiers_from_env`, knob
PIO_SERVING_SEQ_TIERS) bounds the history-length dimension — so the
jitted scorer's executable space is (batch tiers × sequence tiers),
each compiled once, instead of one compile per ragged length.

Pad positions are exact no-ops: histories right-pad, pads are a segment
of their own and the causal mask keeps every real position from
attending past itself (a masked score's softmax term underflows to
exactly 0.0 in f32), the readout gathers the LAST REAL position's
state, and all other ops are per-position or per-row. A history
therefore scores identically at any sequence tier that fits it. Across
BATCH tiers the scores agree to the last bits and not always bitwise:
XLA's CPU backend picks a dot kernel by the operands' shapes (a vector
product, its own small-matrix loop, Eigen) and their sums differ in the
last bit (`TestTierParity::test_batched_vs_single_bitwise_at_every_tier`
shows it on the CPU).

Wire shapes:
    query:  {"user": "u1", "num": 4}            — served session window
            {"items": ["i1", "i2"], "num": 4}   — explicit session
    result: {"itemScores": [{"item": "i5", "score": 0.93}, ...]}
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
from datetime import timezone
from typing import Dict, List, Optional

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource as BaseDataSource,
    Engine,
    EngineFactory,
    FirstServing,
    Params,
    Preparator as BasePreparator,
    SanityCheck,
    WorkflowContext,
)
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.store import EventStore
from predictionio_tpu.data.view import LBatchView
from predictionio_tpu.models.session_model import (
    SessionRecModel,
    recent_window,
)
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.telemetry.spans import span
from predictionio_tpu.serving.batcher import (
    pad_to_seq_tier,
    seq_tier_ladder,
    seq_tiers_from_env,
)

log = logging.getLogger(__name__)

Query = dict
PredictedResult = dict


@dataclasses.dataclass
class DataSourceParams(Params):
    appName: str = ""
    eventNames: list = dataclasses.field(
        default_factory=lambda: ["view", "buy"])
    evalK: int = 0  # >0 enables read_eval with k leave-last-item folds


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Per-user canonical item sequences (the `recent_window` rule over
    the ordered event fold — keep-last dedup, (time, item) order)."""

    sequences: Dict[str, List[str]]  # user id → ordered item ids

    def sanity_check(self):
        if not any(len(s) >= 2 for s in self.sequences.values()):
            raise ValueError(
                "TrainingData has no user with a 2+ item sequence; ingest "
                "view/buy events first (next-item training needs at least "
                "one transition).")


class DataSource(BaseDataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        """Per-user ordered sequences via `LBatchView.
        aggregate_by_entity_ordered` — the time-ordered per-entity fold
        (events sorted by event_time, creation_time) the reference's
        `aggregateByEntityOrdered` provided. The fold accumulates
        (item, event_time) pairs; `recent_window` then applies the one
        canonical window rule the online fold shares."""
        view = LBatchView(self.params.appName,
                          store=EventStore(ctx.storage))
        names = set(self.params.eventNames)

        def pred(e) -> bool:
            return (e.event in names
                    and e.entity_type == "user"
                    and (e.target_entity_type or "item") == "item"
                    and bool(e.target_entity_id))

        def op(acc, e):
            t = e.event_time
            if t is not None and t.tzinfo is None:
                t = t.replace(tzinfo=timezone.utc)
            return acc + ((str(e.target_entity_id), t),)

        folded = view.aggregate_by_entity_ordered(pred, (), op)
        sequences = {str(u): recent_window(pairs, 0)  # 0 = uncapped here;
                     for u, pairs in folded.items() if pairs}
        # the Algorithm caps to maxSeqLen so window length stays an
        # algorithm knob, not a data-shape property
        log.info("DataSource: %d users with sequences, app %r",
                 len(sequences), self.params.appName)
        return TrainingData(sequences=sequences)

    def read_eval(self, ctx: WorkflowContext):
        """k-fold leave-last-item-out: each fold holds out 1/k of the
        2+-item users; their training sequence drops its last item and
        the query replays the prefix asking the model to rank the
        held-out next item."""
        k = self.params.evalK
        if k <= 1:
            raise ValueError("DataSourceParams.evalK must be >= 2 for "
                             "evaluation")
        td = self.read_training(ctx)
        users = sorted(u for u, s in td.sequences.items() if len(s) >= 2)
        folds = []
        for fold in range(k):
            held = set(users[fold::k])
            seqs = {u: (list(s[:-1]) if u in held else list(s))
                    for u, s in td.sequences.items()}
            seqs = {u: s for u, s in seqs.items() if s}
            qa = [({"items": list(seqs[u]), "num": 10},
                   {"items": [td.sequences[u][-1]]})
                  for u in sorted(held) if seqs.get(u)]
            folds.append((TrainingData(sequences=seqs), qa))
        return folds


@dataclasses.dataclass
class PreparedData:
    item_ids: BiMap
    user_seqs: Dict[str, np.ndarray]  # user id → int32 embedding rows


class Preparator(BasePreparator):
    """Code items densely (sorted ids → deterministic rows) and encode
    each user's canonical sequence."""

    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> PreparedData:
        items = sorted({i for s in td.sequences.values() for i in s})
        item_ids = BiMap.string_int(items)
        user_seqs = {
            u: item_ids.to_index(s).astype(np.int32)
            for u, s in sorted(td.sequences.items())
        }
        return PreparedData(item_ids=item_ids, user_seqs=user_seqs)


# -- packing ----------------------------------------------------------------

def pack_histories(seqs, pack_len: int):
    """Pack histories into sequences of `pack_len` tokens, first-fit
    decreasing: the longest first, each into the first sequence that
    still has room. A history longer than `pack_len` keeps its newest
    `pack_len` items. Returns (tokens, segment ids, positions), each
    int32 [n, pack_len]: a history's tokens share a segment id (1, 2, ...
    along the sequence; 0 marks padding) and a position counts from the
    history's start (padding counts from where it starts)."""
    seqs = [np.asarray(s[-pack_len:], np.int32) for s in seqs]
    order = sorted(range(len(seqs)), key=lambda n: -len(seqs[n]))
    room: List[int] = []
    placed: List[List[int]] = []
    for n in order:
        need = len(seqs[n])
        for b, free in enumerate(room):
            if need <= free:
                break
        else:
            b = len(room)
            room.append(pack_len)
            placed.append([])
        room[b] -= need
        placed[b].append(n)
    tokens = np.zeros((len(room), pack_len), np.int32)
    seg = np.zeros_like(tokens)
    pos = np.zeros_like(tokens)
    for b, members in enumerate(placed):
        at = 0
        for k, n in enumerate(members):
            end = at + len(seqs[n])
            tokens[b, at:end] = seqs[n]
            seg[b, at:end] = k + 1
            pos[b, at:end] = np.arange(end - at)
            at = end
        pos[b, at:] = np.arange(pack_len - at)
    return tokens, seg, pos


# -- the encoder's programs ----------------------------------------------------

def _resolve_config_path(path: str) -> str:
    """A configuration file as named: absolute, from the working
    directory, or beside this template's engine.json."""
    if os.path.isabs(path) or os.path.exists(path):
        return path
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), path)


DEFAULT_ENCODER = "encoder-16.json"  # beside this template's engine.json


@functools.lru_cache(maxsize=8)
def _encoder_config(path: str, embed_dim: Optional[int] = None,
                    num_blocks: Optional[int] = None,
                    num_heads: Optional[int] = None):
    """The configuration the file `path` gives. With no file, the
    template's default block (`encoder-16.json`: dense blocks, float32,
    every sequence in one step) at the sizes given: a block's widths
    follow from `embed_dim` and `num_heads` in the default's own
    proportions."""
    from predictionio_tpu.models.encoder import EncoderConfig

    sizes = (embed_dim, num_blocks, num_heads)
    cfg = EncoderConfig.from_json(_resolve_config_path(path
                                                       or DEFAULT_ENCODER))
    if path:
        if any(s is not None for s in sizes):
            raise ValueError(
                "encoderConfig gives the encoder's sizes: embedDim, "
                "numBlocks and numHeads size the default block only")
        return cfg
    d = int(embed_dim or cfg.hidden_size)
    h = int(num_heads or cfg.num_attention_heads)
    n = int(num_blocks or cfg.num_hidden_layers)
    return dataclasses.replace(
        cfg, hidden_size=d, intermediate_size=2 * d, num_hidden_layers=n,
        first_k_dense_replace=n, num_attention_heads=h, q_lora_rank=d,
        kv_lora_rank=max(d // 2, 4), qk_nope_head_dim=max(d // h, 2),
        qk_rope_head_dim=max(d // (2 * h) * 2, 2), v_head_dim=max(d // h, 2))


def _config_of(model: SessionRecModel):
    """The encoder's configuration back from the model's plain dict."""
    from predictionio_tpu.models.encoder import EncoderConfig

    return EncoderConfig(**model.encoder)


@functools.lru_cache(maxsize=8)
def _scorer(cfg):
    """The served next-item scorer, metered so every dispatch lands in
    the jit-cache inventory / device attribution and a ladder miss
    names its changed dimension in /debug/jit.json. Executable space:
    one compile per (batch tier, sequence tier) after warmup — args are
    (params pytree, seq [B, L], lengths [B]), so a sequence-ladder miss
    blames "arg1 dim1: <old>→<new>"."""
    from predictionio_tpu.models import encoder
    from predictionio_tpu.utils import profiling

    def score(params, seq, lengths):
        return encoder.score(params, cfg, seq, lengths)

    return profiling.metered_jit(score, label="sessionrec.score")


@functools.lru_cache(maxsize=8)
def _train_programs(cfg, vocab: int, lr: float):
    """(init, step, report) for a configuration: the state made on the
    device from a key; one Adam step on a batch of packed sequences (the
    state donated); the configuration's report blocks out of the state
    the first step left."""
    from predictionio_tpu.models import encoder
    from predictionio_tpu.utils import profiling

    def init(key):
        return encoder.init_state(cfg, vocab, key)

    return (profiling.metered_jit(init, label="sessionrec.init"),
            profiling.metered_jit(encoder.train_step(cfg, lr),
                                  label="sessionrec.train_step",
                                  donate_argnums=(0,)),
            profiling.metered_jit(encoder.first_step_report(cfg),
                                  label="sessionrec.first_step_report"))


def _run_steps(programs, state, batches, epochs: int, report: bool):
    """Every optimizer step of a train, dispatched back to back and
    waited for once: the device goes from one step's program to the
    next without the host in between. Returns (state, the first step's
    metrics, the last step's, the first step's report or None)."""
    import jax

    _, step, first_report = programs
    first = last = reported = None
    with span("sessionrec.loop.dispatch"):
        for _ in range(epochs):
            for batch in batches:
                state, last = step(state, *batch)
                if first is None:
                    first = last
                    if report:
                        reported = first_report(state)
    with span("sessionrec.loop.wait"):
        jax.block_until_ready((state, last, reported))
    return state, first, last, reported


def _pad_batch_tier(n: int) -> int:
    """Power-of-two batch tier ≥ n (the scorer-side half of the bucket
    ladder: batch groups re-fragment after sequence-tier grouping, so
    the batch dim re-pads onto its own fixed ladder)."""
    t = 1
    while t < n:
        t <<= 1
    return t


def _serve_tiers(model: SessionRecModel) -> tuple:
    """Sequence tiers this model can serve: the env ladder clamped to
    the top tier of the model's own window length (positions are
    rotary, so the clamp bounds the executable space, not a table)."""
    top = seq_tier_ladder(model.max_seq_len)[-1]
    tiers = tuple(t for t in seq_tiers_from_env(model.max_seq_len)
                  if t <= top)
    return tiers or seq_tier_ladder(model.max_seq_len)


# Set by every SessionRecAlgorithm.train. tokens / cells is the share of
# a step's positions that hold an event and not padding.
PACK_TOKENS = REGISTRY.gauge(
    "encoder_pack_tokens", "Events placed into the packed sequences of "
    "the last sessionrec train")
PACK_CELLS = REGISTRY.gauge(
    "encoder_pack_cells", "Positions (sequences x length) of the packed "
    "sequences of the last sessionrec train")
PACK_FILL = REGISTRY.gauge(
    "encoder_pack_fill", "encoder_pack_tokens / encoder_pack_cells of "
    "the last sessionrec train")
EXPERT_TOKENS = REGISTRY.gauge(
    "encoder_expert_tokens", "Tokens the last train step sent to each "
    "held expert, by expert layer (its place among the expert layers; among "
    "the held layers where a layer is one sublayer; mtp = the MTP module's) "
    "and expert id",
    labelnames=("layer", "expert"))
EXPERT_BLOCK_ROWS = REGISTRY.gauge(
    "encoder_expert_block_rows", "Rows the grouped products of the last "
    "train step walked for each held expert: its tokens rounded up to whole "
    "row blocks (ops/moe.py); encoder_expert_tokens over it is the blocks' "
    "fill", labelnames=("layer", "expert"))
KDA_CHUNKS = REGISTRY.gauge(
    "encoder_kda_chunks", "Chunks of the KDA scan (ops/kda.py) in the "
    "sequences of each step of the last train's epoch, a KDA layer",
    labelnames=("step",))
KDA_BOUNDARY_CHUNKS = REGISTRY.gauge(
    "encoder_kda_boundary_chunks", "Of encoder_kda_chunks, those that "
    "hold a history's first token (the scan's reset masks do work there)",
    labelnames=("step",))
KDA_RESETS_TOTAL = REGISTRY.counter(
    "encoder_kda_resets_total", "Histories the train steps of an encoder "
    "with KDA layers held: the times a layer's state started from zero")
SSM_CHUNKS = REGISTRY.gauge(
    "encoder_ssm_chunks", "Chunks of Mamba's selective scan (ops/ssm.py) "
    "in the sequences of each step of the last train's epoch, a Mamba layer",
    labelnames=("step",))
SSM_BOUNDARY_CHUNKS = REGISTRY.gauge(
    "encoder_ssm_boundary_chunks", "Of encoder_ssm_chunks, those that "
    "hold a history's first token (the scan's reset masks do work there)",
    labelnames=("step",))
SSM_RESETS_TOTAL = REGISTRY.counter(
    "encoder_ssm_resets_total", "Histories the train steps of an encoder "
    "with Mamba layers held: the times a layer's state started from zero")
SSD_CHUNKS = REGISTRY.gauge(
    "encoder_ssd_chunks", "Chunks of Mamba-2's scan (ops/ssd.py) in the "
    "sequences of each step of the last train's epoch, a Mamba-2 layer",
    labelnames=("step",))
SSD_BOUNDARY_CHUNKS = REGISTRY.gauge(
    "encoder_ssd_boundary_chunks", "Of encoder_ssd_chunks, those that "
    "hold a history's first token (the scan's reset masks do work there)",
    labelnames=("step",))
SSD_RESETS_TOTAL = REGISTRY.counter(
    "encoder_ssd_resets_total", "Histories the train steps of an encoder "
    "with Mamba-2 layers held: the times a layer's state started from zero")
ATTN_KEY_BLOCKS = REGISTRY.gauge(
    "encoder_attn_key_blocks", "Key blocks the query blocks of the last "
    "train's epoch visit in one blockwise differential-attention layer "
    "(kind: window | full), summed over its steps: of=visited, from a "
    "history's first block or the window's; of=causal, what plain causal "
    "attention would visit", labelnames=("kind", "of"))
TOKENS_TOTAL = REGISTRY.counter(
    "encoder_tokens_total", "Events the sessionrec train steps have "
    "trained on (padding not counted)")


def _count_key_blocks(cfg, batches) -> None:
    """`encoder_attn_key_blocks` for the blockwise differential
    attention of the configuration's windowed and full layers, from the
    positions of an epoch's batches."""
    from predictionio_tpu.ops.attention import first_key_blocks

    for kind, held, window in (
            ("window", "swa" in cfg.kinds, cfg.sliding_window),
            ("full", bool({"full", "cross"} & set(cfg.kinds)), None)):
        if not held:
            continue
        visited = causal = 0
        for _, _, pos in batches:
            lo = first_key_blocks(pos, cfg.attention_block, window, np)
            upto = np.arange(1, lo.shape[1] + 1)
            visited += int((upto[None, :] - lo).sum())
            causal += int(lo.shape[0] * upto.sum())
        ATTN_KEY_BLOCKS.labels(kind=kind, of="visited").set(visited)
        ATTN_KEY_BLOCKS.labels(kind=kind, of="causal").set(causal)


@dataclasses.dataclass
class SessionRecParams(Params):
    # sizes of the default block; not to be given beside encoderConfig
    embedDim: Optional[int] = None
    numBlocks: Optional[int] = None
    numHeads: Optional[int] = None
    maxSeqLen: int = 32
    epochs: int = 30
    stepSize: float = 0.05
    seed: Optional[int] = None
    # a configuration file of models/encoder.py (see EncoderConfig);
    # empty: the default block (encoder-16.json) at the sizes above
    encoderConfig: str = ""


class SessionRecAlgorithm(Algorithm):
    """Causal latent-attention next-item model over session windows."""

    params_class = SessionRecParams
    checkpoint_tags = ("sessionrec",)

    def __init__(self, params: SessionRecParams):
        self.params = params

    def train(self, ctx: WorkflowContext,
              pd: PreparedData) -> SessionRecModel:
        import jax

        p = self.params
        cfg = _encoder_config(p.encoderConfig, p.embedDim, p.numBlocks,
                              p.numHeads)
        seed = ctx.seed if p.seed is None else p.seed
        n_items = len(pd.item_ids)
        vocab = int(cfg.vocab_size) or n_items
        if n_items > vocab:
            raise ValueError(f"{n_items} items do not fit the encoder's "
                             f"vocabulary of {vocab}")
        cap = int(p.maxSeqLen)
        pack_len = int(cfg.pack_len) or seq_tier_ladder(cap)[-1]

        with span("sessionrec.pack"):
            seqs = [s[-cap:] for _, s in sorted(pd.user_seqs.items())
                    if len(s) >= 2]
            tokens, seg, pos = pack_histories(seqs, pack_len)
            # whole steps: sequences of padding alone fill the last one
            per_step = int(cfg.seqs_per_step) or _pad_batch_tier(len(tokens))
            short = -len(tokens) % per_step
            if short:
                tokens, seg = (np.pad(a, ((0, short), (0, 0)))
                               for a in (tokens, seg))
                pos = np.concatenate(
                    [pos, np.tile(np.arange(pack_len, dtype=np.int32),
                                  (short, 1))])
            real = int((seg != 0).sum())
            PACK_TOKENS.set(real)
            PACK_CELLS.set(seg.size)
            PACK_FILL.set(real / seg.size if seg.size else 0.0)

        programs = _train_programs(cfg, vocab, float(p.stepSize))
        with span("sessionrec.init"):
            state = programs[0](jax.random.key(
                int(seed) % (2 ** 31 - 1) if seed is not None else 0))
            # a step's batch: [sequences a step, length] of each array
            n_steps = len(tokens) // per_step if seqs else 0
            batches = [tuple(a[n * per_step:(n + 1) * per_step]
                             for a in (tokens, seg, pos))
                       for n in range(n_steps)]
            on_device = jax.device_put(batches)
        state, first, metrics, reported = _run_steps(
            programs, state, on_device, int(p.epochs),
            bool(cfg.report_blocks))
        TOKENS_TOTAL.inc(real * int(p.epochs))
        for kind, chunk, chunks_of, boundary_of, resets_total in (
                ("kda", cfg.kda_chunk, KDA_CHUNKS, KDA_BOUNDARY_CHUNKS,
                 KDA_RESETS_TOTAL),
                ("mamba", cfg.ssm_chunk, SSM_CHUNKS, SSM_BOUNDARY_CHUNKS,
                 SSM_RESETS_TOTAL),
                ("ssd", cfg.mamba_chunk_size, SSD_CHUNKS,
                 SSD_BOUNDARY_CHUNKS, SSD_RESETS_TOTAL)):
            if kind not in cfg.kinds:
                continue
            from predictionio_tpu.ops.kda import chunk_stats

            resets = 0
            for n, (_, seg_n, _) in enumerate(batches):
                chunks, boundary, held = chunk_stats(seg_n, chunk)
                chunks_of.labels(step=str(n)).set(chunks)
                boundary_of.labels(step=str(n)).set(boundary)
                resets += held
            resets_total.inc(resets * int(p.epochs))
        if pack_len > cfg.attention_block:
            _count_key_blocks(cfg, batches)
        with span("sessionrec.readback"):
            params = jax.device_get({**state["params"], **state["buffers"]})
            first, metrics, reported = jax.device_get(
                (first, metrics, reported))
        train_report = (None if reported is None else
                        {"batch": batches[0], "metrics": first, **reported})
        del state
        if metrics is not None:
            for name in ("counts", "mtp_counts"):
                rows = np.atleast_2d(metrics.get(name, np.zeros((0, 0))))
                # a model whose layers are one sublayer each names an
                # expert layer by its place among the held layers
                held_at = (cfg.expert_layers if cfg.single_sublayer
                           else range(len(rows)))
                for layer, row in zip(held_at, rows):
                    for e, count in enumerate(row):
                        labels = {"layer": "mtp" if name == "mtp_counts"
                                  else str(layer),
                                  "expert": str(cfg.expert_first + e)}
                        EXPERT_TOKENS.labels(**labels).set(int(count))
                        EXPERT_BLOCK_ROWS.labels(**labels).set(
                            -(-int(count) // cfg.moe_block_rows)
                            * cfg.moe_block_rows)
            log.info("SessionRec: trained %d sequences, %d items, final "
                     "loss %.4f", len(seqs), n_items, float(metrics["loss"]))

        windows = {
            u: tuple(pd.item_ids.from_index(s[-cap:]))
            for u, s in sorted(pd.user_seqs.items())
        }
        model = SessionRecModel(
            params=params, item_ids=pd.item_ids, user_windows=windows,
            session_vecs={}, max_seq_len=cap,
            n_heads=int(cfg.num_attention_heads),
            encoder=dataclasses.asdict(cfg), train_report=train_report)
        with span("model.session_vecs"):
            # a window at a time: one gather of every window's rows (a
            # gigabyte at 131072 events x 2048) was 15x slower on the host
            model.session_vecs.update(
                {u: model.session_vec_of(w) for u, w in windows.items()})
        return model

    def predict(self, model: SessionRecModel,
                query: Query) -> PredictedResult:
        # the single path IS the batched path at batch 1: parity between
        # them is a code identity plus the tier-invariance the jitted
        # forward guarantees (asserted in tests/test_sessionrec_template)
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: SessionRecModel,
                      queries) -> list:
        out: list = [None] * len(queries)
        tiers = _serve_tiers(model)
        cap = min(model.max_seq_len, int(tiers[-1]))
        groups: Dict[int, list] = {}
        for pos, q in enumerate(queries):
            hist = q.get("items")
            if hist is None:
                u = q.get("user")
                hist = (model.user_windows.get(str(u), ())
                        if u is not None else ())
            rows = model.window_rows(hist)[-cap:]
            num = int(q.get("num", 10))
            if not rows or num <= 0:
                out[pos] = {"itemScores": []}
                continue
            tier = pad_to_seq_tier(len(rows), tiers)
            groups.setdefault(tier, []).append((pos, rows, num))
        if not groups:
            return out
        score = _scorer(_config_of(model))
        for tier, entries in groups.items():
            b = len(entries)
            bt = _pad_batch_tier(b)
            seq = np.zeros((bt, tier), np.int32)
            lengths = np.zeros(bt, np.int32)
            for r, (_, rows, _) in enumerate(entries):
                seq[r, :len(rows)] = rows
                lengths[r] = len(rows)
            if bt > b:
                # batch padding duplicates the last real row; its
                # results are never read (the batcher's _pad idiom)
                seq[b:] = seq[b - 1]
                lengths[b:] = lengths[b - 1]
            logits = np.asarray(score(model.params, seq, lengths))
            for r, (pos, rows, num) in enumerate(entries):
                s = logits[r][:model.n_items].copy()
                seen = np.unique(np.asarray(rows, np.int32))
                s[seen] = -np.inf  # never re-recommend the window
                k = min(num, s.shape[0] - len(seen))
                if k <= 0:
                    out[pos] = {"itemScores": []}
                    continue
                top = np.argpartition(-s, k - 1)[:k]
                top = top[np.argsort(-s[top])]
                items = model.item_ids.from_index(top)
                out[pos] = {"itemScores": [
                    {"item": i, "score": float(s[j])}
                    for i, j in zip(items, top)]}
        return out


class SessionRecEngine(EngineFactory):
    def apply(self) -> Engine:
        return Engine(
            data_source_class_map=DataSource,
            preparator_class_map=Preparator,
            algorithm_class_map={"attention": SessionRecAlgorithm},
            serving_class_map=FirstServing,
        )
