"""SessionRecModel: the session-based next-item model's served state.

This module is numpy-only on purpose: the online plane imports it to
type-dispatch fold handles (`online/session.py`) and must stay loadable
in processes that never touch jax. The attention forward pass lives with
the DASE components in `templates/sessionrec/engine.py`; everything here
is id bookkeeping plus the ONE rule both the training path and the
online fold must share — what "a user's recent-item window" means.

The canonical window rule (`recent_window`): keep-last dedup per item
(an item's position is its LATEST event), order by (event time, item
id), keep the most recent `max_len` items. The (time, item) sort key —
not raw event order — is what makes the window a pure function of the
keep-last history the online plane already caches, so replaying a
tailed batch after a crash rebuilds a bit-identical window
(at-least-once delivery is free, same as ALS fold-in idempotence).

The per-user `session_vecs` entry is the user's pooled session
embedding — mean of the window's item-embedding rows — recomputed by
every fold that touches the user. Serving's attention scorer derives
everything from the window itself; the pooled vector exists so drills
and parity checks can compare session state bitwise without running the
attention stack, and so a degraded/debug path has a cheap per-user
representation.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from predictionio_tpu.data.bimap import BiMap


def recent_window(pairs: Iterable[Tuple[str, object]],
                  max_len: int) -> List[str]:
    """Canonical session window over `(item_id, event_time)` pairs.

    Keep-last per item, sorted by (last event time, item id), most
    recent `max_len` items, oldest → newest. Pure and deterministic:
    re-applying the same events (tailer replay) or receiving them in a
    different arrival order produces the same window, because only the
    latest time per item survives and the sort key breaks time ties by
    item id rather than arrival order.
    """
    last: Dict[str, object] = {}
    for item, t in pairs:
        prev = last.get(item)
        if prev is None or not (t < prev):  # keep-last; ties keep newest
            last[item] = t
    ordered = sorted(last.items(), key=lambda kv: (kv[1], kv[0]))
    if max_len > 0:
        ordered = ordered[-max_len:]
    return [item for item, _ in ordered]


@dataclasses.dataclass
class SessionRecModel:
    """Immutable served state for the sessionrec template.

    `params` is a plain dict pytree of numpy arrays (pickles with the
    model store, device_puts cleanly at dispatch): the parameter tree of
    `models/encoder.py` (`param_shapes`) with the routers' bias buffers
    beside it. Here only `emb` [V, D], the item embeddings, is read.
    `encoder` is the encoder's configuration as a plain dict
    (`dataclasses.asdict(EncoderConfig)`: this module stays free of jax);
    `train_report` is what the first step of the train reported for the
    configuration's report blocks, or None.

    `user_windows[user]` is the user's canonical recent-item window as
    item-id strings (oldest → newest, ≤ max_seq_len); `session_vecs` is
    the matching pooled embedding per user (see module docstring). Both
    are what the online fold swaps — the learned `params` only change on
    retrain.
    """

    params: dict
    item_ids: BiMap
    user_windows: Dict[str, Tuple[str, ...]]
    session_vecs: Dict[str, np.ndarray]
    max_seq_len: int
    n_heads: int
    encoder: Optional[dict] = None
    train_report: Optional[dict] = None

    @property
    def n_items(self) -> int:
        return len(self.item_ids)

    def window_rows(self, items: Iterable[str]) -> List[int]:
        """Embedding rows for the known items of a window, order kept.
        Items trained into the embedding table only — cold items (ids
        the last retrain never saw) are ignored, matching how ALS
        fold-in treats cold opposing rows."""
        out = []
        for i in items:
            row = self.item_ids.get(str(i))
            if row is not None:
                out.append(int(row))
        return out

    def session_vec_of(self, items: Iterable[str]) -> np.ndarray:
        """Pooled session embedding for an item window: mean of the
        known items' embedding rows (zeros when none are known). This is
        the exact recompute rule the online fold applies per touched
        user, so a drill can assert fold output bitwise."""
        rows = self.window_rows(items)
        emb = np.asarray(self.params["emb"])
        if not rows:
            return np.zeros(emb.shape[1], dtype=emb.dtype)
        return emb[np.asarray(rows, np.int32)].mean(axis=0)
