"""Fault-injection points (SURVEY.md §5 'Failure detection / recovery /
fault injection').

Crash-consistency claims (atomic checkpoints, all-or-nothing batch
ingest) are only evidence when a process actually dies at the worst
moment — and runtime-resilience claims (the supervisor's hang/error
detection, the sqlite locked-retry) are only evidence when a live
process misbehaves without dying. Production code marks those moments
with `faults.inject("site")`; a test arms a site via the `PIO_FAULTS`
env var:

    PIO_FAULTS=checkpoint.pre_replace        # hard-die at first hit
    PIO_FAULTS=events.batch.pre_commit:3     # hard-die at the 3rd hit
    PIO_FAULTS=a.site,b.site:2               # multiple sites
    PIO_FAULTS=serving.pre_dispatch=delay:500    # sleep 500ms per hit
    PIO_FAULTS=serving.pre_dispatch=error        # raise FaultInjected
    PIO_FAULTS=sqlite.pre_commit:2=delay:300     # delay from 2nd hit on

Modes:
- (default) `die` — `os._exit(137)`: no atexit handlers, no flushing,
  like SIGKILL. Fires once the hit count is reached (and then the
  process is gone).
- `delay:<ms>` — sleep that many milliseconds at the site, every hit
  from the armed count onward. Simulates a slow/hung dependency while
  the process stays alive.
- `error` — raise `FaultInjected` at the site, every hit from the armed
  count onward. Simulates a persistent runtime failure (serving surfaces
  map it to HTTP 500).

Unarmed sites cost one dict lookup on a module-level map that is empty in
production (PIO_FAULTS unset ⇒ `inject` returns immediately).

Sites in the tree:
- `checkpoint.pre_replace` — after a checkpoint's temp dir is fully
  written, before the atomic `os.replace` publishes it
- `events.batch.pre_commit` — after a batch insert's `executemany`,
  before the transaction commits
- `events.group.pre_commit` — after a group-commit insert's
  `executemany` (the ingest write plane's coalesced single-event
  requests), before the shared transaction commits: proves no caller is
  ever 201-acknowledged for a row that did not commit
- `als.epoch_boundary` — between a training chunk's execution fence and
  its checkpoint save; armed per-rank it kills one member of a
  multi-process world at the worst moment (the elastic-recovery drill,
  test_failure_elastic_reform.py::TestElasticRecovery)
- `w2v.step_boundary` / `logreg.step_boundary` — the same
  chunk-computed-but-not-saved moment for the segmented W2V SGNS and
  LogReg Adam trainers (workflow/segmented.py)
- `serving.pre_dispatch` — inside the serving plane, after admission,
  before the model dispatch runs; `delay:`/`error` here make a worker
  slow or erroring under live load (the chaos gate's hang/error drills)
- `worker.startup` — in a pool worker before it reports ready; armed
  with the default die mode it crash-loops the worker (the supervisor's
  circuit-breaker drill)
- `sqlite.pre_commit` — in the sqlite backend between a transaction's
  statements and its COMMIT; `delay:` here widens the write-lock window
  to reproduce `database is locked` contention
- `online.pre_watermark` — in the online-learning plane's fold tailer,
  after a batch has folded and hot-swapped into the served state but
  BEFORE the watermark/dedup state advances; a kill or `error` here
  forces the next poll to replay the batch, proving fold-in idempotence
  and zero acked-but-unfolded events (the --online-gate crash drill)
"""

from __future__ import annotations

import os
import threading
import time


class FaultInjected(RuntimeError):
    """Raised by an armed `error`-mode fault site."""


# site -> (hit threshold, mode, delay_ms)
_armed: dict[str, tuple[int, str, int]] = {}
_hits: dict[str, int] = {}
_hits_lock = threading.Lock()
_parsed_from: str = ""


def _parse() -> None:
    global _parsed_from, _armed, _hits
    spec = os.environ.get("PIO_FAULTS", "")
    if spec == _parsed_from:
        return
    # mark the spec seen (and disarm) before parsing: a bad spec raises
    # once, at arm time — later inject() calls must not re-raise it
    _parsed_from = spec
    _armed = {}
    _hits = {}
    armed: dict[str, tuple[int, str, int]] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        mode, delay_ms = "die", 0
        if "=" in part:
            part, mode_spec = part.split("=", 1)
            if mode_spec.startswith("delay:"):
                mode, delay_ms = "delay", int(mode_spec[len("delay:"):])
            elif mode_spec == "error":
                mode = "error"
            elif mode_spec == "die":
                mode = "die"
            else:
                raise ValueError(f"unknown PIO_FAULTS mode {mode_spec!r}")
        if ":" in part:
            site, n = part.rsplit(":", 1)
            armed[site] = (int(n), mode, delay_ms)
        else:
            armed[part] = (1, mode, delay_ms)
    # rebind, don't clear-and-refill: an inject() racing the re-arm must
    # see either the old map or the new one, never a half-built map
    _armed = armed


def inject(site: str) -> None:
    """Fire `site`'s armed fault if its hit count is reached. A no-op
    (one env read + dict lookup) otherwise.

    `die` fires once (the process exits). `delay`/`error` fire on every
    hit from the armed count onward — a misbehaving dependency stays
    misbehaving until the supervisor (or the test) intervenes."""
    _parse()
    if not _armed:
        return
    entry = _armed.get(site)
    if entry is None:
        return
    n, mode, delay_ms = entry
    with _hits_lock:
        hits = _hits[site] = _hits.get(site, 0) + 1
    if hits < n:
        return
    if mode == "die":
        # stderr survives even though buffers don't get flushed on _exit
        os.write(2, f"PIO_FAULTS: dying at {site}\n".encode())
        os._exit(137)
    elif mode == "delay":
        time.sleep(delay_ms / 1000.0)
    else:  # error
        raise FaultInjected(f"PIO_FAULTS: injected error at {site}")
