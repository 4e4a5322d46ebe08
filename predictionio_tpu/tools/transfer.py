"""Import/export: JSON-lines event files ↔ event store.

Parity with «tools/.../tools/imprt/FileToEvents.scala» and
«tools/.../tools/export/EventsToFile.scala» (SURVEY.md §2.3 [U]). The file
format is one event JSON object per line, the same wire shape as the event
API, so a file exported here can be imported by a reference installation
and vice versa.
"""

from __future__ import annotations

import json
import logging
from typing import Optional

from predictionio_tpu.data.events import Event, EventValidationError, validate_event
from predictionio_tpu.storage.registry import Storage

log = logging.getLogger(__name__)


def _resolve_app(storage: Storage, app_name: str, channel_name: Optional[str]):
    app = storage.meta_apps().get_by_name(app_name)
    if app is None:
        raise ValueError(f"App {app_name!r} does not exist.")
    channel_id = None
    if channel_name:
        channels = {c.name: c
                    for c in storage.meta_channels().get_by_app_id(app.id)}
        if channel_name not in channels:
            raise ValueError(f"Channel {channel_name!r} does not exist for app "
                             f"{app_name!r}.")
        channel_id = channels[channel_name].id
    return app.id, channel_id


def _native_sqlite_backend(storage: Storage):
    """The event store's SQLiteBackend when the C++ fast paths apply,
    else None. Exact type check: dialect subclasses (e.g. Postgres)
    share the class but not the db file."""
    from predictionio_tpu.storage.sqlite import SQLiteBackend

    backend = storage._backend(storage.config.eventdata)
    if type(backend) is not SQLiteBackend or backend.path == ":memory:":
        return None
    return backend


def _native_import(storage: Storage, input_path: str, app_id: int,
                   channel_id: Optional[int]) -> Optional[tuple[int, int]]:
    """C++ fast path (native/pio_import.cpp): parse + insert straight into
    the sqlite store; lines the parser can't render Python-identically
    come back as line numbers and go through the Python path below.
    Returns None when inapplicable (non-sqlite-file store, no toolchain,
    hard failure) — the caller then runs the Python path for everything."""
    from predictionio_tpu import native as _native

    backend = _native_sqlite_backend(storage)
    if backend is None:
        return None
    res = _native.import_events_native(input_path, backend.path, app_id,
                                       channel_id)
    if res is None:
        return None
    imported, skipped, fallback_lines, resume_from = res
    # the native importer may have rebuilt indexes it dropped for a
    # fresh-table bulk load; a crash in that window is healed here (and at
    # every backend init) because the schema DDL is IF NOT EXISTS
    with backend._cursor() as cur:
        from predictionio_tpu.storage.sqlite import _SCHEMA

        cur.executescript(_SCHEMA)
    want = set(fallback_lines)
    if want or resume_from:
        if want:
            log.info("import: %d line(s) use constructs outside the "
                     "native fast path; processing them in Python",
                     len(want))
        if resume_from:
            log.warning("import: native path stopped mid-file; resuming "
                        "from line %d in Python", resume_from)
        le = storage.l_events()
        batch: list[Event] = []
        CHUNK = 5000
        with open(input_path) as f:
            for lineno, line in enumerate(f, 1):
                redo = lineno in want or (resume_from
                                          and lineno >= resume_from)
                if not redo:
                    continue
                line = line.strip()
                if not line:
                    continue
                try:
                    event = Event.from_dict(json.loads(line))
                    validate_event(event)
                    event.event_id = None
                    batch.append(event)
                except (json.JSONDecodeError, EventValidationError,
                        ValueError, TypeError, KeyError) as e:
                    skipped += 1
                    log.warning("import: skipping line %d: %s", lineno, e)
                if len(batch) >= CHUNK:
                    imported += len(le.insert_batch(batch, app_id,
                                                    channel_id))
                    batch.clear()
        if batch:
            imported += len(le.insert_batch(batch, app_id, channel_id))
    return imported, skipped


def file_to_events(
    input_path: str,
    app_name: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
) -> tuple[int, int]:
    """Import events; returns (imported, skipped). Invalid lines are
    skipped with a warning, matching the reference's tolerant import."""
    storage = storage or Storage.get()
    app_id, channel_id = _resolve_app(storage, app_name, channel_name)
    native_result = _native_import(storage, input_path, app_id, channel_id)
    if native_result is not None:
        log.info("import: served by the native path")
        return native_result
    # bit-identical rows, but minutes slower at 20M events: say so
    log.info("import: served by the Python path")
    le = storage.l_events()
    imported = skipped = 0
    batch: list[Event] = []
    CHUNK = 5000  # one transaction per chunk (~20× the per-row-commit rate)
    with open(input_path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                event = Event.from_dict(json.loads(line))
                validate_event(event)
                # fresh ids: exported files keep eventId for traceability,
                # but ids are store-unique, so re-import must not reuse them
                event.event_id = None
                batch.append(event)
            except (json.JSONDecodeError, EventValidationError, ValueError,
                    TypeError, KeyError) as e:
                skipped += 1
                log.warning("import: skipping line %d: %s", lineno, e)
                continue
            if len(batch) >= CHUNK:
                imported += len(le.insert_batch(batch, app_id, channel_id))
                batch.clear()
    if batch:
        imported += len(le.insert_batch(batch, app_id, channel_id))
    return imported, skipped


def _native_export(storage: Storage, output_path: str, app_id: int,
                   channel_id: Optional[int]) -> Optional[int]:
    """C++ fast path (native/pio_export.cpp): stream sqlite rows straight
    to JSON lines, byte-identical to the Python path for rows this
    framework wrote. All-or-nothing: returns None when inapplicable or
    when the writer bailed (it removes its partial file), and the caller
    runs the Python path."""
    from predictionio_tpu import native as _native

    backend = _native_sqlite_backend(storage)
    if backend is None:
        return None
    return _native.export_events_native(backend.path, output_path, app_id,
                                        channel_id)


def events_to_file(
    output_path: str,
    app_name: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
) -> int:
    """Export all of an app's events as JSON lines; returns the count.

    SQLite stores stream through the C++ writer (measured 5.2× the
    per-event Python path at 1M events, byte-identical output, and O(1)
    memory where `find()` materializes every row as an Event object —
    18M events export in 84 s / 215k events/s, a scale the Python path
    cannot hold in memory); other stores take the Python path."""
    storage = storage or Storage.get()
    app_id, channel_id = _resolve_app(storage, app_name, channel_name)
    native_count = _native_export(storage, output_path, app_id, channel_id)
    if native_count is not None:
        return native_count
    events = storage.l_events().find(app_id=app_id, channel_id=channel_id)
    n = 0
    with open(output_path, "w") as f:
        for event in events:
            f.write(json.dumps(event.to_dict()) + "\n")
            n += 1
    return n
