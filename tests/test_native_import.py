"""Native JSON-lines import (native/pio_import.cpp): the C++ fast path
must produce exactly the rows the Python path produces — same validation
outcomes, same normalized properties/tags/timestamps — with unsupported
constructs routed back through Python per-line. Cross-validated by
running both paths on the same file and diffing the stored rows."""

import json
import sqlite3

import pytest

from predictionio_tpu import native
from predictionio_tpu.storage.base import App
from predictionio_tpu.storage.registry import (
    SourceConfig, Storage, StorageConfig,
)
from predictionio_tpu.tools import transfer

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="no native toolchain")


LINES = [
    # plain event
    {"event": "rate", "entityType": "user", "entityId": "u1",
     "targetEntityType": "item", "targetEntityId": "i1",
     "properties": {"rating": 4.5}, "eventTime": "2024-03-01T10:20:30.123Z"},
    # integer-coerced ids, int + float + bool + null + nested properties
    {"event": "view", "entityType": "user", "entityId": 42,
     "targetEntityType": "item", "targetEntityId": 7,
     "properties": {"z": 1, "a": 100.0, "m": {"y": [1, 2.5, "s"], "x": True},
                    "n": None, "big": 12345678901234567890123},
     "eventTime": "2024-03-01T12:00:00+05:30"},
    # unicode + escapes + sorted-key check + tags + prId
    {"event": "buy", "entityType": "user", "entityId": "ué",
     "properties": {"b": "héllo\nworld", "a": "ctrl",
                    "emoji": "\U0001f600"},
     "tags": ["t2", "t1"], "prId": "pr-1",
     "eventTime": "2024-12-31T23:59:59.999999Z"},
    # special events
    {"event": "$set", "entityType": "user", "entityId": "s1",
     "properties": {"p": "v"}},
    {"event": "$unset", "entityType": "user", "entityId": "s2",
     "properties": {"p": None}},
    {"event": "$delete", "entityType": "user", "entityId": "s3"},
    # no eventTime → import-time stamp (compared modulo time)
    {"event": "ping", "entityType": "user", "entityId": "p1"},
    # duplicate keys in properties: last wins (raw JSON below)
    None,  # placeholder, replaced by raw line
    # float exponent + negative zero + small floats
    {"event": "f", "entityType": "user", "entityId": "f1",
     "properties": {"a": 1e20, "b": -0.0, "c": 1.5e-07, "d": 0.1}},
    # r2 review: repr picks FIXED notation for exponents in [-4, 16)
    {"event": "f2", "entityType": "user", "entityId": "f2",
     "properties": {"a": 1e5, "b": 1e15, "c": 1e16, "d": 1e-4, "e": 1e-5,
                    "f": 123456.789}},
    # r2 review: falsy properties coerce to {} (Python's `or {}`)
    {"event": "falsyprops", "entityType": "user", "entityId": "fp1",
     "properties": []},
    # r2 review: falsy eventTime means "stamp now", not an error
    {"event": "falsytime", "entityType": "user", "entityId": "ft1",
     "eventTime": ""},
    # r2 review: dict-valued tag elements keep insertion order (no
    # sort_keys on the tags dump)
    {"event": "dicttags", "entityType": "user", "entityId": "dt1",
     "tags": [{"b": 1, "a": 2}]},
    # eventId in file must NOT be reused
    {"event": "hasid", "entityType": "user", "entityId": "h1",
     "eventId": "feedfacefeedfacefeedfacefeedface"},
]

RAW_EXTRAS = [
    '{"event": "dup", "entityType": "user", "entityId": "d1", '
    '"properties": {"k": 1, "k": 2}}',
    # invalid: reserved event name
    '{"event": "$bogus", "entityType": "user", "entityId": "x"}',
    # invalid: pio_ property
    '{"event": "e", "entityType": "user", "entityId": "x", '
    '"properties": {"pio_x": 1}}',
    # invalid: $set with target
    '{"event": "$set", "entityType": "user", "entityId": "x", '
    '"targetEntityId": "y"}',
    # invalid: not json
    'not json at all',
    # invalid: missing entityId
    '{"event": "e", "entityType": "user"}',
    # fallback-path construct: NaN (json.loads accepts it)
    '{"event": "nan", "entityType": "user", "entityId": "n1", '
    '"properties": {"v": NaN}}',
    # fallback: float-typed entityId (Python str()s it)
    '{"event": "fid", "entityType": "user", "entityId": 3.5}',
    # r2 review: leading-zero int is invalid JSON (Python skips the line)
    '{"event": "lz", "entityType": "user", "entityId": 007}',
    # r2 review: -0 int normalizes to 0 like json.dumps(json.loads("-0"))
    '{"event": "negzero", "entityType": "user", "entityId": "nz1", '
    '"properties": {"v": -0}}',
    # r2 review: impossible date — Python rejects, so must we
    '{"event": "feb30", "entityType": "user", "entityId": "x", '
    '"eventTime": "2024-02-30T00:00:00Z"}',
    "",  # blank line
]


def _write_file(path):
    with open(path, "w") as f:
        for obj in LINES:
            if obj is None:
                continue
            f.write(json.dumps(obj) + "\n")
        for raw in RAW_EXTRAS:
            f.write(raw + "\n")


def _mk_storage(db_path):
    src = SourceConfig(name="S", type="sqlite", path=str(db_path))
    storage = Storage(StorageConfig(metadata=src, modeldata=src,
                                    eventdata=src))
    app_id = storage.meta_apps().insert(App(id=0, name="ImpApp"))
    return storage, app_id


def _rows(db_path):
    conn = sqlite3.connect(db_path)
    rows = conn.execute(
        "SELECT event, entity_type, entity_id, target_entity_type, "
        "target_entity_id, properties, event_time, tags, pr_id "
        "FROM events").fetchall()
    conn.close()
    # event_time of stamped-at-import events varies → zero it when recent
    out = []
    for r in rows:
        r = list(r)
        out.append(tuple(r))
    return sorted(out)


def test_native_and_python_paths_produce_identical_rows(tmp_path):
    f = tmp_path / "events.jsonl"
    _write_file(f)

    db_native = tmp_path / "native.db"
    st_n, app_n = _mk_storage(db_native)
    imported_n, skipped_n = transfer.file_to_events(str(f), "ImpApp",
                                                    storage=st_n)
    st_n.close()

    db_py = tmp_path / "python.db"
    st_p, app_p = _mk_storage(db_py)
    orig = native.import_events_native
    try:
        native.import_events_native = lambda *a, **k: None  # force Python
        imported_p, skipped_p = transfer.file_to_events(str(f), "ImpApp",
                                                        storage=st_p)
    finally:
        native.import_events_native = orig
    st_p.close()

    assert (imported_n, skipped_n) == (imported_p, skipped_p)
    rows_n, rows_p = _rows(db_native), _rows(db_py)
    assert len(rows_n) == len(rows_p) == imported_n

    # the only lines with a REAL eventTime (falsytime's "" means "now")
    has_time = {"rate", "view", "buy"}

    def strip_now(rows):
        # events without an eventTime are stamped at import time; compare
        # those for format only, not value
        out = []
        for r in rows:
            r = list(r)
            if r[0] not in has_time:
                assert len(r[6]) == 27 and r[6].endswith("Z")
                r[6] = "<now>"
            out.append(tuple(r))
        return out

    assert strip_now(rows_n) == strip_now(rows_p)


def test_native_import_normalizations(tmp_path):
    """Spot-check the C++ renderings directly: sorted keys, ensure_ascii,
    float repr, timezone conversion, id coercion, duplicate-key last-wins,
    fresh event ids."""
    f = tmp_path / "ev.jsonl"
    _write_file(f)
    db = tmp_path / "n2.db"
    st, _ = _mk_storage(db)
    transfer.file_to_events(str(f), "ImpApp", storage=st)
    st.close()

    conn = sqlite3.connect(db)
    get = lambda ev: conn.execute(
        "SELECT properties, event_time, entity_id, target_entity_id, tags, "
        "id FROM events WHERE event=?", (ev,)).fetchone()

    props, etime, eid, teid, tags, rowid = get("view")
    assert eid == "42" and teid == "7"
    assert etime == "2024-03-01T06:30:00.000000Z"  # +05:30 → UTC
    obj = json.loads(props)
    assert list(obj.keys()) == sorted(obj.keys())
    assert obj["big"] == 12345678901234567890123
    assert props == json.dumps(obj, sort_keys=True)

    props, _, eid, _, tags, _ = get("buy")
    assert "\\u00e9" in props and "\\ud83d\\ude00" in props  # ensure_ascii
    assert json.loads(tags) == ["t2", "t1"]  # list order preserved

    props, _, _, _, _, _ = get("f")
    assert json.loads(props) == {"a": 1e20, "b": -0.0, "c": 1.5e-07,
                                 "d": 0.1}
    assert props == json.dumps(json.loads(props), sort_keys=True)

    props, _, _, _, _, _ = get("dup")
    assert json.loads(props) == {"k": 2}  # duplicate key: last wins

    _, _, _, _, _, rowid = get("hasid")
    assert rowid != "feedfacefeedfacefeedfacefeedface"  # fresh id
    assert len(rowid) == 32

    _, _, eid, _, _, _ = get("fid")  # float id via the Python fallback
    assert eid == "3.5"

    props, _, _, _, _, _ = get("f2")  # fixed-vs-scientific thresholds
    assert props == json.dumps(
        {"a": 1e5, "b": 1e15, "c": 1e16, "d": 1e-4, "e": 1e-5,
         "f": 123456.789}, sort_keys=True)
    assert '"a": 100000.0' in props and '"c": 1e+16' in props
    assert '"d": 0.0001' in props and '"e": 1e-05' in props

    props, _, _, _, _, _ = get("falsyprops")
    assert props == "{}"
    assert get("falsytime") is not None  # imported, stamped now
    assert get("lz") is None             # invalid JSON → skipped
    assert get("feb30") is None          # impossible date → skipped
    props, _, _, _, _, _ = get("negzero")
    assert props == '{"v": 0}'
    _, _, _, _, tags, _ = get("dicttags")
    assert tags == '[{"b": 1, "a": 2}]'  # insertion order kept
    conn.close()


def test_native_import_speed_sanity(tmp_path):
    """The fast path must actually import a bulk file (count integrity at
    a non-trivial size; speed itself is a benchmark's business)."""
    f = tmp_path / "bulk.jsonl"
    n = 20_000
    with open(f, "w") as fh:
        for i in range(n):
            fh.write(json.dumps({
                "event": "rate", "entityType": "user",
                "entityId": str(i % 500), "targetEntityType": "item",
                "targetEntityId": str(i % 300),
                "properties": {"rating": float(1 + i % 5)},
                "eventTime": "2024-01-01T00:00:00Z"}) + "\n")
    db = tmp_path / "bulk.db"
    st, _ = _mk_storage(db)
    imported, skipped = transfer.file_to_events(str(f), "ImpApp", storage=st)
    assert (imported, skipped) == (n, 0)
    assert len(st.l_events().find(app_id=1, limit=n + 1)) == n
    st.close()


def test_stamped_times_are_distinct_and_ordered(tmp_path):
    """Events missing eventTime/creationTime get per-line 'now' stamps
    that advance monotonically (ADVICE r2 #2) — a single shared stamp
    would tie every such event in ORDER BY event_time, creation_time."""
    path = tmp_path / "stamped.json"
    with open(path, "w") as f:
        for i in range(50):
            f.write(json.dumps({"event": "sign-up", "entityType": "user",
                                "entityId": f"u{i}"}) + "\n")
    storage, app_id = _mk_storage(tmp_path / "stamped.db")
    try:
        imported, skipped = transfer.file_to_events(
            str(path), "ImpApp", storage=storage)
        assert (imported, skipped) == (50, 0)
        conn = sqlite3.connect(tmp_path / "stamped.db")
        times = [r[0] for r in conn.execute(
            "SELECT event_time FROM events ORDER BY rowid").fetchall()]
        conn.close()
        assert len(set(times)) == 50  # all distinct
        assert times == sorted(times)  # file order preserved
    finally:
        storage.close()


def test_bulk_path_preserves_user_created_indexes(tmp_path):
    """The fresh-table bulk load drops/rebuilds only the _SCHEMA-owned
    idx_events_* indexes; a user-created index must survive untouched
    (ADVICE r2 #3 — previously it was dropped and, after a crash in the
    drop→rebuild window, lost forever)."""
    db = tmp_path / "uidx.db"
    storage, app_id = _mk_storage(db)
    try:
        conn = sqlite3.connect(db)
        conn.execute("CREATE INDEX user_custom_idx ON events (pr_id)")
        conn.commit()
        conn.close()
        path = tmp_path / "bulk.json"
        with open(path, "w") as f:
            for i in range(100):
                f.write(json.dumps(
                    {"event": "rate", "entityType": "user",
                     "entityId": f"u{i}", "targetEntityType": "item",
                     "targetEntityId": "i1",
                     "properties": {"rating": 3.0}}) + "\n")
        imported, _ = transfer.file_to_events(str(path), "ImpApp",
                                              storage=storage)
        assert imported == 100
        conn = sqlite3.connect(db)
        names = {r[0] for r in conn.execute(
            "SELECT name FROM sqlite_master WHERE type='index' "
            "AND tbl_name='events'").fetchall()}
        conn.close()
        assert "user_custom_idx" in names
        assert any(n.startswith("idx_events_") for n in names)  # rebuilt
    finally:
        storage.close()
