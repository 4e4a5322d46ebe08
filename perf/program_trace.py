"""The run's trace once more, for what the program says of itself.

`perf/trace.py::load` keeps the benchmark's own `perf:*` host spans and
names an op by its instruction, shape and target. Two more things are in
the same `.xplane.pb`, on the same clock, once the program carries them:

- host events under the program's own span names
  (`predictionio_tpu/telemetry/spans.py::span` opens a
  `jax.profiler.TraceAnnotation` for each: `als.digest`, `als.bucketize`,
  `model.seen_items`, ...), kept here beside `perf:call`;
- for every event of a device's `XLA Ops` line the `op_name` of its
  instruction: the path of `jax.named_scope`s it was traced under
  (`jit(run)/while/body/closed_call/als.gather_gram/jit(_take)/gather:`).
  `scope_of` picks the innermost scope of a given set out of it.

Where the `op_name` is. Not in the event's name: this libtpu (0.0.34)
names an op by its HLO text in the short form, without `metadata={}`.
Not in the event's stats (`device_offset_ps`, `device_duration_ps`,
`Time Scale Multiplier`). It is the stat `tf_op` of the event's
*metadata* record (`XEventMetadata.stats`, beside `source`, `flops`,
`bytes_accessed`), which `jax.profiler.ProfileData` does not hand out. So
`op_names` reads that one map from the file's bytes with a wire-format
reader of its own (`_fields`; the message and field numbers are
xplane.proto's), keyed by the HLO text that is both the record's name
and the event's. The two other places are still looked at first, for a
runtime that fills them.

`load` returns

    {"host": [[name, start_ns, duration_ns], ...],
     "ops":  {device plane: [[short name, start_ns, duration_ns, op_name], ...]},
     "op_name_from": {where the op_name was found: events}}

The harness hands a reader no trace directory, so `latest` looks for the
newest capture under `$PIO_FS_BASEDIR/trace/`, where every run writes
its own. A program without spans or scopes (the commit before them, or
executables out of a compilation cache written before them: JAX leaves
metadata out of the cache's key) gives no such host events and empty
`op_name`s; the readers then return nothing.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import time

from perf import trace
from perf.harness import say

# the program's span names start with one of these; `perf:call` brackets
# one call of the traffic
PROGRAM_SPANS = re.compile(
    r"^(perf:call$|(als|model|dase|workflow|checkpoint)\.)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def latest() -> str | None:
    """Newest `.xplane.pb` under `$PIO_FS_BASEDIR/trace/`."""
    base = os.path.join(os.environ.get("PIO_FS_BASEDIR", ""), "trace")
    paths = glob.glob(os.path.join(base, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf):
    """(field number, value) of every field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field;
    fixed-width fields are skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {at}")


def op_names(xplane_path: str, stat: str = "tf_op") -> dict:
    """{device plane: {HLO text of an op: its `stat`}} from the planes'
    event-metadata records. xplane.proto: XSpace.planes = 1; XPlane.name
    = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key 1, value 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a
    stat-metadata id whose name is the value)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, stat_names, events = "", {}, []
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(dict(_fields(value))[2])
            elif number == 5:
                entry = dict(_fields(value))
                stat_names[entry[1]] = bytes(
                    dict(_fields(entry[2])).get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        ids = {i for i, n in stat_names.items() if n == stat}
        found = {}
        for record in events:
            text = ""
            for number, value in _fields(record):
                if number == 2:
                    text = bytes(value).decode()
                elif number == 5:
                    st = dict(_fields(value))
                    if st.get(1) in ids:
                        found[text] = (bytes(st[5]).decode() if 5 in st
                                       else stat_names.get(st.get(7), ""))
        out[name] = found
    return out


def _op_name(event, by_text: dict) -> tuple[str, str]:
    """(op_name, where it was found) of one `XLA Ops` event: in the HLO
    text that is its name, in one of its stats, else in its metadata
    record's `tf_op`."""
    m = _OP_NAME.search(event.name)
    if m:
        return m.group(1), "hlo_text"
    for key, value in event.stats:
        if isinstance(value, str) and key in ("tf_op", "op_name"):
            return value, f"stat:{key}"
    if event.name in by_text:
        return by_text[event.name], "metadata:tf_op"
    return "", "nowhere"


@functools.lru_cache(maxsize=2)
def load(xplane_path: str) -> dict:
    import jax

    t0 = time.perf_counter()
    data = jax.profiler.ProfileData.from_file(xplane_path)
    by_plane = op_names(xplane_path)
    out: dict = {"host": [], "ops": {}, "op_name_from": {}}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == trace.OPS_LINE:
                events = []
                by_text = by_plane.get(plane.name, {})
                for e in line.events:
                    op_name, where = _op_name(e, by_text)
                    out["op_name_from"][where] = (
                        out["op_name_from"].get(where, 0) + 1)
                    events.append([trace.short_name(e.name),
                                   float(e.start_ns), float(e.duration_ns),
                                   op_name])
                if events:
                    out["ops"][plane.name] = events
            elif not device:
                out["host"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if PROGRAM_SPANS.match(e.name))
    say(f"program_trace: {len(out['host'])} host events of the program, "
        f"op_name found in {out['op_name_from']}; the second load of the "
        f"trace took {time.perf_counter() - t0:.3f} s")
    return out


def of_run() -> dict | None:
    """The program's view of the run's own trace, loaded once."""
    path = latest()
    return load(path) if path else None


def scope_of(op_name: str, known) -> str | None:
    """The innermost of the `known` scopes on an op's name path."""
    for part in reversed(op_name.split("/")):
        if part in known:
            return part
    return None


def matches(name: str, patterns) -> bool:
    """A span name against a list of names; a pattern that ends in `*`
    matches every name that starts with the rest."""
    return any(name.startswith(p[:-1]) if p.endswith("*") else name == p
               for p in patterns)


def calls_of(host) -> list[tuple[float, float]]:
    """(start, end) of every `perf:call`, in order."""
    return sorted((s, s + d) for n, s, d in host if n == "perf:call")


def spans_inside(host, lo: float, hi: float, patterns):
    """(start, end) of the program's spans that match and lie inside
    [lo, hi]."""
    return [(s, s + d) for n, s, d in host
            if n != "perf:call" and lo <= s and s + d <= hi
            and matches(n, patterns)]
