"""`perf/program_trace.py` and the three readers on top of it, on a slice
recorded on the chip (`trace_als64_program_slice.json`: the first call of
one traced `als64.train10` run, one v5e, PR 24, cut to the program's host
spans and to the ops of the first 0.12 s of its train loop with their
`op_name`s, the loop's module event cut to the same length; it is
`json.dump` of what `program_trace.load` returned plus `trace.load`'s
`modules`), on an `.xplane.pb` encoded by hand, and on a program that has
neither spans nor scopes."""

import json
import os
import types

import pytest

from perf import program_trace, trace
from perf.readers import gauge_ratio, program_span, scope_busy
from perf.tests.conftest import load

HERE = os.path.dirname(os.path.abspath(__file__))
KNOWN = ["als.gather_gram", "als.yty", "als.solve", "als.split_merge",
         "als.scatter", "als.rmse"]
NEW = ["train.digest_s", "train.bucket_build_s", "train.bucket_save_s",
       "train.put_buckets_s", "train.model_build_s",
       "train.call_unspanned_s", "train.gather_gram_s", "train.solve_s",
       "train.loop_rest_s", "train.bucket_fill"]


@pytest.fixture()
def recorded(monkeypatch):
    with open(os.path.join(HERE, "trace_als64_program_slice.json")) as f:
        view = json.load(f)
    monkeypatch.setattr(program_trace, "of_run", lambda: view)
    return view


def harness_of(view):
    return types.SimpleNamespace(
        trace={"modules": view["modules"]},
        config={"trace_names": {"train_loop": "^jit_run"}},
        traffic={"iterations": 1})


def layer(name):
    return load("perf", "layers", name + ".json")


def test_scope_of_takes_the_innermost_known_scope():
    path = ("jit(run)/while/body/closed_call/als.rmse/while/body/"
            "closed_call/als.gather_gram/jit(_take)/gather:")
    assert program_trace.scope_of(path, set(KNOWN)) == "als.gather_gram"
    assert program_trace.scope_of(path, {"als.rmse"}) == "als.rmse"
    assert program_trace.scope_of("jit(run)/while:", set(KNOWN)) is None
    assert program_trace.scope_of("", set(KNOWN)) is None


def test_a_pattern_is_a_name_or_a_prefix():
    pats = ["als.digest", "checkpoint.save*", "model.*"]
    assert program_trace.matches("als.digest", pats)
    assert program_trace.matches("checkpoint.save step_3", pats)
    assert program_trace.matches("model.seen_items", pats)
    assert not program_trace.matches("als.digest.more", pats)
    assert not program_trace.matches("perf:call", pats)


@pytest.mark.parametrize("metric,seconds", [
    ("train.digest_s", 0.6459668),
    ("train.bucket_build_s", 2.282431323 + 7.003918056),
    ("train.bucket_save_s", 0.456579244),
    ("train.put_buckets_s", 0.311290398),
    ("train.model_build_s", 3.525905163),
])
def test_program_span_sums_the_named_spans_inside_the_call(
        recorded, metric, seconds):
    assert program_span.read(layer(metric), None) == pytest.approx(
        seconds, rel=1e-9)


def test_self_time_is_the_call_less_the_union_of_its_leaf_spans(recorded):
    spec = layer("train.call_unspanned_s")
    got = program_span.read(spec, None)
    (lo, hi), = program_trace.calls_of(recorded["host"])
    leaves = program_trace.spans_inside(recorded["host"], lo, hi,
                                        spec["spans"])
    assert len(leaves) == 11  # als.train itself is no leaf
    assert got == pytest.approx(
        (hi - lo - sum(e - s for s, e in leaves)) / 1e9, rel=1e-9)
    assert 0.0 < got < 0.2
    # a span listed twice, or one inside another, is counted once
    twice = dict(spec, spans=spec["spans"] + ["als.digest", "als.train"])
    whole = program_span.read(twice, None)
    (t_lo, t_hi), = program_trace.spans_inside(recorded["host"], lo, hi,
                                               ["als.train"])
    seen, = program_trace.spans_inside(recorded["host"], lo, hi, ["model.*"])
    assert whole == pytest.approx(
        (hi - lo - (t_hi - t_lo) - (seen[1] - seen[0])) / 1e9, rel=1e-9)


def test_the_scope_metrics_partition_the_loops_busy_time(recorded):
    h = harness_of(recorded)
    parts = {m: scope_busy.read(layer(m), h) for m in
             ("train.gather_gram_s", "train.solve_s", "train.loop_rest_s")}
    ops = {p: [e[:3] for e in ev] for p, ev in recorded["ops"].items()}
    runs = trace.module_intervals(h.trace, "^jit_run")
    whole = trace.busy_seconds({"ops": ops}, runs)
    assert sum(parts.values()) == pytest.approx(whole, rel=1e-9)
    assert whole == pytest.approx(0.082704773, rel=1e-9)
    assert parts["train.gather_gram_s"] == pytest.approx(0.019150566, rel=1e-9)
    assert parts["train.solve_s"] == pytest.approx(0.061313744, rel=1e-9)
    assert parts["train.loop_rest_s"] == pytest.approx(0.002240463, rel=1e-9)
    for m in parts:
        assert layer(m)["known"] == KNOWN


def test_units_are_the_programs_runs_times_the_traffics_iterations(recorded):
    h = harness_of(recorded)
    one = scope_busy.read(layer("train.solve_s"), h)
    h.traffic = {"iterations": 10}
    assert scope_busy.read(layer("train.solve_s"), h) == pytest.approx(
        one / 10)


def test_a_program_without_spans_or_scopes_gives_nothing(recorded,
                                                         monkeypatch):
    bare = {"host": [e for e in recorded["host"] if e[0] == "perf:call"],
            "ops": {p: [e[:3] + [""] for e in ev]
                    for p, ev in recorded["ops"].items()},
            "modules": recorded["modules"]}
    monkeypatch.setattr(program_trace, "of_run", lambda: bare)
    for m in NEW[:6]:
        assert program_span.read(layer(m), None) is None, m
    for m in NEW[6:9]:
        assert scope_busy.read(layer(m), harness_of(bare)) is None, m
    monkeypatch.setattr(program_trace, "of_run", lambda: None)
    assert program_span.read(layer(NEW[0]), None) is None
    assert scope_busy.read(layer(NEW[6]), harness_of(bare)) is None


def test_gauge_ratio_sums_each_gauge_over_its_labels():
    from predictionio_tpu.telemetry.registry import REGISTRY

    num = REGISTRY.gauge("perf_test_entries", "", labelnames=("side",))
    den = REGISTRY.gauge("perf_test_cells", "", labelnames=("side",))
    spec = {"numerator": "perf_test_entries",
            "denominator": "perf_test_cells"}
    assert gauge_ratio.read(spec, None) is None  # nothing set: 0 cells
    for side, n, d in (("user", 30, 40), ("item", 30, 60)):
        num.labels(side=side).set(n)
        den.labels(side=side).set(d)
    assert gauge_ratio.read(spec, None) == pytest.approx(60.0)
    assert gauge_ratio.read(dict(spec, numerator="perf_test_absent"),
                            None) is None


def test_the_ten_new_metrics_are_in_the_manifest_with_their_files(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-10:] == NEW
    for name in NEW:
        spec = layer(name)
        assert spec["reader"] in ("program_span", "scope_busy",
                                  "gauge_ratio")
        assert "workloads" not in entries[name]
        assert entries[name]["source"] == {
            "program_span": "program_span", "scope_busy": "device_trace",
            "gauge_ratio": "program_counter"}[spec["reader"]]


# -- an .xplane.pb by hand: the wire reader and `load` end to end ----------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def event_metadata(key, name, *stats):
    record = field(1, key) + field(2, name) + b"".join(
        field(5, s) for s in stats)
    return field(4, field(1, key) + field(2, record))


def stat_metadata(key, name):
    return field(5, field(1, key) + field(2, field(1, key) + field(2, name)))


def line(name, *events):
    return field(3, field(2, name) + field(3, 1000) + b"".join(
        field(4, field(1, md) + field(2, off_ps) + field(3, dur_ps))
        for md, off_ps, dur_ps in events))


@pytest.fixture()
def by_hand(tmp_path):
    """One device plane (two ops; `tf_op` once as a string, once as a
    reference to a stat-metadata name), one host plane (`perf:call`
    around `als.digest`, and an event that is not the program's)."""
    gather = "%fusion.1 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %p), kind=kLoop"
    solve = '%als.solve.2 = f32[8,4]{1,0} custom-call(), custom_call_target="tpu_custom_call"'
    device = (field(2, "/device:TPU:0")
              + stat_metadata(7, "tf_op") + stat_metadata(8, "flops")
              + stat_metadata(9, "jit(run)/while/body/als.solve/pallas_call:")
              + event_metadata(
                  1, gather, field(1, 8) + field(3, 64),
                  field(1, 7) + field(5, "jit(run)/als.gather_gram/gather:"))
              + event_metadata(2, solve, field(1, 7) + field(7, 9))
              + event_metadata(3, "%copy.3 = f32[8]{0} copy(f32[8]{0} %q)")
              + line("XLA Ops", (1, 0, 2_000_000), (2, 2_000_000, 3_000_000),
                     (3, 5_000_000, 1_000_000))
              + line("XLA Modules", (1, 0, 6_000_000)))
    host = (field(2, "/host:CPU")
            + event_metadata(1, "perf:call") + event_metadata(2, "als.digest")
            + event_metadata(3, "PjitFunction(run)")
            + line("python3", (1, 0, 9_000_000), (2, 1_000_000, 4_000_000),
                   (3, 5_000_000, 1_000_000)))
    path = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    (path / "vm.xplane.pb").write_bytes(field(1, device) + field(1, host))
    return str(path / "vm.xplane.pb")


def test_the_wire_reader_finds_tf_op_in_the_event_metadata(by_hand):
    names = program_trace.op_names(by_hand)
    assert list(names) == ["/device:TPU:0"]
    got = {text.split(" ")[0]: op for text, op in
           names["/device:TPU:0"].items()}
    assert got == {"%fusion.1": "jit(run)/als.gather_gram/gather:",
                   "%als.solve.2": "jit(run)/while/body/als.solve/pallas_call:"}


def test_load_puts_spans_and_scoped_ops_on_one_clock(by_hand, monkeypatch,
                                                    tmp_path):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    assert program_trace.latest() == by_hand
    view = program_trace.of_run()
    assert view["host"] == [["perf:call", 1000.0, 9000.0],
                            ["als.digest", 2000.0, 4000.0]]
    assert view["ops"] == {"/device:TPU:0": [
        ["%fusion.1 f32[8,4]", 1000.0, 2000.0,
         "jit(run)/als.gather_gram/gather:"],
        ["%als.solve.2 f32[8,4] tpu_custom_call", 3000.0, 3000.0,
         "jit(run)/while/body/als.solve/pallas_call:"],
        ["%copy.3 f32[8]", 6000.0, 1000.0, ""]]}
    assert view["op_name_from"] == {"metadata:tf_op": 2, "nowhere": 1}
    assert program_span.read({"spans": ["als.digest"]}, None) == 4e-6


def test_no_trace_directory_no_view(monkeypatch, tmp_path):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    assert program_trace.latest() is None and program_trace.of_run() is None
