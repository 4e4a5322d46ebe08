"""The set-up route's seven metrics: the reader `compile_log` on a
hand-made log, the layer files and their entries (the tail of
`per_layer` since PR 39), and two traced rehearsals on the CPU at
`perf/tests/tiny/`. No number of these runs is a measurement."""

import collections
import copy
import math
import time
import types

import pytest

from perf import harness
from perf.readers import compile_log
from perf.tests.conftest import ROOT, load
from perf.tests.test_encoder_cell import expected_per_layer

CELLS = ["als64.train10", "als128i.train10", "joyai.fit8_pack8k",
         "kimi_linear.fit8_pack8k", "phi4flash.fit8_pack8k"]
MINE = ["setup.trace_s", "setup.lower_s", "setup.cache_load_s",
        "setup.cache_misses", "setup.import_s", "setup.warmup_unspanned_s",
        "setup.unspanned_s"]
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}

Record = collections.namedtuple(
    "Record", "phase fn start end depth cache name", defaults=(None,))


class HandMadeLog:
    """The program's log as the reader sees it: `records(lo, hi)` gives
    what lies inside, `dropped` the count past the cap."""

    dropped = 0

    def __init__(self, records):
        self._records = records

    def records(self, t_lo=float("-inf"), t_hi=float("inf")):
        return [r for r in self._records
                if r.start >= t_lo and r.end <= t_hi]


# One run on a clock that starts at 100: set-up [100, 140], the data span
# 5 s somewhere in it, the warm-up call [120, 140], a window [140, 200] of
# calls of 8, 9 and 10 s, then the check.
RECORDS = [
    Record("trace", "earlier.process", 90.0, 95.0, 0, None),   # before t0
    Record("process.import", "predictionio_tpu", 100.5, 101.0, 0, None),
    Record("process.import", "jax", 101.0, 104.0, 0, None),
    Record("runtime.backend_init", "tpu", 104.0, 110.0, 0, None),
    # an eager op before the warm-up: its three phases
    Record("trace", "convert_element_type", 111.0, 111.5, 0, None),
    Record("lower", "convert_element_type", 111.5, 112.0, 0, None),
    Record("cache_load", "convert_element_type", 112.1, 112.4, 1, None),
    Record("backend_compile", "convert_element_type", 112.0, 112.5, 0,
           "hit"),
    # the warm-up call: a lazy import, the native library, one program
    # whose trace holds two inner traces (one inside the other) and whose
    # backend compile missed the cache
    Record("process.import", "predictionio_tpu.ops", 120.0, 121.0, 0, None),
    Record("native.load", "pio_native.so", 121.0, 121.5, 0, None),
    Record("trace", "als.train_steps", 122.5, 123.0, 2, None, "_take"),
    Record("trace", "als.train_steps", 122.0, 124.0, 1, None, "_lanes_kernel"),
    Record("trace", "als.train_steps", 121.5, 125.0, 0, None, "run"),
    Record("lower", "als.train_steps", 125.0, 127.0, 0, None),
    Record("backend_compile", "als.train_steps", 127.0, 130.0, 0, "miss"),
    # a recompile inside the window, and the check's own program after it
    Record("trace", "als.train_steps", 150.0, 150.25, 0, None),
    Record("trace", "reference", 201.0, 203.0, 0, None),
    Record("backend_compile", "reference", 203.0, 209.0, 0, "miss"),
]


def hand_made_harness():
    calls = [{"call": [(140.0, 148.0)]}, {"call": [(148.0, 157.0)]},
             {"call": [(157.0, 167.0)]}]
    return types.SimpleNamespace(
        t0=100.0, setup_end=140.0, window=(140.0, 200.0),
        setup_spans={"setup.data_s": 5.0, "setup.warmup_call_s": 20.0},
        recorder=types.SimpleNamespace(calls=calls))


@pytest.fixture()
def hand_made(monkeypatch):
    monkeypatch.setattr(compile_log, "_log", lambda: HandMadeLog(RECORDS))
    return hand_made_harness()


def spec_of(name):
    return load("perf", "layers", name + ".json")


@pytest.mark.parametrize("name, want", [
    # 0.5 before the warm-up + the outer trace's 3.5: the inner two are
    # inside it, the record before t0 and the window's and the check's
    # are outside set-up
    ("setup.trace_s", 4.0),
    ("setup.lower_s", 2.5),
    ("setup.cache_load_s", 0.3),
    ("setup.cache_misses", 1),        # the check's miss comes later
    ("setup.import_s", 4.5),
    # 20 s of warm-up - (1 + 0.5 + 3.5 + 2 + 3 of records) - a steady 9
    ("setup.warmup_unspanned_s", 1.0),
    # 40 s - (5 + 20 of the harness) - (0.5 + 3 + 6 of first seconds
    # before the warm-up; the import and the load inside it are its own)
    ("setup.unspanned_s", 5.5),
])
def test_the_reader_on_a_hand_made_log(hand_made, name, want):
    got = compile_log.read(spec_of(name), hand_made)
    assert got == pytest.approx(want, abs=1e-9)


def test_the_set_up_wall_is_the_sum_of_its_named_parts(hand_made):
    """By construction: the harness's spans, the first-seconds spans
    outside them and `setup.unspanned_s` are the wall the harness
    prints."""
    h = hand_made
    unspanned = compile_log.read(spec_of("setup.unspanned_s"), h)
    lo, _ = compile_log.warmup_interval(spec_of("setup.unspanned_s"), h)
    first_seconds = compile_log.union_s(
        r for r in HandMadeLog(RECORDS).records(h.t0, lo)
        if r.phase not in compile_log.JIT_PHASES)
    assert (sum(h.setup_spans.values()) + first_seconds + unspanned
            == pytest.approx(h.setup_end - h.t0))
    # and the warm-up call over a steady one is its phases + the rest
    warmup = compile_log.union_s(HandMadeLog(RECORDS).records(lo, h.setup_end))
    rest = compile_log.read(spec_of("setup.warmup_unspanned_s"), h)
    assert warmup + rest == pytest.approx(20.0 - 9.0)


def test_the_first_read_says_the_programs_and_the_windows_recompile(
        hand_made, capsys):
    compile_log._said.discard(id(hand_made))
    compile_log.read(spec_of("setup.trace_s"), hand_made)
    out = capsys.readouterr().out
    assert "als.train_steps: trace 3.500, lower 2.000, " \
           "backend_compile 3.000, cache_load 0.000, cache ['miss']" in out
    assert "traced inside it: _lanes_kernel x1 2.000\n" in out
    assert out.index("als.train_steps: trace") \
        < out.index("convert_element_type: trace")
    assert "built inside the window: trace als.train_steps 0.250 s " \
           "at 10.000 s" in out
    assert "reference" not in out
    compile_log.read(spec_of("setup.lower_s"), hand_made)
    assert capsys.readouterr().out == ""  # once a run


def test_a_program_without_the_log_gives_nothing(monkeypatch):
    """The parent commit's program: every metric is left out of the line."""
    monkeypatch.setattr(compile_log, "_log", lambda: None)
    for name in MINE:
        assert compile_log.read(spec_of(name), hand_made_harness()) is None


def test_the_seven_entries_and_their_layer_files(bench):
    tail = bench["per_layer"][-7:]
    assert [m["name"] for m in tail] == MINE
    for m in tail:
        assert m["workloads"] == CELLS
        assert (m["moves"], m["layer"], m["better"]) == (
            "setup_s", "set-up", "lower")
        spec = spec_of(m["name"])
        assert spec["reader"] == "compile_log"
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
    assert [m["unit"] for m in tail] == ["s", "s", "s", "count", "s", "s",
                                         "s"]
    # a counted quantity says so; a span's seconds are the program's span
    assert [m["source"] for m in tail] == [
        "program_span", "program_span", "program_counter",
        "program_counter", "program_span", "program_span", "program_span"]
    # the set-up layer's four older metrics have no list: every cell
    older = [m for m in bench["per_layer"][:-7] if m["layer"] == "set-up"]
    assert len(older) == 4 and all("workloads" not in m for m in older)
    # what a traced run's line is expected to hold, a cell: seven more than
    # `test_encoder_cell.py` and its siblings pinned (21 for an ALS cell)
    assert [len(expected_per_layer(bench, cell)) for cell in CELLS] == [
        28, 28, 25, 18, 20]


@pytest.fixture()
def run(bench, tmp_path, monkeypatch):
    tiny = copy.deepcopy(bench)
    for c in tiny["configs"]:
        c["file"] = f"perf/tests/tiny/{c['name']}.json"
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    # programs kept from an earlier test would carry its span wrapper,
    # and would not be built again
    from predictionio_tpu.ops import als
    from predictionio_tpu.templates.sessionrec import engine

    als._get_train_loop.cache_clear()
    engine._train_programs.cache_clear()
    engine._encoder_config.cache_clear()

    def run_cell(workload):
        import jax

        return harness.run_cell(ROOT, tiny, workload, 2 ** 31 + 39, 0.3,
                                True, time.perf_counter(),
                                jax.devices()[:1], peaks=PEAKS)

    return run_cell


@pytest.mark.parametrize("workload, program", [
    ("als64.train10", "als.train_steps"),
    ("phi4flash.fit8_pack8k", "sessionrec.train_step"),
])
def test_a_traced_run_prints_the_seven_beside_the_older(run, capsys,
                                                        workload, program):
    result = run(workload)
    assert result["correct"] is True
    got = result["metrics"]
    for name in MINE + ["setup.data_s", "setup.warmup_call_s",
                        "setup.compile_s", "setup.window_compiles"]:
        assert math.isfinite(got[name]["value"]), name
    assert got["setup.window_compiles"]["value"] == 0
    # the warm-up call built the cell's program: Python traced and
    # lowered it, and the union is no more than the call that holds it
    assert 0 < got["setup.trace_s"]["value"]
    assert 0 < got["setup.lower_s"]["value"]
    assert (got["setup.cache_load_s"]["value"]
            <= got["setup.compile_s"]["value"])
    out = capsys.readouterr().out
    assert f"[perf]   {program}: trace " in out
    assert "built inside the window" not in out
