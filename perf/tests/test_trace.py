"""The reduction from a trace to numbers, on a trace worked by hand and on
a slice recorded on the chip (`trace_als64_slice.json`: set-up, the host
phases of one `als64.train10` call and the first quarter second of its
train loop, one v5e, PR 23)."""

import os

import pytest

from perf import trace

HERE = os.path.dirname(os.path.abspath(__file__))
S = 1e9  # the trace counts nanoseconds


@pytest.fixture(scope="module")
def recorded():
    return trace.read(os.path.join(HERE, "trace_als64_slice.json"))


def by_hand():
    """Window 0..10 s. Ops a 1..3, b 2..4 (overlaps a), c 6..7; a `while`
    spans 1..7 and must not fill the gap 4..6. Host spans: outer 0..9,
    inner 4.5..5.5."""
    return {
        "ops": {"/device:TPU:0": [["%a f32[8,8]", 1 * S, 2 * S],
                                  ["%while.1 tuple", 1 * S, 6 * S],
                                  ["%b f32[8,8]", 2 * S, 2 * S],
                                  ["%a f32[8,8]", 6 * S, 1 * S]]},
        "modules": {"/device:TPU:0": [["jit_run(1)", 1 * S, 6 * S]]},
        "host": [["window", 0.0, 10 * S], ["outer", 0.0, 9 * S],
                 ["inner", 4.5 * S, 1 * S]],
    }


def test_busy_is_the_union_of_the_leaf_ops():
    t = by_hand()
    assert trace.busy_seconds(t) == pytest.approx(4.0)  # 1..4 and 6..7
    assert trace.idle_share(t) == pytest.approx(0.6)
    runs = trace.module_intervals(t, "^jit_run")
    assert runs == [(1 * S, 7 * S)]
    assert trace.busy_seconds(t, runs) == pytest.approx(4.0)
    assert trace.idle_share(t, [(0.0, 4 * S)]) == pytest.approx(0.25)


def test_sums_by_name_leave_out_control_flow():
    assert trace.top_ops(by_hand()) == [["%a f32[8,8]", 3.0],
                                        ["%b f32[8,8]", 2.0]]


def test_a_gap_goes_to_the_innermost_host_span():
    gaps = dict(trace.idle_gaps_by_span(by_hand()))
    # idle: 0..1, 4..6, 7..10; inner covers 4.5..5.5, outer the rest to 9
    assert gaps == pytest.approx({"outer": 1 + 1 + 2, "inner": 1.0,
                                  "(none)": 1.0})


def test_short_name_of_an_hlo_instruction():
    hlo = ('%closed_call.325 = f32[31296,64]{1,0:T(8,128)S(1)} custom-call('
           'f32[31296,64,128]{2,1,0:T(8,128)} %select_maximum_fusion.113), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace.short_name(hlo) == (
        "%closed_call.325 f32[31296,64] tpu_custom_call")
    assert trace.short_name("%while.90 = (s32[]{:T(128)}, f32[8]) while(...)"
                            ) == "%while.90 tuple"
    assert trace.short_name("perf:call") == "perf:call"


def test_the_recorded_slice(recorded):
    ops = recorded["ops"]["/device:TPU:0"]
    lo, hi = trace.window_of(recorded)
    assert (hi - lo) / S == pytest.approx(11.197056515)
    busy = trace.busy_seconds(recorded)
    assert busy == pytest.approx(0.250128578, rel=1e-6)
    assert trace.idle_share(recorded) == pytest.approx(1 - busy / 11.197056515)
    # the loop's program is busy from end to end
    runs = trace.module_intervals(recorded, "^jit_run")
    assert trace.busy_seconds(recorded, runs) == pytest.approx(0.25, rel=1e-4)
    name, seconds = trace.top_ops(recorded, 1)[0]
    assert name == "%closed_call.325 f32[31296,64] tpu_custom_call"
    assert seconds == pytest.approx(
        sum(d for n, _, d in ops if n == name) / S)
    # the device did nothing while the host bucketized: the whole span
    gaps = dict(trace.idle_gaps_by_span(recorded))
    span = next(d for n, _, d in recorded["host"] if n == "bucketize")
    assert gaps["bucketize"] == pytest.approx(span / S)
