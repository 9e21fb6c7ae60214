"""The program's own spans on a constructed trace (``bench/program_spans.py``):
their nesting inside the benchmark's spans, their self time, the device's
idle time by the innermost span, the per-call stages and waits, and calls
with no device operation or no program span."""

import dataclasses

import numpy as np
import pytest

from bench import program_spans as ps
from bench import trace as tr

HEAD_OP = "%fusion.2 = f32[1,3]{1,0} fusion(f32[1,120]{1,0} %a)"
SCAN_OP = "%_lstm_scan_jit.1 = f32[8,120]{1,0} custom-call(f32[15,8,6]{2,1,0} %x)"
US = 1e-6
STAGE_KEYS = {"put_us": "engine.put", "dispatch_us": "engine.dispatch",
              "fetch_us": "engine.fetch"}


def one_call(t0, put, dispatch, fetch, ops=()):
    """A ``bench.call`` at ``t0`` holding one ``engine.predict_one`` whose
    stages last ``put``, ``dispatch`` and ``fetch`` us, with 10 us of the
    engine's own work before them and 5 us after; ``ops`` are ``(start,
    end)`` in us from the start of ``engine.dispatch``."""
    s = t0 + 2 * US
    a = s + 10 * US
    b = a + put * US
    c = b + dispatch * US
    d = c + fetch * US
    e = d + 5 * US
    spans = [(t0, e + 2 * US, "bench.call")]
    program = [(s, e, "engine.predict_one"), (a, b, "engine.put"),
               (b, c, "engine.dispatch"), (c, d, "engine.fetch")]
    device = [(b + o0 * US, b + o1 * US, op)
              for (o0, o1), op in zip(ops, (SCAN_OP, HEAD_OP))]
    return spans, program, device


def constructed(calls, window=(0.0, 0.01)):
    spans, program, ops = [(window[0], window[1], tr.WINDOW_SPAN)], [], []
    for t0, *args in calls:
        s, p, o = one_call(t0, *args)
        spans += s
        program += p
        ops += o
    return (tr.Trace(ops={0: sorted(ops)}, spans=sorted(spans),
                     window=window), sorted(program))


# three calls; the third has no device operation
CALLS = [(0.001, 20, 30, 400, [(40, 48), (50, 52)]),
         (0.002, 40, 50, 600, [(60, 68), (70, 72)]),
         (0.003, 30, 40, 500, [])]


def split(constructed_trace):
    return ps.split(*constructed_trace, device=0)


def test_program_spans_nest_inside_bench_calls():
    trace, program = constructed(CALLS)
    roots = [sp for sp in program if sp[2] == "engine.predict_one"]
    for (s, e, _), (cs, ce, _) in zip(
            roots, [sp for sp in trace.spans if sp[2] == "bench.call"]):
        assert cs < s and e < ce
    got = ps.calls(program, trace.ops[0], trace.window)
    assert len(got) == 3
    assert got[0].stages == pytest.approx(
        {"engine.put": 20 * US, "engine.dispatch": 30 * US,
         "engine.fetch": 400 * US})
    assert split((trace, program))["bench_call_us"] == pytest.approx(
        np.mean([10 + sum(c[1:4]) + 5 + 4 for c in CALLS]))


@pytest.mark.parametrize("key, stage", list(STAGE_KEYS.items()))
def test_stage_means(key, stage):
    col = ("engine.put", "engine.dispatch", "engine.fetch").index(stage) + 1
    want = np.mean([c[col] for c in CALLS])
    assert split(constructed(CALLS))[key] == pytest.approx(want)


def test_stages_and_self_add_up_to_the_root():
    trace, program = constructed(CALLS)
    got = split((trace, program))
    total = sum(got[k] for k in STAGE_KEYS) + got["self_us"]
    roots = [e - s for s, e, n in program if n == "engine.predict_one"]
    assert got["self_us"] == pytest.approx(15.0)
    assert total == pytest.approx(1e6 * np.mean(roots))


def test_self_seconds_subtracts_children_not_grandchildren_twice():
    spans = sorted([(0.0, 10.0, "engine.flush"), (1.0, 2.0, "engine.pad"),
                    (2.0, 8.0, "engine.dispatch"),
                    (3.0, 7.0, "compile.acquire"),
                    (12.0, 13.0, "engine.flush")])
    assert ps.self_seconds(spans, "engine.flush") == pytest.approx([3.0, 1.0])
    assert ps.self_seconds(spans, "engine.dispatch") == pytest.approx([2.0])
    # a child that starts with its parent still counts as inside it
    tied = sorted([(0.0, 4.0, "engine.predict"), (0.0, 1.0, "engine.put")])
    assert ps.self_seconds(tied, "engine.predict") == pytest.approx([3.0])


def test_idle_goes_to_the_innermost_span():
    spans = sorted([(1.0, 9.0, "engine.predict_one"),
                    (2.0, 3.0, "engine.put"), (3.0, 5.0, "engine.dispatch"),
                    (5.0, 8.0, "engine.fetch")])
    gaps = [(0.0, 2.5), (4.0, 6.0), (7.5, 10.0)]
    assert ps.idle_by_innermost_span(gaps, spans) == pytest.approx({
        ps.OUTSIDE: 2.0, "engine.predict_one": 2.0,
        "engine.put": 0.5, "engine.dispatch": 1.0, "engine.fetch": 1.5})


def test_innermost_cuts_a_span_that_outlasts_its_parent():
    spans = sorted([(0.0, 4.0, "a"), (1.0, 6.0, "b"), (7.0, 8.0, "c")])
    assert ps.innermost(spans) == [(0.0, 1.0, "a"), (1.0, 4.0, "b"),
                                   (7.0, 8.0, "c")]


def test_idle_by_innermost_span_of_a_constructed_window():
    trace, program = constructed(CALLS)
    idle = dict(split((trace, program))["idle_by_innermost_span"])
    busy = sum(e - s for s, e in trace.merged(0))
    assert sum(idle.values()) == pytest.approx(0.01 - busy)
    # the two calls' device operations (10 us each) run inside fetch
    assert idle["engine.fetch"] == pytest.approx((1500 - 20) * US)


def test_launch_and_answer_waits_from_hand_placed_ops():
    got = split(constructed(CALLS))
    # first op 40 and 60 us after dispatch starts; the third call is
    # skipped, not read as 0
    assert got["launch_wait_us"] == pytest.approx(50.0)
    # fetch ends at dispatch + 30 + 400 and + 50 + 600; ops end at 52, 72
    assert got["answer_wait_us"] == pytest.approx(
        np.mean([430 - 52, 650 - 72]))
    assert got["in_order_share"] == pytest.approx(100.0)


def test_a_device_clock_offset_shows_as_a_negative_wait():
    trace, program = constructed(CALLS)
    shifted = dataclasses.replace(trace, ops={0: [
        (s - 50 * US, e - 50 * US, n) for s, e, n in trace.ops[0]]})
    w = ps.waits(ps.calls(program, shifted.ops[0], trace.window))
    assert [a / US for a, _ in w] == pytest.approx([-10.0, 10.0])
    # the sum of the two waits does not depend on the offset
    assert [(a + b) / US for a, b in w] == pytest.approx(
        [(a + b) / US for a, b in ps.waits(
            ps.calls(program, trace.ops[0], trace.window))])
    assert split((shifted, program))["in_order_share"] == pytest.approx(50.0)


def test_waits_read_nothing_when_no_call_has_a_device_op():
    got = split(constructed([c[:4] + ([],) for c in CALLS]))
    for key in ("launch_wait_us", "answer_wait_us", "in_order_share"):
        assert got[key] is None, key
    assert got["fetch_us"] == pytest.approx(500.0)


def test_calls_outside_the_window_are_left_out():
    got = split(constructed(CALLS, window=(0.0015, 0.01)))
    assert got["calls"] == 2
    assert got["put_us"] == pytest.approx(35.0)


def test_split_reads_nothing_without_program_spans():
    """A program with no spans: no call, and no number read as 0."""
    trace, _ = constructed(CALLS)
    got = split((trace, []))
    assert got["calls"] == 0
    for key in (*STAGE_KEYS, "self_us", "launch_wait_us", "answer_wait_us"):
        assert got[key] is None, key
    assert dict(got["idle_by_innermost_span"]).keys() == {ps.OUTSIDE}


def test_another_root_reads_its_own_calls():
    trace, program = constructed(CALLS)
    renamed = sorted((s, e, "engine.predict" if n == ps.ROOT else n)
                     for s, e, n in program)
    assert ps.split(trace, renamed, 0)["calls"] == 0
    got = ps.split(trace, renamed, 0, root="engine.predict")
    want = split((trace, program))
    for key in ("calls", *STAGE_KEYS, "self_us", "launch_wait_us",
                "answer_wait_us"):
        assert got[key] == want[key], key
