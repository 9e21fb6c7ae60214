"""The trace reduction on a constructed trace: busy time as a union of
intervals, idle gaps given to the span the host was in, matching of
operations, and the per-layer readers built on them."""

import types

import numpy as np
import pytest

from bench import check, spec
from bench import trace as tr

LSTM_OP = "%_lstm_scan_jit.1 = f32[1024,128]{1,0} custom-call(f32[100,1024,3]{2,1,0} %x)"
COPY_OP = "%copy = f32[1024,100,3]{2,0,1} copy(f32[1024,100,3]{0,1,2} %x.1)"
HEAD_OP = "%fusion.2 = f32[1024,5]{1,0} fusion(f32[1024,128]{1,0} %a)"


def test_union_merges_overlaps_and_touches():
    assert tr.union([(3, 4, "c"), (0, 1, "a"), (0.5, 2, "b"), (2, 2.5, "d")]) \
        == [(0, 2.5), (3, 4)]


def test_busy_and_gaps_clip_to_the_window():
    merged = [(0.0, 2.0), (3.0, 4.0)]
    assert tr.busy(merged, 0.5, 3.5) == pytest.approx(2.0)
    assert tr.busy(merged, 2.1, 2.9) == 0.0
    assert tr.gaps(merged, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.gaps(merged, 0.5, 1.5) == []


def test_busy_bisection_equals_the_plain_sum():
    rng = np.random.RandomState(0)
    starts = np.sort(rng.rand(200)) * 100
    merged = tr.union([(s, s + rng.rand(), "") for s in starts])
    b = tr.Busy(merged)
    for lo, hi in rng.rand(50, 2) * 100:
        lo, hi = min(lo, hi), max(lo, hi)
        plain = sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)
        assert b.between(lo, hi) == pytest.approx(plain)


def test_idle_gaps_go_to_the_span_the_host_was_in():
    spans = [(0.0, 10.0, tr.WINDOW_SPAN), (1.0, 3.0, "bench.submit"),
             (3.0, 6.0, "bench.flush")]
    gaps = [(1.5, 2.5), (4.0, 5.0), (7.0, 9.0)]
    assert tr.idle_by_span(gaps, spans) == {
        "bench.submit": 1.0, "bench.flush": 1.0, tr.OUTSIDE_SPANS: 2.0}


def test_op_labels_and_pattern_matching():
    assert tr.op_label(LSTM_OP) == "_lstm_scan_jit.1 custom-call f32[1024,128]"
    ops = [(0.0, 1.0, LSTM_OP), (1.0, 1.5, COPY_OP), (2.0, 4.0, LSTM_OP)]
    assert sum(tr.op_seconds(ops, 0.5, 3.0, r"^%_lstm_scan_jit").values()) \
        == pytest.approx(1.5)
    assert tr.op_seconds(ops, 0.0, 5.0, r"^%_lstm_scan_jit").keys() == \
        {"_lstm_scan_jit.1 custom-call f32[1024,128]"}


def _run(cell_name, record, ops, spans, window):
    cell = spec.resolve(spec.load_benchmark(), cell_name)
    trace = tr.Trace(ops={0: sorted(ops)}, spans=sorted(spans), window=window)
    peaks = spec.load_json(f"{spec.ROOT}/bench/peaks.json")["TPU v5 lite"]
    return check.Run(cell=cell, record=record, trace=trace, setup_s=1.0,
                     peaks=peaks, device_ids=[0])


def test_host_self_time_and_idle_share():
    # two calls of 1 ms; the device is busy 0.1 ms in each
    spans = [(0.0, 0.004, tr.WINDOW_SPAN), (0.001, 0.002, "bench.call"),
             (0.002, 0.003, "bench.call")]
    ops = [(0.0015, 0.0016, HEAD_OP), (0.0025, 0.0026, HEAD_OP)]
    rec = types.SimpleNamespace(idx=np.arange(2), calls=2, t_begin=0.0,
                                t_end=0.004, winners={})
    run = _run("flavor_lstm.single", rec, ops, spans, (0.0, 0.004))
    read = spec.metric_reader("engine.host_self_us.latency")
    assert read(run) == pytest.approx(900.0)
    idle = spec.metric_reader("device.idle_share.latency")
    assert idle(run) == pytest.approx(95.0)
    idle_gaps = dict(check.breakdown(run)["idle_gaps"])
    assert idle_gaps == pytest.approx({"bench.call": 0.0018,
                                       tr.OUTSIDE_SPANS: 0.002})
    assert check.device_busy(run) == pytest.approx(
        {"busy_s": 0.0002, "window_s": 0.004})


def test_lstm_scan_roofline_counts_the_layer_work():
    # 1,024 QuickDraw events in one call, 1 ms of kernel time
    ops = [(0.0, 0.001, LSTM_OP), (0.001, 0.0015, COPY_OP)]
    spans = [(0.0, 0.002, tr.WINDOW_SPAN)]
    rec = types.SimpleNamespace(idx=np.arange(1024), calls=1, t_begin=0.0,
                                t_end=0.002, winners={})
    run = _run("quickdraw_lstm.backlog", rec, ops, spans, (0.0, 0.002))
    share = spec.metric_reader("lstm_scan_roofline")(run)
    assert share == pytest.approx(100 * 1024 * 13_414_400 / 197e12 / 0.001)
    mfu = spec.metric_reader("tagger_mfu.throughput")(run)
    assert mfu == pytest.approx(
        100 * 1024 / 0.002 * (13_414_400 + 132_352) / 197e12)


def test_readers_return_nothing_without_a_trace():
    rec = types.SimpleNamespace(idx=np.arange(3), calls=1, t_begin=0.0,
                                t_end=1.0, winners={}, queue_wait_s=None)
    run = _run("quickdraw_lstm.backlog", rec, [], [], (0.0, 1.0))
    run.trace = None
    for name in ("lstm_scan_roofline", "device.idle_share.throughput",
                 "engine.host_self_us.latency", "engine.queue_wait_p99_us",
                 "router.busiest_replica_share"):
        assert spec.metric_reader(name)(run) is None, name
