"""A whole run of each cell, on the CPU at a small pool and a short window,
skipping only the harness's look for a chip: sound, it comes out correct;
with the timed path broken underneath, or with the control (one bfloat16
pass per product, as a TPU computes float32 at the default precision) put
in the program's place, it comes out not correct."""

import dataclasses
import itertools
import time

import jax
import numpy as np
import pytest

import bench.run as bench_run
from bench import reference, spec
from repro.serving import RNNServingEngine

BENCH = spec.load_benchmark()
PEAKS = spec.load_json(f"{spec.ROOT}/bench/peaks.json")["TPU v5 lite"]
SEED = 2**31 + 11

# the CPU runs the program's XLA datapath; the routed cell runs the
# flavor tagger so that its six schedules compile quickly
CELLS = {
    "flavor_lstm.single": ({}, {}),
    "quickdraw_lstm.backlog": ({}, {"batch": 128}),
    "flavor_lstm.trigger": ({}, {"rate_per_s": 20000}),
    "quickdraw_lstm.routed4": (
        spec.load_json(f"{spec.ROOT}/bench/configs/flavor_lstm.json"),
        {"schedules": [dict(s, backend="xla") for s in spec.load_json(
            spec.traffic_file("routed4"))["schedules"]]}),
}
BATCHED = ("quickdraw_lstm.backlog", "flavor_lstm.trigger")


def small_cell(name):
    """The cell's configuration and traffic files, found by its name, at a
    small pool on the XLA datapath.  Cells not (yet) in ``BENCHMARK.json``
    report the metrics their entry point yields."""
    config_over, mix = CELLS[name]
    config_name, traffic = name.split(".")
    config = config_over or spec.load_json(
        f"{spec.ROOT}/bench/configs/{config_name}.json")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    listed = {w["name"] for w in BENCH["workloads"]}
    cell = (spec.resolve(BENCH, name) if name in listed else spec.Cell(
        name=name, chips=1, config=config, traffic=traffic,
        mix=spec.load_json(spec.traffic_file(traffic)),
        end_to_end=[e2e["setup_s"]], per_layer=[],
        family=spec.family(config["family"])))
    return dataclasses.replace(cell, config=dict(config, impl="xla"),
                               mix=dict(cell.mix, pool=256, warm_calls=2,
                                        **mix))


def run(cell, cache_root):
    return bench_run.run_cell(cell, SEED, 0.25, False, jax.devices()[:1],
                              time.perf_counter(), PEAKS,
                              cache_root=str(cache_root))


@pytest.fixture(scope="module")
def cache_root(tmp_path_factory):
    return tmp_path_factory.mktemp("engines")


def _wrap_outputs(monkeypatch, change):
    """Apply ``change(answers) -> answers`` where the engine produces its
    answers: the batched call and the batch-1 call."""
    predict_key = RNNServingEngine._predict_key
    predict_one = RNNServingEngine.predict_one

    def batched(self, key, x, lengths=None):
        return change(self, np.array(predict_key(self, key, x, lengths)), x)

    def one(self, x, *a, **kw):
        return change(self, np.array(predict_one(self, x, *a, **kw))[None],
                      np.asarray(x)[None])[0]

    monkeypatch.setattr(RNNServingEngine, "_predict_key", batched)
    monkeypatch.setattr(RNNServingEngine, "predict_one", one)


def answer_altered(monkeypatch):
    calls = itertools.count()

    def change(self, out, x):
        if next(calls) % 7 == 3:
            out[len(out) // 2, 0] += 1e-3
        return out
    _wrap_outputs(monkeypatch, change)


def answer_of_another_event(monkeypatch):
    last = {}

    def change(self, out, x):
        if len(out) > 1:
            return np.roll(out, 1, axis=0)
        prev, last["out"] = last.get("out", out), out
        return prev
    _wrap_outputs(monkeypatch, change)


def half_batch_left_out(monkeypatch):
    def change(self, out, x):
        keep = (len(out) + 1) // 2
        out[keep:] = out[:len(out) - keep]
        return out
    _wrap_outputs(monkeypatch, change)


def control_in_place(monkeypatch):
    def change(self, out, x):
        params = {k: np.asarray(v) for k, v in self.params.items()}
        model = {"cell": self.cfg.rnn.cell,
                 "dense_sizes": self.cfg.rnn.dense_sizes}
        return reference.probabilities(model, params, np.asarray(x),
                                       "default")
    _wrap_outputs(monkeypatch, change)


def state_unchanged(monkeypatch):
    """The recurrence returns its initial state: every step is skipped."""
    from repro.models import rnn_tagger

    def frozen(rnn, xs, *a, **kw):
        return jax.numpy.zeros((xs.shape[0], rnn.hidden), xs.dtype)
    monkeypatch.setattr(rnn_tagger, "rnn_layer", frozen)


FAULTS = {"answer_altered": answer_altered,
          "answer_of_another_event": answer_of_another_event,
          "half_batch_left_out": half_batch_left_out,
          "state_unchanged": state_unchanged,
          "control": control_in_place}
CASES = [(c, f) for c in CELLS for f in FAULTS
         if f != "half_batch_left_out" or c in BATCHED]


@pytest.mark.parametrize("name", list(CELLS))
def test_sound_run_is_correct(name, cache_root):
    res = run(small_cell(name), cache_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {m["name"] for m in small_cell(name).end_to_end} \
        == set(res["metrics"])


@pytest.mark.parametrize("name, fault", CASES)
def test_broken_timed_path_is_not_correct(name, fault, cache_root, tmp_path,
                                          monkeypatch):
    FAULTS[fault](monkeypatch)
    # a fault inside the compiled program must not reach the shared cache
    root = tmp_path if fault == "state_unchanged" else cache_root
    res = run(small_cell(name), root)
    assert not res["correct"], res["checks"]


def test_traced_run_reads_each_metric_from_its_window(cache_root, tmp_path,
                                                      monkeypatch):
    """Per-layer metrics from the trace read the traced window; the others
    read the untraced window that comes first; both windows are checked."""
    seen = {}

    def reader(name):
        def read(run):
            seen[name] = run.trace is not None
            return 1.0
        return read
    monkeypatch.setattr(bench_run.spec, "metric_reader", reader)
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path))
    cell = small_cell("quickdraw_lstm.backlog")
    res = bench_run.run_cell(cell, SEED, 0.25, True, jax.devices()[:1],
                             time.perf_counter(), PEAKS,
                             cache_root=str(cache_root))
    assert res["correct"], res["checks"]
    assert seen == {m["name"]: m["source"] in bench_run.TRACED_SOURCES
                    for m in cell.per_layer}
    assert set(seen) == set(res["metrics"])
    assert res["device"]["window_s"] > 0 and "breakdown" in res
