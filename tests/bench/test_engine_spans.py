"""The serving engine's spans on the CPU: under a profiler session
``predict_one``, ``predict`` and ``flush`` leave their ``engine.*`` spans,
nested and in order, where ``bench.program_spans.read`` finds them, and the
benchmark's own reading of the trace does not see them; with no session no
``TraceAnnotation`` is built; answers are the same either way."""

import types


import jax
import numpy as np
import pytest

from bench import check, spec, weights
from bench import program_spans as ps
from bench import trace as tr
from repro.kernels.schedule import KernelSchedule
from repro.serving import RNNServingEngine, spans

CONFIG = spec.load_json(f"{spec.ROOT}/bench/configs/flavor_lstm.json")
ROWS = 4


def make_engine():
    params = weights.make_params(CONFIG["model"], 7, jax.devices()[0])
    return RNNServingEngine(
        spec.family(CONFIG["family"]).model_config(CONFIG), params,
        impl="xla", max_batch=ROWS,
        schedule=KernelSchedule(**dict(CONFIG["schedule"], backend="xla")))


def serve(eng, x):
    """One of each traced entry point: the batch-1 call, a direct batch,
    and a flush of a partly filled queue (padded to ``max_batch``)."""
    one = eng.predict_one(x[0])
    batch = eng.predict(x[:ROWS])
    reqs = [eng.submit(r) for r in x[:ROWS - 1]]
    eng.flush(force=True)
    return [one, batch, np.stack([r.result for r in reqs])]


@pytest.fixture(scope="module")
def x():
    return np.random.RandomState(3).randn(ROWS, 15, 6).astype(np.float32)


@pytest.fixture(scope="module")
def traced(x, tmp_path_factory):
    """Answers served untraced, then the same served under a profiler
    session, with a batch shape not yet compiled at the end."""
    eng = make_engine()
    untraced = serve(eng, x)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(log_dir):
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            answers = serve(eng, x)
            eng.predict(x[:2])                 # compiles inside the window
    return untraced, answers, tr.read(log_dir), ps.read(log_dir)


def children(program, root):
    """Names of the spans inside each ``root`` span, in order."""
    out = []
    for s, e, n in program:
        if n == root:
            out.append([m for a, b, m in program
                        if s <= a and b <= e and (a, b, m) != (s, e, n)])
    return out


def test_spans_nest_in_order(traced):
    program = traced[3]
    roots = [n for *_, n in program
             if n in ("engine.predict_one", "engine.predict", "engine.flush")]
    assert roots == ["engine.predict_one", "engine.predict", "engine.flush",
                     "engine.predict"]
    stages = ["engine.put", "engine.dispatch", "engine.fetch"]
    assert children(program, "engine.predict_one") == [stages]
    assert children(program, "engine.predict")[0] == stages
    assert children(program, "engine.flush") == [["engine.pad"] + stages]
    # a compile inside the window shows inside the dispatch that waited
    assert children(program, "engine.predict")[1] == [
        "engine.put", "engine.dispatch", "compile.acquire", "engine.fetch"]
    assert children(program, "engine.dispatch")[-1] == ["compile.acquire"]


def test_stages_cover_the_batch_1_call(traced):
    trace, program = traced[2:]
    (call,) = ps.calls(program, trace.ops.get(0, []), trace.window)
    assert set(call.stages) == {"engine.put", "engine.dispatch",
                                "engine.fetch"}
    assert call.self_s > 0
    assert call.self_s + sum(call.stages.values()) \
        == pytest.approx(call.end - call.start)
    assert call.first_op_start is None         # no TPU plane on the CPU


def test_answers_are_the_same_traced_or_not(traced):
    untraced, answers, *_ = traced
    for a, b in zip(untraced, answers):
        np.testing.assert_array_equal(a, b)


def test_benchmark_reading_leaves_program_spans_out(traced):
    trace, program = traced[2:]
    assert {n for *_, n in trace.spans} == {tr.WINDOW_SPAN}
    assert program and all(n.startswith(ps.PREFIXES) for *_, n in program)
    run = types.SimpleNamespace(trace=trace, device_ids=[0])
    assert dict(check.breakdown(run)["idle_gaps"]).keys() \
        <= {tr.OUTSIDE_SPANS}


def test_no_annotation_is_built_without_a_session(x, monkeypatch, tmp_path):
    real = spans.TraceAnnotation
    built = []

    class Counting:
        is_enabled = staticmethod(real.is_enabled)

        def __new__(cls, name, **kw):
            built.append(name)
            return real(name, **kw)
    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    eng = make_engine()
    serve(eng, x)
    assert built == []
    with jax.profiler.trace(str(tmp_path)):
        eng.predict_one(x[0])
    assert built == ["engine.predict_one", "engine.put", "engine.dispatch",
                     "engine.fetch"]
