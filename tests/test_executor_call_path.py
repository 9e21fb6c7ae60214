"""The engines' call into their executables.

  * ``CachedExecutor`` finds a compiled executable by a signature of
    objects (the pytree structure and each leaf's shape and dtype), with no
    string formatting on a hit; the string form is built on a miss only,
    and names the same persistent entry as it always has;
  * ``KeyCompileStats.hot`` counts the calls served from memory;
  * ``RNNServingEngine`` hands a host event to the executable as NumPy (the
    executable's call copies it to the device), passes a ``jax.Array`` on
    its device as it is, and moves any other: answers and executable counts
    are the same as when every input went through ``jax.device_put``.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.schedule import schedule_key
from repro.models import rnn_tagger
from repro.models.init import init_params
from repro.registry import get_config
from repro.serving import CachedExecutor, CompileCache, RNNServingEngine
from repro.serving import compile_cache as cc

ROWS = 4


def string_signature(args):
    """The signature's string form as the persistent cache has always
    keyed it: pinned here, so that entry names and hashes never move."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (str(treedef),
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves))


def executor(cache=None, name="unit"):
    def f(params, x):
        return x @ params["w"] + params["b"]

    return CachedExecutor(jax.jit(f), cache or CompileCache(None), "k",
                          {"kind": name})


def unit_params(dtype=np.float32):
    return {"w": jnp.ones((3, 2), dtype), "b": jnp.zeros((2,), dtype)}


def count_acquires(ex, monkeypatch):
    seen = []
    real = ex._acquire

    def spy(sig, args):
        seen.append(sig)
        return real(sig, args)
    monkeypatch.setattr(ex, "_acquire", spy)
    return seen


# ---------------------------------------------------------------------------
# The lookup
# ---------------------------------------------------------------------------


def test_repeat_signature_is_served_hot(monkeypatch):
    ex = executor()
    p, x = unit_params(), np.ones((2, 3), np.float32)
    first = ex(p, x)
    acquires = count_acquires(ex, monkeypatch)
    for _ in range(3):
        np.testing.assert_array_equal(ex(p, x), first)
    assert acquires == []
    row = ex._cache.report_row("k")
    assert (row["cold"], row["warm"], row["hot"]) == (1.0, 0.0, 3.0)
    assert row["hot_share"] == 0.75


@pytest.mark.parametrize("shape,dtype", [((5, 3), np.float32),
                                         ((2, 3), np.int32)])
def test_new_batch_shape_or_dtype_acquires(monkeypatch, shape, dtype):
    ex = executor()
    p = unit_params()
    ex(p, np.ones((2, 3), np.float32))
    acquires = count_acquires(ex, monkeypatch)
    y = ex(p, np.ones(shape, dtype))
    assert len(acquires) == 1 and ex.compiled_signatures() == 2
    assert y.shape == (shape[0], 2)
    ex(p, np.ones(shape, dtype))
    assert len(acquires) == 1
    assert ex._cache.stats("k").hot == 1


def test_shape_dtype_struct_warm_then_host_call_compiles_nothing(monkeypatch):
    ex = executor()
    p = unit_params()
    assert ex.warm(p, jax.ShapeDtypeStruct((2, 3), jnp.float32))["status"] \
        == "cold"
    acquires = count_acquires(ex, monkeypatch)
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    np.testing.assert_array_equal(ex(p, x), x @ np.ones((3, 2)))
    ex(p, jnp.asarray(x))
    assert acquires == [] and ex.compiled_signatures() == 1
    st = ex._cache.stats("k")
    assert (st.cold, st.warm, st.hot) == (1, 0, 2)
    assert ex.warm(p, x)["status"] == "hot"
    assert st.hot == 2                   # warm() finds it, serves no call


@pytest.mark.parametrize("other", [
    {"w": jnp.ones((3, 2)), "c": jnp.zeros((2,))},        # another key
    {"w": jnp.ones((3, 2)), "b": (jnp.zeros((2,)),)},     # another nesting
])
def test_params_of_another_structure_never_reuse(monkeypatch, other):
    def f(params, x):
        return [x @ leaf if leaf.ndim == 2 else leaf
                for leaf in jax.tree_util.tree_leaves(params)]

    ex = CachedExecutor(jax.jit(f), CompileCache(None), "k", {"kind": "t"})
    x = np.ones((2, 3), np.float32)
    ex(unit_params(), x)
    acquires = count_acquires(ex, monkeypatch)
    ex(other, x)
    assert len(acquires) == 1 and ex.compiled_signatures() == 2
    # same leaf shapes and dtypes, so only the structure tells them apart
    assert cc._arg_signature((other, x))[1] \
        == cc._arg_signature((unit_params(), x))[1]


@pytest.mark.parametrize("args", [
    (unit_params(), np.ones((2, 3), np.float32)),
    (unit_params(), jnp.ones((2, 3), jnp.float32)),
    (unit_params(), jax.ShapeDtypeStruct((2, 3), jnp.float32)),
    (unit_params(jnp.bfloat16), np.ones((1, 3), jnp.bfloat16),
     np.zeros((), np.int32)),
    ([np.ones((4,), np.int8)], None, {"a": np.ones((2, 1), np.float16)}),
], ids=["numpy", "device", "aval", "bf16-scalar", "nested"])
def test_string_meta_is_pinned(args):
    sig = cc._arg_signature(args)
    treedef, leaves = string_signature(args)
    assert cc._signature_meta(sig) == {"treedef": treedef, "leaves": leaves}


@pytest.mark.parametrize("kind", ["numpy", "device", "aval"])
def test_persistent_entry_path_and_meta_are_unchanged(tmp_path, kind):
    cache = CompileCache(tmp_path)
    ex = executor(cache, name="pin")
    p = unit_params()
    x = {"numpy": np.ones((2, 3), np.float32),
         "device": jnp.ones((2, 3), jnp.float32),
         "aval": jax.ShapeDtypeStruct((2, 3), jnp.float32)}[kind]
    if kind == "aval":
        ex.warm(p, x)
    else:
        ex(p, x)
    treedef, leaves = string_signature((p, x))
    meta = {"kind": "pin", "treedef": treedef, "leaves": leaves}
    path = cache.entry_path("k", meta)
    assert [f.name for f in tmp_path.iterdir()] == [path.name]
    with open(path, "rb") as f:
        assert pickle.load(f)["meta"] == cache.entry_meta(meta)
    # a fresh executor over the directory loads it: warm, then hot
    again = executor(CompileCache(tmp_path), name="pin")
    again(p, np.ones((2, 3), np.float32))
    again(p, np.ones((2, 3), np.float32))
    st = again._cache.stats("k")
    assert (st.cold, st.warm, st.hot) == (0, 1, 1)


# ---------------------------------------------------------------------------
# The engine's inputs
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tagger():
    cfg = get_config("flavor-tagging-lstm")
    return cfg, init_params(jax.random.PRNGKey(0),
                            rnn_tagger.param_specs(cfg))


def make_engine(tagger, **kw):
    cfg, params = tagger
    kw.setdefault("device", jax.devices()[0])
    return RNNServingEngine(cfg, params, impl="xla", max_batch=ROWS, **kw)


def device_put_engine(tagger, monkeypatch):
    """An engine whose every input goes through ``jax.device_put``, as the
    engine's inputs all did before they were handed over as NumPy."""
    eng = make_engine(tagger)
    monkeypatch.setattr(eng, "_put", lambda x: jax.device_put(
        x if isinstance(x, jax.Array) else np.asarray(x), eng.device))
    return eng


def serve(eng, x):
    """predict_one, a direct predict, and a padded flush of a part-filled
    queue, twice each."""
    out = []
    for _ in range(2):
        out.append(np.stack([eng.predict_one(r) for r in x]))
        out.append(eng.predict(x[:3]))
        reqs = [eng.submit(r) for r in x[:ROWS - 1]]
        eng.flush(force=True)
        out.append(np.stack([r.result for r in reqs]))
    return out


@pytest.fixture()
def events(tagger):
    r = tagger[0].rnn
    return np.random.RandomState(5).randn(
        ROWS, r.seq_len, r.input_size).astype(np.float32)


def test_answers_bit_identical_to_device_put_path(tagger, events,
                                                  monkeypatch):
    got = serve(make_engine(tagger), events)
    want = serve(device_put_engine(tagger, monkeypatch), events)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_executable_count_as_on_device_put_path(tagger, events, monkeypatch):
    eng, ref = make_engine(tagger), device_put_engine(tagger, monkeypatch)
    serve(eng, events)
    serve(ref, events)
    count = {k: len(v) for k, v in eng.executables().items()}
    assert count == {k: len(v) for k, v in ref.executables().items()}
    assert sum(count.values()) == 3       # batch 1, batch 3, padded batch 4


def test_host_event_reaches_the_executable_as_numpy(tagger, events,
                                                    monkeypatch):
    eng = make_engine(tagger)
    eng.predict_one(events[0])
    (fn,) = eng._one_cache.values()
    (compiled,) = fn._compiled.values()
    seen = []

    def spy(*args):
        seen.append(type(args[1]))
        return compiled(*args)
    monkeypatch.setattr(fn, "_compiled", {k: spy for k in fn._compiled})
    monkeypatch.setattr(jax, "device_put", None)     # never called
    eng.predict_one(events[1])
    assert seen == [np.ndarray]


def test_device_array_on_engine_device_passes_without_copy(tagger, events,
                                                           monkeypatch):
    eng = make_engine(tagger)
    want = eng.predict_one(events[0])
    xd = jax.device_put(events[0][None], eng.device)
    assert eng._put(xd) is xd
    event = jax.device_put(events[0], eng.device)
    monkeypatch.setattr(jax, "device_put", None)     # never called
    np.testing.assert_array_equal(eng.predict_one(event), want)
    assert eng.compile_cache.stats(schedule_key(*eng.resolve())).cold == 1


def test_other_device_arrays_are_moved(tagger, events, monkeypatch):
    moved = []

    def spy(x, device=None):
        moved.append(device)
        return x
    eng = make_engine(tagger)
    elsewhere = make_engine(tagger, device=None)
    xd = jax.device_put(events[0][None], jax.devices()[0])
    monkeypatch.setattr(jax, "device_put", spy)
    assert eng._put(xd) is xd and moved == []
    elsewhere._put(xd)                    # no device of its own: as before
    assert moved == [None]
    other = object()                      # a device that xd is not on
    monkeypatch.setattr(eng, "device", other)
    eng._put(xd)
    assert moved == [None, other]
    eng._put(events[0])                   # host arrays never move here
    assert len(moved) == 2


def test_host_input_is_contiguous_in_its_canonical_dtype(tagger, events):
    eng = make_engine(tagger)
    strided = np.asarray(events[:, ::-1], np.float64)
    x = eng._put(strided)
    assert type(x) is np.ndarray and x.flags.c_contiguous
    assert x.dtype == np.float32
    np.testing.assert_array_equal(x, strided.astype(np.float32))
    assert eng._put(events) is events
    # a float64 event lands on the float32 executable: no second compile
    eng.predict_one(events[0])
    one = eng.predict_one(events[0].astype(np.float64).tolist())
    np.testing.assert_array_equal(one, eng.predict_one(events[0]))
    assert sum(len(v) for v in eng.executables().values()) == 1


def test_predict_one_matches_batched_predict(tagger, events):
    eng = make_engine(tagger)
    for r in events:
        np.testing.assert_array_equal(eng.predict_one(r),
                                      eng.predict(r[None])[0])


def test_serve_report_shows_hot_share(tagger, events):
    eng = make_engine(tagger)
    for _ in range(4):
        eng.predict_one(events[0])
    key = schedule_key(*eng.resolve())
    row = eng.serve_report()[key]["compile"]
    assert (row["cold"], row["hot"]) == (1.0, 3.0)
    assert row["hot_share"] == 0.75
