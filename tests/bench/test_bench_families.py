"""Model families found by name: every configuration names a family module
that exposes the interface, a name that no module has (or none at all) is
refused, the tagger's family gives what the direct calls give, and a
family that the benchmark has never seen runs a whole cell from new files
alone."""

import inspect
import json
import os
import textwrap
import time

import jax
import numpy as np
import pytest

import bench.run as bench_run
from bench import check, drive, reference, spec, weights
from bench.events import POOLS

BENCH = spec.load_benchmark()
PEAKS = spec.load_json(f"{spec.ROOT}/bench/peaks.json")["TPU v5 lite"]
CONFIGS = {c["name"]: spec.load_json(os.path.join(spec.ROOT, c["file"]))
           for c in BENCH["configs"]}
SEED = 2**31 + 23


@pytest.mark.parametrize("name", list(CONFIGS))
def test_configuration_names_a_family_with_the_interface(name):
    config = CONFIGS[name]
    family = spec.family(config["family"])
    for key in spec.FAMILY:
        assert hasattr(family, key), key
    for key in ("model_config", "make_params", "make_inputs", "reference",
                "compare"):
        assert callable(getattr(family, key)), key
    assert family.DRIVERS and all(
        inspect.isclass(d) and issubclass(d, drive.Driver)
        for d in family.DRIVERS.values())
    assert set(family.TOLERANCE_CHECKS) <= set(config["limits"])
    assert family.model_config(config) is not None


def _root_with(tmp_path, config, families, mix=None):
    """A benchmark root under ``tmp_path`` holding the family modules
    (name -> source) and one cell, ``c.t``, of ``config`` under ``mix``;
    returns its ``BENCHMARK.json``."""
    for sub in ("configs", "traffic", "families"):
        os.makedirs(tmp_path / "bench" / sub)
    (tmp_path / "bench" / "configs" / "c.json").write_text(json.dumps(config))
    (tmp_path / "bench" / "traffic" / "t.json").write_text(json.dumps(
        mix or {"entry": "predict_one", "pool": 8, "warm_calls": 1}))
    for name, source in families.items():
        (tmp_path / "bench" / "families" / f"{name}.py").write_text(source)
    bench = {"configs": [{"name": "c", "file": "bench/configs/c.json"}],
             "workloads": [{"name": "c.t", "config": "c", "traffic": "t",
                            "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def test_unknown_family_is_refused_with_the_known_ones(tmp_path):
    bench = _root_with(tmp_path, dict(CONFIGS["flavor_lstm"],
                                      family="no_such_family"),
                       {"a_family": "", "b_family": ""})
    with pytest.raises(ValueError, match="no_such_family") as err:
        spec.resolve(bench, "c.t", str(tmp_path))
    assert "['a_family', 'b_family']" in str(err.value)
    with pytest.raises(ValueError, match="rnn_tagger"):
        spec.family("no_such_family")


def test_configuration_without_family_is_refused(tmp_path):
    config = dict(CONFIGS["flavor_lstm"])
    del config["family"]
    bench = _root_with(tmp_path, config, {"rnn_tagger": ""})
    with pytest.raises(ValueError, match="names no family"):
        spec.resolve(bench, "c.t", str(tmp_path))


def test_family_lacking_the_interface_is_refused(tmp_path):
    bench = _root_with(tmp_path, dict(CONFIGS["flavor_lstm"], family="half"),
                       {"half": "DRIVERS = {}\n"})
    with pytest.raises(ValueError, match="lacks") as err:
        spec.resolve(bench, "c.t", str(tmp_path))
    assert "'reference'" in str(err.value) and "'DRIVERS'" not in str(
        err.value)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_tagger_family_gives_what_the_direct_calls_give(name):
    """Weights, input pool and reference probabilities, bit for bit."""
    config = CONFIGS[name]
    family = spec.family(config["family"])
    words = [int(w) for w in np.random.RandomState(5).randint(
        0, 2**32, 2, dtype=np.uint64)]
    dev = jax.devices()[0]
    params = family.make_params(config, words[0], dev)
    direct = weights.make_params(config["model"], words[0], dev)
    assert set(params) == set(direct)
    for k in direct:
        assert params[k].dtype == direct[k].dtype
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(direct[k]))
    x = family.make_inputs(config, {"pool": 48}, words[1])
    want_x = POOLS[config["events"]](48, words[1])[0].astype(np.float32)
    assert x.dtype == want_x.dtype == np.float32
    np.testing.assert_array_equal(x, want_x)
    idx = np.array([3, 17, 3, 40, 0, 17])
    answered = drive.Record(idx=idx, answers=np.zeros((6, 1)), attempted=6,
                            failed=0, t_begin=0.0, t_end=1.0, calls=6)
    got = family.reference(config, params, answered, x)
    host = {k: np.asarray(v) for k, v in direct.items()}
    used = np.unique(idx)
    want = check.reference_for(idx, want_x, lambda xs:
                               reference.probabilities(config["model"],
                                                       host, xs))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got[used], reference.probabilities(config["model"], host, x[used]))
    assert np.isnan(got[np.setdiff1d(np.arange(48), used)]).all()
    checks = family.compare(answered, None, config["limits"])
    assert list(checks) == list(family.TOLERANCE_CHECKS)
    assert checks["prob_max_abs_err"] == {
        "value": None, "limit": config["limits"]["prob_max_abs_err"]}


# A second family, unknown to the benchmark: greedy tokens of a seeded
# bigram table.  Each generated token is one event, timed from the
# previous token of its request (or from the request's issue), and
# answered by the pair (previous token, token).
TOY = textwrap.dedent('''
    """Greedy generation from a seeded bigram table."""

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.drive import Driver, Record

    TOLERANCE_CHECKS = ("token_mismatches",)


    def model_config(config):
        return config["model"]


    def make_params(config, word, device):
        v = config["model"]["vocab"]
        with jax.default_device(device):
            return jax.jit(lambda k: jax.random.normal(k, (v, v)))(
                jax.random.key(np.uint32(word)))


    def make_inputs(config, mix, word):
        return np.random.default_rng(word).integers(
            0, config["model"]["vocab"], mix["pool"])


    @jax.jit
    def next_token(table, tok):
        return jnp.argmax(table[tok])


    class Generate(Driver):
        def engines(self):
            return []

        def warm(self):
            next_token(self.params, 0).block_until_ready()

        def window(self, seconds, span):
            idx, answers, lat = [], [], []
            clock = time.perf_counter
            t_begin = clock()
            k, t1 = 0, t_begin
            while t1 < t_begin + seconds:
                i = self.order[k % len(self.x)]
                tok, t0 = int(self.x[i]), clock()
                for _ in range(self.mix["tokens"]):
                    prev, tok = tok, int(next_token(self.params, tok))
                    t1 = clock()
                    idx.append(i)
                    answers.append((prev, tok))
                    lat.append(t1 - t0)
                    t0 = t1
                k += 1
            return Record(idx=np.asarray(idx), answers=np.asarray(answers),
                          attempted=len(idx), failed=0, t_begin=t_begin,
                          t_end=t1, calls=len(idx), latency_s=np.asarray(lat))


    DRIVERS = {"generate": Generate}


    def reference(config, params, answered, inputs):
        return np.argmax(np.asarray(params), axis=1)


    def compare(answered, expected, limits):
        prev, tok = answered.answers[:, 0], answered.answers[:, 1]
        return {"token_mismatches": {
            "value": int((expected[prev] != tok).sum()),
            "limit": limits["token_mismatches"]}}
''')


@pytest.fixture
def toy_cell(tmp_path):
    """A benchmark root under ``tmp_path`` with one cell of the toy family;
    nothing under the repository's ``bench/`` knows it."""
    bench = _root_with(
        tmp_path, {"name": "toy", "family": "toy", "model": {"vocab": 64},
                   "matmul_precision": "highest",
                   "limits": {"token_mismatches": 0}},
        {"toy": TOY}, {"entry": "generate", "pool": 32, "tokens": 4})
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    bench["end_to_end"] = [dict(e2e[n], workloads=["c.t"]) for n in (
        "events_per_s", "event_latency_p50_us")] + [e2e["setup_s"]]
    assert "toy" not in spec.known_families()
    return spec.resolve(bench, "c.t", str(tmp_path))


def _run(cell, cache_root):
    return bench_run.run_cell(cell, SEED, 0.25, False, jax.devices()[:1],
                              time.perf_counter(), PEAKS,
                              cache_root=str(cache_root))


def test_a_new_family_runs_a_cell_from_new_files_alone(toy_cell, tmp_path):
    res = _run(toy_cell, tmp_path / "engines")
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["token_mismatches", "missing_answers",
                                   "executables_without_kernel",
                                   "compiles_in_window"]
    assert res["checks"]["token_mismatches"]["value"] == 0
    assert res["attempted"] > 0 and res["attempted"] % 4 == 0
    assert set(res["metrics"]) == {"events_per_s", "event_latency_p50_us",
                                   "setup_s"}
    assert list(res)[-1] == "checks"


def test_a_new_family_with_a_token_altered_is_not_correct(
        toy_cell, tmp_path, monkeypatch):
    served = toy_cell.family.next_token
    calls = iter(range(10**9))

    def altered(table, tok):
        out = served(table, tok)
        return (out + 1) % table.shape[0] if next(calls) % 5 == 2 else out
    monkeypatch.setattr(toy_cell.family, "next_token", altered)
    res = _run(toy_cell, tmp_path / "engines")
    assert not res["correct"], res["checks"]
    assert res["checks"]["token_mismatches"]["value"] > 0
