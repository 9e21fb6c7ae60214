"""The benchmark's NumPy reference against the program's XLA forward pass
at small sizes, and its lower-precision stand-in."""

import jax
import numpy as np
import pytest

from bench import reference, spec, weights


def _model(cell, hidden=16, seq_len=7, input_size=5):
    return {"cell": cell, "hidden": hidden, "seq_len": seq_len,
            "input_size": input_size, "dense_sizes": [12, 6],
            "n_outputs": 4, "output_activation": "softmax"}


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_reference_matches_the_program_forward(cell):
    from repro.models import rnn_tagger

    model = _model(cell)
    cfg = spec.family("rnn_tagger").model_config(
        {"name": "t", "model": model, "param_dtype": "float32",
         "compute_dtype": "float32"})
    params = weights.make_params(model, 5, jax.devices()[0])
    x = np.random.RandomState(1).randn(9, 7, 5).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(rnn_tagger.forward(cfg, params, x, impl="xla"))
    want = reference.probabilities(
        model, {k: np.asarray(v) for k, v in params.items()}, x, block=4)
    assert want.shape == (9, 4)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(want.sum(-1), 1.0, atol=1e-6)


def test_weights_repeat_with_the_seed_and_are_float32():
    model = _model("lstm")
    a = weights.make_params(model, 7, jax.devices()[0])
    b = weights.make_params(model, 7, jax.devices()[0])
    c = weights.make_params(model, 8, jax.devices()[0])
    assert set(a) == set(weights.shapes(model))
    for k in a:
        assert a[k].dtype == np.float32
        assert a[k].shape == weights.shapes(model)[k]
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["rnn/kernel"], c["rnn/kernel"])


def test_lower_precisions_depart_in_order():
    """One bfloat16 pass per product departs from float32 by far more than
    float32's own rounding, and by less than the answers' scale."""
    model = _model("lstm", hidden=64, seq_len=30, input_size=3)
    params = {k: np.asarray(v) for k, v in
              weights.make_params(model, 3, jax.devices()[0]).items()}
    x = np.random.RandomState(2).randn(64, 30, 3).astype(np.float32)
    f32 = reference.probabilities(model, params, x)
    default = np.abs(reference.probabilities(model, params, x, "default")
                     - f32).max()
    assert 1e-5 < default < 1e-1


def test_unknown_precision_is_refused():
    with pytest.raises(ValueError):
        reference.matmul("fp8")
