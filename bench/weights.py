"""Seeded tagger weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference gets nothing
that the program made.  Layouts and names are the ones the serving engine
takes (Keras: ``rnn/kernel`` [in, G*H], ``rnn/recurrent`` [H, G*H],
``rnn/bias`` [G*H] for an LSTM and [2, G*H] for a GRU; ``dense<i>/w|b``;
``head/w|b``).  Kernels are Glorot-uniform and the recurrent kernel
orthogonal, as Keras initialises them; biases get Gaussian noise (sd 0.1,
plus Keras's unit forget bias on the LSTM) so that the bias paths are
exercised as well.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GATES = {"lstm": 4, "gru": 3}


def shapes(model: Dict) -> Dict[str, Tuple[int, ...]]:
    """Every parameter's shape, from the configuration's sizes."""
    g, h, fin = GATES[model["cell"]], model["hidden"], model["input_size"]
    out = {"rnn/kernel": (fin, g * h), "rnn/recurrent": (h, g * h),
           "rnn/bias": (g * h,) if model["cell"] == "lstm" else (2, g * h)}
    prev = h
    for i, width in enumerate(model["dense_sizes"]):
        out[f"dense{i}/w"] = (prev, width)
        out[f"dense{i}/b"] = (width,)
        prev = width
    out["head/w"] = (prev, model["n_outputs"])
    out["head/b"] = (model["n_outputs"],)
    return out


@partial(jax.jit, static_argnames=("cell", "spec"))
def _init(key: jax.Array, *, cell: str, spec: Tuple) -> Dict[str, jax.Array]:
    glorot = jax.nn.initializers.glorot_uniform()
    ortho = jax.nn.initializers.orthogonal()
    out = {}
    for k, (name, shape) in zip(jax.random.split(key, len(spec)), spec):
        if name == "rnn/recurrent":
            out[name] = ortho(k, shape, jnp.float32)
        elif len(shape) == 2 and name != "rnn/bias":
            out[name] = glorot(k, shape, jnp.float32)
        else:
            b = 0.1 * jax.random.normal(k, shape, jnp.float32)
            if name == "rnn/bias" and cell == "lstm":
                h = shape[0] // 4
                b = b.at[h:2 * h].add(1.0)
            out[name] = b
    return out


def make_params(model: Dict, key_word: int,
                device: jax.Device) -> Dict[str, jax.Array]:
    """The tagger's float32 weights on ``device``, from one 32-bit word of
    the run's seed."""
    spec = tuple(sorted(shapes(model).items()))
    # the same weights whatever matmul precision the run computes at
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        key = jax.random.key(np.uint32(key_word))
        return _init(key, cell=model["cell"], spec=spec)
