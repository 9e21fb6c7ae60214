"""Plain NumPy reference of the paper's RNN taggers, independent of the
program under test.

An LSTM or GRU layer (Keras layouts: LSTM gates i|f|c|o; GRU reset_after
with gates z|r|h and bias [input; recurrent]) returns its final hidden
state, then a ReLU dense stack and a softmax head give class
probabilities.  Everything is float32 and computed on the host.

The configurations state float32, which the benchmark runs at the
``highest`` matmul precision.  ``"default"`` stands in for the program
computed one step below it: one bfloat16 pass, as a TPU takes a float32
product at ``Precision.DEFAULT`` (operands rounded to bfloat16, float32
accumulation); it changes only the matrix products and keeps everything
else float32.
"""

from __future__ import annotations

from typing import Callable, Dict

import ml_dtypes
import numpy as np

PRECISIONS = ("float32", "default")


def _bf16(a: np.ndarray) -> np.ndarray:
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def _matmul_bf16x1(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return _bf16(a) @ _bf16(b)


def matmul(precision: str) -> Callable[[np.ndarray, np.ndarray], np.ndarray]:
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return {"float32": np.matmul, "default": _matmul_bf16x1}[precision]


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return np.float32(1.0) / (np.float32(1.0) + np.exp(-x))


def rnn_final_state(cell: str, x: np.ndarray, W: np.ndarray, U: np.ndarray,
                    b: np.ndarray, mm=np.matmul) -> np.ndarray:
    """[B, T, in] -> final hidden state [B, H]."""
    x, W, U, b = (np.asarray(a, np.float32) for a in (x, W, U, b))
    B, T, _ = x.shape
    H = U.shape[0]
    h = np.zeros((B, H), np.float32)
    c = np.zeros((B, H), np.float32)
    for t in range(T):
        if cell == "lstm":
            z = mm(x[:, t], W) + mm(h, U) + b
            i, f, g, o = np.split(z, 4, axis=-1)
            c = _sigmoid(f) * c + _sigmoid(i) * np.tanh(g)
            h = _sigmoid(o) * np.tanh(c)
        elif cell == "gru":
            zx = mm(x[:, t], W) + b[0]
            zh = mm(h, U) + b[1]
            xz, xr, xh = np.split(zx, 3, axis=-1)
            hz, hr, hh = np.split(zh, 3, axis=-1)
            z = _sigmoid(xz + hz)
            r = _sigmoid(xr + hr)
            h = z * h + (1 - z) * np.tanh(xh + r * hh)
        else:
            raise ValueError(f"cell {cell!r} is neither lstm nor gru")
    return h


def head(params: Dict[str, np.ndarray], h: np.ndarray, n_dense: int,
         mm=np.matmul) -> np.ndarray:
    """ReLU dense stack, then softmax probabilities."""
    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    h = np.asarray(h, np.float32)
    for i in range(n_dense):
        h = np.maximum(mm(h, p[f"dense{i}/w"]) + p[f"dense{i}/b"], 0)
    logits = mm(h, p["head/w"]) + p["head/b"]
    e = np.exp(logits - logits.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def probabilities(model: Dict, params: Dict[str, np.ndarray], x: np.ndarray,
                  precision: str = "float32",
                  block: int = 2048) -> np.ndarray:
    """Class probabilities [B, n_outputs] for the events ``x``, computed
    ``block`` events at a time so that the working set stays small."""
    mm = matmul(precision)
    out = []
    for s in range(0, len(x), block):
        h = rnn_final_state(model["cell"], x[s:s + block],
                            params["rnn/kernel"], params["rnn/recurrent"],
                            params["rnn/bias"], mm)
        out.append(head(params, h, len(model["dense_sizes"]), mm))
    return np.concatenate(out)
