"""Operations and bytes of a tagger's work, counted from its shapes.

Counts are of the model's work for the events answered, not of what an
implementation runs: padding rows, repeated passes of a higher matmul
precision and recomputation do not count.  A multiply-add is two
operations.
"""

from __future__ import annotations

from typing import Dict

GATES = {"lstm": 4, "gru": 3}
F32_BYTES = 4


def rnn_flops_per_event(model: Dict) -> int:
    """The recurrent layer: T steps of [1, in+H] @ [in+H, G*H]."""
    g = GATES[model["cell"]]
    h, fin, t = model["hidden"], model["input_size"], model["seq_len"]
    return 2 * t * (fin + h) * g * h


def head_flops_per_event(model: Dict) -> int:
    """The dense stack and the output layer."""
    prev, total = model["hidden"], 0
    for width in list(model["dense_sizes"]) + [model["n_outputs"]]:
        total += 2 * prev * width
        prev = width
    return total


def model_flops_per_event(model: Dict) -> int:
    return rnn_flops_per_event(model) + head_flops_per_event(model)


def rnn_weight_bytes(model: Dict) -> int:
    g = GATES[model["cell"]]
    h, fin = model["hidden"], model["input_size"]
    n_bias = g * h if model["cell"] == "lstm" else 2 * g * h
    return F32_BYTES * ((fin + h) * g * h + n_bias)


def rnn_bytes(model: Dict, events: int, calls: int) -> int:
    """Least HBM traffic of the recurrent layer: each event's inputs read
    and final state written once, and the weights read once per call."""
    per_event = F32_BYTES * (model["seq_len"] * model["input_size"]
                             + model["hidden"])
    return events * per_event + calls * rnn_weight_bytes(model)
