"""Operations and bytes per event, against counts worked out by hand."""

import pytest

from bench import weights, work
from bench.spec import ROOT, load_json

FLAVOR = load_json(f"{ROOT}/bench/configs/flavor_lstm.json")["model"]
QUICKDRAW = load_json(f"{ROOT}/bench/configs/quickdraw_lstm.json")["model"]


@pytest.mark.parametrize("model, rnn, head", [
    # 2 * T * (in + H) * 4H; head 2 * (120*50 + 50*10 + 10*3)
    (FLAVOR, 2 * 15 * 126 * 480, 2 * (6000 + 500 + 30)),
    # the 13,414,400 + 132,352 of the QuickDraw tagger
    (QUICKDRAW, 13_414_400, 132_352),
])
def test_flops_per_event(model, rnn, head):
    assert work.rnn_flops_per_event(model) == rnn
    assert work.head_flops_per_event(model) == head
    assert work.model_flops_per_event(model) == rnn + head


def test_gru_flops_use_three_gates():
    gru = dict(FLAVOR, cell="gru")
    assert work.rnn_flops_per_event(gru) == 2 * 15 * 126 * 360


@pytest.mark.parametrize("model, weight_bytes, per_event", [
    # (6 + 120) * 480 weights + 480 biases; 15 * 6 inputs + 120 outputs
    (FLAVOR, 4 * (126 * 480 + 480), 4 * (90 + 120)),
    (QUICKDRAW, 4 * (131 * 512 + 512), 4 * (300 + 128)),
])
def test_rnn_bytes(model, weight_bytes, per_event):
    assert work.rnn_weight_bytes(model) == weight_bytes
    assert work.rnn_bytes(model, events=1024, calls=3) == \
        1024 * per_event + 3 * weight_bytes


@pytest.mark.parametrize("model, n_params", [
    (FLAVOR, 67_553),        # paper Table 1
    (QUICKDRAW, 134_149),
])
def test_parameter_counts_match_the_paper(model, n_params):
    total = 0
    for shape in weights.shapes(model).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert total == n_params
