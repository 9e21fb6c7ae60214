"""The traffic generator and the copied event generators: the same seed
gives the same events and due times; other seeds the same sizes and
gaps in another order."""

import numpy as np
import pytest

from bench import traffic
from bench.events import POOLS

MIX = {"arrivals": "poisson", "rate_per_s": 20000}
BIG = 2**31 + 12345


def test_seed_words_take_large_seeds():
    w = traffic.seed_words(BIG)
    assert w.dtype == np.uint32 and len(w) == 4
    assert np.array_equal(w, traffic.seed_words(BIG))
    assert not np.array_equal(w, traffic.seed_words(BIG + 1))


def test_same_seed_same_arrivals_and_order():
    w = traffic.seed_words(BIG)
    a = traffic.arrival_times(MIX, 2.0, int(w[3]))
    assert np.array_equal(a, traffic.arrival_times(MIX, 2.0, int(w[3])))
    assert np.array_equal(traffic.pool_order(4096, int(w[2])),
                          traffic.pool_order(4096, int(w[2])))


def test_other_seeds_share_the_gaps_in_another_order():
    a = traffic.arrival_times(MIX, 2.0, 1)
    b = traffic.arrival_times(MIX, 2.0, 2)
    assert len(a) == len(b) == 40000
    ga, gb = np.diff(a, prepend=0), np.diff(b, prepend=0)
    assert not np.array_equal(ga, gb)
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert a[-1] == pytest.approx(b[-1])
    # the gaps' mean is that of the exponential they are drawn from
    assert ga.mean() == pytest.approx(1 / 20000, rel=0.01)


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        traffic.arrival_times({"arrivals": "bursty", "rate_per_s": 1}, 1.0, 0)


@pytest.mark.parametrize("events, shape", [
    ("flavor_tagging", (64, 15, 6)), ("quickdraw", (64, 100, 3))])
def test_event_pools_repeat_with_the_seed(events, shape):
    x, y = POOLS[events](64, 99)
    assert x.shape == shape and np.isfinite(x).all()
    x2, y2 = POOLS[events](64, 99)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    assert not np.array_equal(x, POOLS[events](64, 100)[0])
