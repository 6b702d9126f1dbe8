"""Every seed offers the same work, in another order."""
import numpy as np
import pytest

from bench import stats, traffic


@pytest.mark.parametrize("rate,seconds", [(12.5, 20.0), (30.0, 20.0)])
def test_arrival_seeds_order_the_same_arrivals(rate, seconds):
    a = traffic.poisson_dues(rate, seconds, 1)
    b = traffic.poisson_dues(rate, seconds, 7)
    assert np.array_equal(a, traffic.poisson_dues(rate, seconds, 1))
    assert len(a) == len(b) == round(rate * seconds)
    assert np.all((a >= 0) & (a < seconds)) and np.all(np.diff(a) > 0)
    n = len(a)
    base = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    base *= seconds / (base.sum() + base.max())
    for d in (np.diff(a), np.diff(b)):     # n - 1 gaps of one set of n
        assert np.isclose(d[:, None], base[None], rtol=1e-9).any(1).all()
    assert not np.allclose(a, b)
    assert np.mean(np.diff(a)) == pytest.approx(1 / rate, rel=0.05)


def test_prompts_are_drawn_from_seed_and_index():
    p = traffic.prompt(2147483901, 5, 512, 64000)
    assert p.dtype == np.int32 and p.shape == (512,)
    assert np.array_equal(p, traffic.prompt(2147483901, 5, 512, 64000))
    assert not np.array_equal(p, traffic.prompt(2147483901, 6, 512, 64000))
    assert not np.array_equal(p, traffic.prompt(2147483902, 5, 512, 64000))


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == pytest.approx(np.percentile(xs, 50))
    assert stats.percentile(xs, 95) == pytest.approx(np.percentile(xs, 95))
    assert stats.percentile([], 50) is None
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) > 0
