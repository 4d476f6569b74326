"""The numpy transcription of the documented quantile rule, by hand."""

import numpy as np
import pytest

from conftest import load

rule = load("reference", "tdigest_rule.py")


def test_unit_weights_are_the_hazen_percentile():
    rng = np.random.default_rng(3)
    x = rng.gamma(2.0, 10.0, (5, 16))
    got = rule.weighted_quantiles(x, np.ones_like(x), x.min(1), x.max(1),
                                  [0.5, 0.9, 0.99])
    want = np.percentile(x, [50, 90, 99], axis=1, method="hazen").T
    assert got == pytest.approx(want, rel=1e-12)


def test_weighted_hand_case():
    # points 10 (w 2), 20 (w 6), 40 (w 2): total 10
    # midpoints: 1, 5, 9.  q=0.5 -> tq=5 -> exactly the middle point, 20.
    # q=0.3 -> tq=3: between (1, 10) and (5, 20): 10 + 10 * (3-1)/4 = 15
    # q=0.8 -> tq=8: between (5, 20) and (9, 40): 20 + 20 * 3/4 = 35
    mean = np.array([[40.0, 10.0, 20.0, 0.0]])
    weight = np.array([[2.0, 2.0, 6.0, 0.0]])      # last cell is empty
    got = rule.weighted_quantiles(mean, weight, [10.0], [40.0],
                                  [0.3, 0.5, 0.8])
    assert got[0] == pytest.approx([15.0, 20.0, 35.0])


def test_clamped_to_min_max_and_single_point():
    # below the first midpoint the rule extrapolates no further than min
    got = rule.weighted_quantiles(np.array([[10.0, 20.0]]),
                                  np.array([[4.0, 4.0]]), [9.0], [25.0],
                                  [0.01, 0.999])
    assert got[0] == pytest.approx([10.0, 20.0])
    one = rule.weighted_quantiles(np.array([[7.0, 0.0]]),
                                  np.array([[3.0, 0.0]]), [7.0], [7.0], [0.5])
    assert one[0, 0] == 7.0
