"""The float64 compress the hot keys are compared against
(reference/tdigest_compress.py), held to hand-worked cases."""

import numpy as np

from conftest import load

tc = load("reference", "tdigest_compress.py")
rule = load("reference", "tdigest_rule.py")

DELTA = 100.0
CAP = tc.centroid_capacity(DELTA)


def test_capacity_is_the_programs():
    # floor(1.5 x 100) + 1 = 151 clusters, rounded up to a multiple of 8
    assert CAP == 152
    assert tc.centroid_capacity(50.0) == 80


def test_scale_function_by_hand():
    # k(0) = 0, k(1/2) = 0.75 delta, k(1) = 1.5 delta; asin(1/2) = pi/6
    assert tc.scale_k(0.0, DELTA) == 0.0
    assert tc.scale_k(0.5, DELTA) == 75.0
    assert tc.scale_k(1.0, DELTA) == 150.0
    assert abs(tc.scale_k(0.75, DELTA) - 150.0 * (1 / 6 + 0.5)) < 1e-12
    # the cluster holding q is 1 / k'(q) wide: 0.0105 / 0.0063 / 0.0021
    got = [round(tc.cluster_width(q, DELTA), 4) for q in (0.5, 0.9, 0.99)]
    assert got == [0.0105, 0.0063, 0.0021]


def test_singletons_under_the_cap_come_back_unchanged():
    """40 unit-weight points: consecutive left edges are 1/40 apart in q,
    at least 150 x (2 / pi) / 40 = 2.4 apart in k, so no two share a
    cluster; the row comes back sorted, bit for bit."""
    vals = np.random.default_rng(1).gamma(2.0, 10.0, 40)
    m, w = tc.compress(vals, np.ones(40), DELTA, CAP)
    np.testing.assert_array_equal(m, np.sort(vals))
    np.testing.assert_array_equal(w, np.ones(40))
    # empty slots (weight 0) are not points
    m2, w2 = tc.compress(np.concatenate([vals, [1e9, -1e9]]),
                         np.concatenate([np.ones(40), [0, 0]]), DELTA, CAP)
    np.testing.assert_array_equal(m2, m)
    np.testing.assert_array_equal(w2, w)


def test_four_points_by_hand():
    """delta = 1: k(q) = 1.5 (asin(2q - 1) / pi + 1/2), clusters 0 and 1
    (capacity 8).  Four unit points have left edges q = 0, 1/4, 1/2, 3/4:
    k = 0, 0.5, 0.75, 1.0 -> clusters 0, 0, 0, 1."""
    cap = tc.centroid_capacity(1.0)
    assert cap == 8
    m, w = tc.compress([4.0, 1.0, 3.0, 2.0], np.ones(4), 1.0, cap)
    np.testing.assert_array_equal(w, [3.0, 1.0])
    np.testing.assert_array_equal(m, [2.0, 4.0])
    # weights move the edges: (1, w=2), (2, w=1), (3, w=1) -> q = 0, 1/2,
    # 3/4 -> clusters 0, 0, 1
    m, w = tc.compress([3.0, 1.0, 2.0], [1.0, 2.0, 1.0], 1.0, cap)
    np.testing.assert_array_equal(w, [3.0, 1.0])
    np.testing.assert_array_equal(m, [4.0 / 3.0, 3.0])


def test_weights_are_conserved_to_the_last_unit():
    rng = np.random.default_rng(2)
    for n in (1, 513, 4096, 16_700):
        vals = np.round(rng.gamma(2.0, 10.0, n), 3)
        wts = rng.integers(1, 200, n).astype(np.float64)
        m, w = tc.compress(vals, wts, DELTA, CAP)
        assert w.sum() == wts.sum()                # integers: exact
        assert len(m) <= CAP - 1 and (np.diff(m) >= 0).all()
        assert abs((m * w).sum() - (vals * wts).sum()) \
            <= 1e-12 * (vals * wts).sum()
        assert m[0] >= vals.min() and m[-1] <= vals.max()


def test_rank_of_reads_the_hazen_percentile_as_q():
    s = np.sort(np.round(np.random.default_rng(3).gamma(2.0, 10.0, 600), 3))
    for q in (0.5, 0.9, 0.99):
        x = np.percentile(s, q * 100, method="hazen")
        assert tc.rank_error(s, x, q) < 1e-12
    # and a value a whole cluster away reads about that far off
    x = np.percentile(s, 51.05, method="hazen")
    assert abs(tc.rank_error(s, x, 0.5) - 0.0105) < 1e-3


def test_one_stage_answer_sits_inside_its_cluster():
    rng = np.random.default_rng(4)
    for n in (513, 3000, 16_700):
        s = np.sort(np.round(rng.gamma(2.0, 10.0, n), 3))
        got = tc.one_stage_quantiles(s, [0.5, 0.9, 0.99], DELTA,
                                     rule.weighted_quantiles)
        for q, x in zip((0.5, 0.9, 0.99), got):
            assert tc.rank_error(s, x, q) <= tc.cluster_width(q, DELTA)
