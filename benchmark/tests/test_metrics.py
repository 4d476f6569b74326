"""The metric arithmetic against hand cases."""

import pytest

from conftest import load

m = load("metrics.py")


def test_percentile_is_over_all_samples_by_linear_interpolation():
    xs = list(range(1, 41))            # 40 flushes: 1..40 ms
    assert m.percentile(xs, 50) == 20.5
    # (40 - 1) * 0.95 = 37.05 -> between the 38th and 39th order statistic
    assert m.percentile(xs, 95) == pytest.approx(38.05)
    assert m.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        m.percentile([], 50)


def test_samples_beyond_a_tail():
    assert m.samples_beyond(40, 95) == 2
    assert m.samples_beyond(160, 95) == 8
    assert m.samples_beyond(20, 50) == 10


def test_flush_latency_is_from_the_scheduled_tick():
    ticks = [100.0, 101.0, 102.0]
    taken = [100.012, 101.250, 102.004]
    got = m.flush_latencies_ms(ticks, taken)
    assert got == pytest.approx([12.0, 250.0, 4.0])
    with pytest.raises(ValueError):
        m.flush_latencies_ms(ticks, taken[:2])


def test_late_and_failed_forwards_are_failed_operations():
    # I = 2 s, due at 0.25 I: the next tick is 1.5 s after the due time
    reports = [
        {"ack_s": [0.4, 0.6], "sent_s": [0.0, 0.0], "digests": 20},
        {"ack_s": [1.7, None], "sent_s": [0.0, 0.0], "digests": 20},
    ]
    lat, failed = m.forward_latencies_ms(reports, 2.0, 0.25)
    assert failed == 2                     # one late, one never acked
    assert lat == pytest.approx([400.0, 600.0, 1700.0, 1500.0])


def test_import_rate_is_digests_over_first_send_to_last_ack():
    reports = [{"ack_s": [0.5, 1.0], "sent_s": [0.0, 0.1], "digests": 1000},
               {"ack_s": [0.5, None], "sent_s": [0.0, 0.1], "digests": 1000}]
    assert m.import_rates(reports) == [1000.0]     # the failed one is left out


def test_cpu_per_line():
    assert m.cpu_us_per_line(2.0, 1_000_000) == pytest.approx(2.0)
    with pytest.raises(ValueError):
        m.cpu_us_per_line(1.0, 0)


def test_spread_is_quartile_distance_over_median():
    import statistics
    vals = [10.0, 10.2, 10.4, 10.6, 10.8, 11.0]
    q = statistics.quantiles(vals, n=4)
    assert m.spread(vals) == pytest.approx((q[2] - q[0]) / 10.5)
