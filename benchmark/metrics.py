"""The benchmark's metric arithmetic: the reduction from what a run
observed (tick times, dequeue times, acks, CPU seconds, counts) to the
end-to-end numbers.  Pure functions; `tests/test_metrics.py` holds them to
hand cases.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default rule), over ALL the values given."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile (the guide
    wants about ten before a tail is believed; the harness prints it)."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def flush_latencies_ms(ticks: list[float], taken: list[float]) -> list[float]:
    """Interval close to sink, per flush: from the flush's SCHEDULED tick
    (wall clock) to the moment its batch left the sink's queue."""
    if len(ticks) != len(taken):
        raise ValueError("one dequeue time per scheduled tick")
    return [(t - tick) * 1e3 for tick, t in zip(ticks, taken)]


def forward_latencies_ms(reports: list[dict], interval_s: float,
                         due_share: float):
    """Per local and interval: from the moment the forward was DUE to the
    ack of its last chunk.  A forward that failed, or was not acked before
    the next tick (1 - due_share of an interval after it was due), is a
    failed operation; a late one still counts in the tail with the time it
    took, a failed one with the deadline.  Returns (latencies_ms,
    failed_forwards)."""
    deadline = (1.0 - due_share) * interval_s
    lat, failed = [], 0
    for r in reports:
        for ack in r["ack_s"]:
            if ack is None:
                failed += 1
                lat.append(deadline * 1e3)
            else:
                if ack > deadline:
                    failed += 1
                lat.append(ack * 1e3)
    return lat, failed


def import_rates(reports: list[dict]) -> list[float]:
    """Digests of one interval over (last ack - first send), per interval,
    on the generator's own clock."""
    out = []
    for r in reports:
        acks = [a for a in r["ack_s"] if a is not None]
        sent = [s for s in r["sent_s"] if s is not None]
        if len(acks) == len(r["ack_s"]) and sent and max(acks) > min(sent):
            out.append(r["digests"] / (max(acks) - min(sent)))
    return out


def cpu_us_per_line(cpu_seconds: float, lines: int) -> float:
    """Server-process CPU (all threads) over the window, per statsd line
    the sink's aggregates account for."""
    if lines <= 0:
        raise ValueError("no lines accounted for")
    return cpu_seconds * 1e6 / lines


def spread(values) -> float:
    """Interquartile distance as a share of the median, by
    statistics.quantiles(n=4) — the contract's spread."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
