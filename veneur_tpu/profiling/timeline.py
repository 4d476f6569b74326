"""Flush timeline: a fixed-size ring of structured per-flush records.

PR 1 rebuilt the flush launch path and made the bench emit a segment
decomposition (layout/dispatch/collective/readback) — but only the bench
could see it.  This ring makes the same decomposition observable on a
LIVE server: `core/server.py` appends one record per flush from the
aggregator's measured `last_flush_segments`, and `/debug/flush_timeline`
serves the ring as JSON.  The records double as the raw material for the
t-digest accuracy dossier (each carries the interval's key counts and
bytes moved alongside the timings).

Appends are O(1) under a lock and allocate one small dict per flush;
with the default capacity (512 records ≈ 85 minutes at a 10 s interval)
the ring holds a few hundred KiB.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Optional

DEFAULT_CAPACITY = 512


class FlushRecord(dict):
    """One flush's structured record.  A dict subclass (not a dataclass)
    so the segment set can grow without a schema migration — the
    aggregator's measured segments vary by tier (meshed flushes have no
    per-chunk layout split; idle intervals have no device segment)."""

    REQUIRED = ("interval", "unix_ts", "total_ms")


def record_from_segments(interval: int, unix_ts: float, total_s: float,
                         segments: Optional[dict] = None,
                         devices: int = 1, **extra) -> FlushRecord:
    """Build a FlushRecord from the aggregator's `last_flush_segments`:
    `*_s` second segments become `*_ms` milliseconds, byte/count gauges
    pass through unchanged."""
    rec = FlushRecord(interval=int(interval),
                      unix_ts=round(float(unix_ts), 3),
                      total_ms=round(total_s * 1e3, 3),
                      devices=int(devices))
    for name, v in (segments or {}).items():
        if not isinstance(v, (int, float)):
            # structured sub-records (the chunked pipeline's per-chunk
            # upload/dispatch/drain/wait stats) are trace material —
            # the flight recorder lays them as spans; the timeline row
            # keeps only their count
            if name == "device_chunks":
                rec["device_chunks"] = len(v)
            continue
        if name.endswith("_s"):
            rec[name[:-2] + "_ms"] = round(float(v) * 1e3, 3)
        else:
            rec[name] = int(v) if float(v).is_integer() else float(v)
    for name, v in extra.items():
        if v is not None:
            rec[name] = v
    return rec


class FlushTimeline:
    """Thread-safe bounded ring of FlushRecords (newest last)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(1, int(capacity))
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.total_recorded = 0
        # fields amended before their flush's row was appended, by
        # interval (the newest AMEND_PENDING_MAX intervals)
        self._early: dict = {}

    # how many intervals' early amendments wait for their row at most
    # (a flush that raised after its hand-off never appends one)
    AMEND_PENDING_MAX = 8

    def append(self, rec: FlushRecord) -> None:
        with self._lock:
            if self._early and "event" not in rec:
                rec.update(self._early.pop(rec.get("interval"), ()))
            self._ring.append(rec)
            self.total_recorded += 1

    def amend(self, interval: int, **fields) -> None:
        """Add fields to the flush row of `interval` — what a thread
        other than the flush thread learned of that flush after its
        hand-off (the egress lane: when the sink had the batch).  The
        flush thread appends the row AFTER it has enqueued the lane's
        job, so a small batch's lane can be done first: the fields then
        wait here and join the row as it is appended.  A later
        amendment of the same field overwrites the earlier."""
        with self._lock:
            for rec in reversed(self._ring):
                if rec["interval"] == interval and "event" not in rec:
                    rec.update(fields)
                    return
            self._early.setdefault(interval, {}).update(fields)
            while len(self._early) > self.AMEND_PENDING_MAX:
                del self._early[min(self._early)]

    def record(self, interval: int, unix_ts: float, total_s: float,
               segments: Optional[dict] = None, devices: int = 1,
               **extra) -> FlushRecord:
        """Build + append in one call (the server's per-flush hook)."""
        rec = record_from_segments(interval, unix_ts, total_s,
                                   segments, devices, **extra)
        self.append(rec)
        return rec

    def snapshot(self, last: Optional[int] = None) -> list[dict]:
        """Newest-last copy of the ring (optionally only the last N)."""
        with self._lock:
            recs = list(self._ring)
        if last is not None and last >= 0:
            recs = recs[-last:] if last else []
        return [dict(r) for r in recs]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)
