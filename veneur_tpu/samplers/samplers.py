"""Flush-ready metric records and histogram aggregate configuration.

Mirrors `samplers/samplers.go:34-94` (InterMetric, metric type constants)
and the HistogramAggregates bitmask (`samplers/samplers.go` aggregates +
config parsing).  The samplers themselves (Counter/Gauge/Set/Histo/Status)
are not per-key objects here — their state lives in the batched device
arenas (veneur_tpu/core/arena.py); this module defines the shared value
types both sides exchange.
"""

from __future__ import annotations

import enum
import gc
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from veneur_tpu.samplers import record_builder

# Metric type constants (samplers/samplers.go:50-60).
COUNTER = "counter"
GAUGE = "gauge"
STATUS = "status"

# Sampler type names used in MetricKey.Type (worker.go Upsert switch).
TYPE_COUNTER = "counter"
TYPE_GAUGE = "gauge"
TYPE_HISTOGRAM = "histogram"
TYPE_SET = "set"
TYPE_TIMER = "timer"
TYPE_STATUS = "status"


class Aggregate(enum.IntFlag):
    """Histogram aggregate selection bitmask (samplers/samplers.go)."""
    MAX = 1
    MIN = 2
    SUM = 4
    AVERAGE = 8
    COUNT = 16
    MEDIAN = 32
    HARMONIC_MEAN = 64


AGGREGATE_NAMES = {
    "max": Aggregate.MAX,
    "min": Aggregate.MIN,
    "sum": Aggregate.SUM,
    "avg": Aggregate.AVERAGE,
    "count": Aggregate.COUNT,
    "median": Aggregate.MEDIAN,
    "hmean": Aggregate.HARMONIC_MEAN,
}

# config.go:106-112 default aggregates
DEFAULT_AGGREGATES = Aggregate.MIN | Aggregate.MAX | Aggregate.COUNT


def parse_aggregates(names: list[str]) -> "HistogramAggregates":
    value = Aggregate(0)
    for n in names:
        agg = AGGREGATE_NAMES.get(n)
        if agg is not None:
            value |= agg
    return HistogramAggregates(value)


@dataclass(frozen=True)
class HistogramAggregates:
    value: Aggregate = DEFAULT_AGGREGATES

    @property
    def count(self) -> int:
        return bin(self.value).count("1")


@dataclass(slots=True)
class InterMetric:
    """The flush-ready record handed to sinks (samplers/samplers.go:34-47).

    Slotted: a high-cardinality flush constructs hundreds of thousands of
    these per interval; slots cut both per-object memory and init time."""
    name: str
    timestamp: int
    value: float
    tags: list[str]
    type: str  # counter | gauge | status
    message: str = ""
    hostname: str = ""
    # sink routing allowlist; None = all sinks (RouteInformation)
    sinks: Optional[set[str]] = None


_UNLOADED = object()
# record_builder.RecordBuilder of InterMetric from the first bulk build
# on; None on a host that cannot have one (record_builder.load)
_builder = _UNLOADED


def _record_builder() -> Optional[record_builder.RecordBuilder]:
    global _builder
    if _builder is _UNLOADED:
        _builder = record_builder.load(InterMetric)
    return _builder


@dataclass
class ForwardMetric:
    """A metric exported for forwarding to the global tier — the neutral
    in-memory twin of metricpb.Metric (samplers/metricpb/metric.proto).

    kind/scope are strings to keep this independent of generated protobuf;
    the gRPC layer converts to/from real protos.
    """
    name: str
    tags: list[str]
    kind: str                    # counter|gauge|histogram|timer|set
    scope: int                   # MetricScope value
    counter_value: int = 0
    gauge_value: float = 0.0
    # histogram payload (digest centroids + scalars)
    digest_means: Optional[list[float]] = None
    digest_weights: Optional[list[float]] = None
    digest_min: float = 0.0
    digest_max: float = 0.0
    digest_sum: float = 0.0
    digest_rsum: float = 0.0
    digest_compression: float = 100.0
    # moments-family histogram payload (sketches/moments.py vector;
    # mutually exclusive with the digest fields — a histogram/timer
    # ForwardMetric carries exactly one sketch family and the importer
    # routes by which is present)
    moments: Optional[list[float]] = None
    # compactor-family histogram payload (sketches/compactor.py wire
    # vector: self-describing header + level items; same exactly-one-
    # sketch-family contract as `moments`)
    compactor: Optional[list[float]] = None
    # set payload
    hll: bytes = b""


class MetricSegment:
    """A column-oriented run of flush-ready metrics: one (suffix, type)
    over a shared row set.

    This is the TPU-native answer to the reference's generateInterMetrics
    cost center (`flusher.go:342-415`): instead of constructing one
    InterMetric struct per emitted value, the flush keeps each aggregate
    column (`.max`, `.count`, `.50percentile`, ...) as a numpy value
    array plus SHARED per-row name/tag columns.  `bases` and `tags` are
    the same list objects across every segment of a family, so a
    100k-key flush builds them once; per-row Python work is deferred to
    the consumer that actually needs record objects (a sink encoder),
    which runs on the parallel sink pool off the flush critical path.

    `sel` selects the subset of rows this column emits for (sparse
    emission guards, `samplers/samplers.go:359-514`); None means every
    row.  `values` is aligned with `sel` (or with the full row set when
    `sel` is None).  `sinks` (routing allowlists) is aligned the same
    way when present.
    """

    __slots__ = ("bases", "tags", "suffix", "values", "type", "sel",
                 "timestamp", "sinks")

    def __init__(self, bases, tags, suffix, values, type, timestamp,
                 sel=None, sinks=None):
        self.bases = bases
        self.tags = tags
        self.suffix = suffix
        self.values = values
        self.type = type
        self.timestamp = timestamp
        self.sel = sel
        self.sinks = sinks

    def __len__(self) -> int:
        return len(self.values)

    def row(self, i: int) -> int:
        return int(self.sel[i]) if self.sel is not None else i

    def metric(self, i: int) -> InterMetric:
        r = self.row(i)
        base = self.bases[r]
        return InterMetric(
            name=base + self.suffix if self.suffix else base,
            timestamp=self.timestamp, value=float(self.values[i]),
            tags=self.tags[r], type=self.type,
            sinks=self.sinks[i] if self.sinks is not None else None)

    def materialize(self) -> list[InterMetric]:
        """Every record of the segment at once."""
        out: list[InterMetric] = []
        self.extend_records(out)
        return out

    def extend_records(self, out: list) -> bool:
        """Append every record of the segment to `out`: the value column
        leaves numpy in one `tolist`, names and (for a sparse column)
        rows are taken column-wise, and the records are built in native
        code, a chunk a call and no interpreter frame a record
        (record_builder: a quarter to a third of the cost on the chip's
        host, PERF.md §6, PR 43) — True then.  Where this
        host has no builder, or the columns are not lists of one length,
        the comprehension below builds the same records (a third of the
        cost of taking them one `metric(i)` at a time) — False."""
        vals = np.asarray(self.values, np.float64).tolist()
        bases, tags, sinks = self.bases, self.tags, self.sinks
        if self.sel is not None:
            rows = np.asarray(self.sel).tolist()
            bases = [bases[r] for r in rows]
            tags = [tags[r] for r in rows]
        suffix, ts, typ = self.suffix, self.timestamp, self.type
        builder = _record_builder()
        if (builder is not None and type(bases) is list
                and type(tags) is list and type(suffix) is str
                and len(bases) == len(tags) == len(vals)
                and (sinks is None or (type(sinks) is list
                                       and len(sinks) == len(vals)))):
            builder.extend(out, bases, suffix, ts, vals, tags, typ, sinks)
            return True
        names = [b + suffix for b in bases] if suffix else bases
        if sinks is None:
            out += [InterMetric(n, ts, v, t, typ)
                    for n, v, t in zip(names, vals, tags)]
        else:
            out += [InterMetric(n, ts, v, t, typ, "", "", s)
                    for n, v, t, s in zip(names, vals, tags, sinks)]
        return False

    def __iter__(self):
        return iter(self.materialize())


class MetricBatch:
    """The flush-ready metric collection handed to sinks: columnar
    segments (high-cardinality families) plus a loose list of individual
    InterMetrics (status checks, odd one-offs).

    Behaves like a sequence of InterMetric — iteration, len, indexing and
    slicing all work — so existing sink encoders consume it unchanged;
    they pay per-record materialization lazily on their own flush
    threads.  Columnar-aware consumers read `segments` directly.
    """

    __slots__ = ("segments", "loose", "stamps", "built")

    def __init__(self, segments=None, loose=None):
        self.segments: list[MetricSegment] = segments or []
        self.loose: list[InterMetric] = loose if loose is not None else []
        # wall-clock ns of the last materialize(): (start, records
        # built, collector splice done); None until one ran.  The egress
        # lane lays them as flush.seg.lane.sink.* spans
        self.stamps: Optional[tuple] = None
        # records the last materialize() built from segments: (in
        # native code, by the interpreter); the lane's lane_records /
        # lane_records_native
        self.built: tuple = (0, 0)

    def append(self, m: InterMetric) -> None:
        self.loose.append(m)

    def add_segment(self, seg: MetricSegment) -> None:
        if len(seg):
            self.segments.append(seg)

    def __len__(self) -> int:
        return sum(len(s) for s in self.segments) + len(self.loose)

    def __iter__(self):
        for seg in self.segments:
            yield from seg
        yield from self.loose

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            start, stop, step = idx.indices(len(self))
            if step != 1:
                return list(self)[idx]
            return self._slice(start, stop)
        if idx < 0:
            idx += len(self)
        got = self._slice(idx, idx + 1)
        if not got:
            raise IndexError(idx)
        return got[0]

    def _slice(self, start: int, stop: int) -> list[InterMetric]:
        out: list[InterMetric] = []
        off = 0
        for seg in self.segments:
            n = len(seg)
            lo, hi = max(start - off, 0), min(stop - off, n)
            for i in range(lo, hi):
                out.append(seg.metric(i))
            off += n
        lo, hi = max(start - off, 0), max(stop - off, 0)
        out.extend(self.loose[lo:hi])
        return out

    def __eq__(self, other):
        if isinstance(other, MetricBatch):
            return list(self) == list(other)
        if isinstance(other, list):
            return list(self) == other
        return NotImplemented

    def materialize(self) -> list[InterMetric]:
        """Every record at once, for a consumer that keeps them all.  The
        cyclic collector is paused meanwhile: a batch is hundreds of
        thousands of records that are all alive when the list is done,
        so the collections their allocation sets off (one young pass per
        700 records, and one or two passes over the whole server heap per
        batch) can free nothing, and the full passes land in one flush
        and not in the next.

        Lifting the pause with that many young objects counted would
        set the deferred young pass off at the caller's next allocation
        — over every record, before the caller has handed the list on
        (a quarter of the whole call, and now and then an older
        generation's pass with it).  The records are alive and hold no
        cycle (strings, numbers, a tag list), so they go to the oldest
        generation as they are: freeze() and unfreeze() splice the
        generations' lists and look at no object.  They die by
        reference count when the consumer drops the list."""
        t_start = t_built = time.time_ns()
        paused = gc.isenabled()
        gc.disable()
        native = interpreted = 0
        out: list[InterMetric] = []
        try:
            for seg in self.segments:
                before = len(out)
                if seg.extend_records(out):
                    native += len(out) - before
                else:
                    interpreted += len(out) - before
            out.extend(self.loose)
            t_built = time.time_ns()
        finally:
            if paused:
                gc.freeze()
                gc.unfreeze()
                gc.enable()
            self.stamps = (t_start, t_built, time.time_ns())
            self.built = (native, interpreted)
        return out

    def apply_routing(self, rules, match_fn) -> None:
        """Compute per-metric sink allowlists (flusher.go:97-113) across
        every segment row and loose metric.  `match_fn(rule.match, name,
        tags) -> bool`; a metric's allowlist is the union of `matched`
        lists of hitting rules plus `not_matched` of missing ones."""
        for seg in self.segments:
            sinks = []
            for i in range(len(seg)):
                r = seg.row(i)
                name = (seg.bases[r] + seg.suffix if seg.suffix
                        else seg.bases[r])
                allow: set = set()
                for rc in rules:
                    hit = match_fn(rc.match, name, seg.tags[r])
                    allow.update(rc.matched if hit else rc.not_matched)
                sinks.append(allow)
            seg.sinks = sinks
        for m in self.loose:
            allow = set()
            for rc in rules:
                hit = match_fn(rc.match, m.name, m.tags)
                allow.update(rc.matched if hit else rc.not_matched)
            m.sinks = allow
