"""Built-in simple sinks: blackhole, debug, channel, localfile.

blackhole (`sinks/blackhole/blackhole.go`) drops everything — the
test/benchmark baseline.  debug (`sinks/debug/debug.go`) logs everything.
channel is the test fixture sink from `server_test.go:184-218`
(delivers each flush's metrics to a queue).  localfile
(`sinks/localfile/localfile.go`) appends TSV rows, sharing its encoder
with the s3 sink (`util/csv.go`).
"""

from __future__ import annotations

import io
import logging
import os
import queue
from typing import Optional

from veneur_tpu import sinks as sink_mod
from veneur_tpu.samplers.samplers import InterMetric, MetricBatch

logger = logging.getLogger("veneur_tpu.sinks")


@sink_mod.register_metric_sink("blackhole")
class BlackholeMetricSink(sink_mod.BaseMetricSink):
    KIND = "blackhole"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None):
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)

    def flush(self, metrics):
        return sink_mod.MetricFlushResult(flushed=len(metrics))


@sink_mod.register_span_sink("blackhole")
class BlackholeSpanSink(sink_mod.BaseSpanSink):
    KIND = "blackhole"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None):
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)


@sink_mod.register_metric_sink("debug")
class DebugMetricSink(sink_mod.BaseMetricSink):
    KIND = "debug"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None):
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)

    def flush(self, metrics):
        for m in metrics:
            logger.info("debug sink metric: %s", m)
        return sink_mod.MetricFlushResult(flushed=len(metrics))

    def flush_other_samples(self, samples):
        for s in samples:
            logger.info("debug sink sample: %s", s)


@sink_mod.register_span_sink("debug")
class DebugSpanSink(sink_mod.BaseSpanSink):
    KIND = "debug"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None):
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)

    def ingest(self, span):
        logger.info("debug sink span: %s", span)


@sink_mod.register_span_sink("channel")
class ChannelSpanSink(sink_mod.BaseSpanSink):
    """Captures every ingested span to a queue — the span-side test
    fixture (trace/testbackend channel-backed ClientBackend analog)."""

    KIND = "channel"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None, out: Optional[queue.Queue] = None):
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)
        self.queue: queue.Queue = out if out is not None else queue.Queue()

    def ingest(self, span):
        self.queue.put(span)


@sink_mod.register_metric_sink("channel")
class ChannelMetricSink(sink_mod.BaseMetricSink):
    """Delivers each flush's InterMetric list to a queue — the in-process
    test fixture pattern (server_test.go:184-218)."""

    KIND = "channel"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None, out: Optional[queue.Queue] = None):
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)
        self.queue: queue.Queue = out if out is not None else queue.Queue()
        self.other_samples: list = []

    def flush(self, metrics):
        # a columnar batch builds its records in bulk (collector
        # paused); a list is copied
        self.queue.put(metrics.materialize()
                       if isinstance(metrics, MetricBatch)
                       else list(metrics))
        return sink_mod.MetricFlushResult(flushed=len(metrics))

    def flush_other_samples(self, samples):
        self.other_samples.extend(samples)


@sink_mod.register_metric_sink("jsonl")
class JsonLinesMetricSink(sink_mod.BaseMetricSink):
    """Appends each flush's metrics as JSON lines — the cross-PROCESS
    analog of the channel sink (testbed/proccluster.py): a parent
    harness tails the file to observe a subprocess tier's emissions
    with exact per-flush boundaries (each flush appends one `flush`
    framing record after its metric rows, so a reader can attribute
    rows to intervals without sharing memory)."""

    KIND = "jsonl"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None):
        import json
        import threading
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)
        self._json = json
        self.path = self.config.get("path", "/tmp/veneur_tpu_emit.jsonl")
        self._lock = threading.Lock()

    def flush(self, metrics):
        rows = [self._json.dumps({
            "name": m.name, "type": m.type, "value": m.value,
            "tags": list(m.tags), "timestamp": m.timestamp,
            "hostname": m.hostname}) for m in metrics]
        rows.append(self._json.dumps(
            {"flush": True, "metrics": len(metrics)}))
        with self._lock:
            # one write per flush; the final newline commits the frame
            # (a torn tail is detectable as a line with no newline)
            with open(self.path, "a") as f:
                f.write("\n".join(rows) + "\n")
                f.flush()
                os.fsync(f.fileno())
        return sink_mod.MetricFlushResult(flushed=len(metrics))


def encode_tsv_row(m: InterMetric, hostname: str, interval_s: float,
                   partition_date: str) -> str:
    """TSV row encoder shared by localfile and s3 (util/csv.go):
    name, tags, type, hostname, timestamp, value, partition date."""
    value = m.value
    if m.type == "counter" and interval_s > 0:
        value = m.value / interval_s
    return "\t".join([
        m.name, ",".join(m.tags), m.type, hostname or m.hostname,
        str(m.timestamp), repr(value), partition_date])


@sink_mod.register_metric_sink("localfile")
class LocalFileMetricSink(sink_mod.BaseMetricSink):
    KIND = "localfile"

    def __init__(self, spec: Optional[sink_mod.SinkSpec] = None,
                 server_config=None):
        spec = spec or sink_mod.SinkSpec(kind=self.KIND)
        super().__init__(spec.name, spec.config)
        self.path = self.config.get("flush_file", "/tmp/veneur_tpu_flush.tsv")
        self.hostname = getattr(server_config, "hostname", "") or ""
        self.interval_s = float(getattr(server_config, "interval", 10.0)
                                or 10.0)

    def flush(self, metrics):
        import datetime
        date = datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y-%m-%d")
        buf = io.StringIO()
        for m in metrics:
            buf.write(encode_tsv_row(m, self.hostname, self.interval_s, date))
            buf.write("\n")
        with open(self.path, "a") as f:
            f.write(buf.getvalue())
        return sink_mod.MetricFlushResult(flushed=len(metrics))
