"""The interval ledger (core/aggregator.py): work done for an interval by
threads other than the flush thread — import RPCs, the native drain's fold
— is accumulated under the aggregator lock, swapped out at the snapshot
exactly as `imported` is, and lands on the flush timeline row of the flush
that closes the interval; the snapshot's own parts and the egress lane's
are spans on the flush's trace; which queue overflowed is a counter."""

import socket
import threading
import time

import pytest

from veneur_tpu import config as config_mod
from veneur_tpu import http_api
from veneur_tpu import ingest as ingest_mod
from veneur_tpu.core.aggregator import (COLUMNS_PART_KEYS,
                                        LEDGER_SEGMENT_KEYS,
                                        ROW_ONLY_SEGMENT_KEYS,
                                        MetricAggregator)
from veneur_tpu.core.server import Server
from veneur_tpu.forward.client import ForwardClient
from veneur_tpu.profiling.timeline import FlushTimeline
from veneur_tpu.protocol import forward_pb2, metric_pb2, tdigest_pb2
from veneur_tpu.sinks import simple as simple_sinks
from veneur_tpu.trace import assembly

ROW_FIELDS = ("snapshot_lock_wait_ms", "snapshot_sync_ms",
              "snapshot_staged_ms", "snapshot_columns_ms", "import_rpcs",
              "import_lock_wait_ms", "import_scan_ms", "import_held_ms",
              "fold_calls", "fold_lines", "fold_lock_wait_ms", "fold_ms",
              "import_digest_hits", "import_digest_misses",
              "staged_points", "staged_cut_copy_bytes", "staged_regrows",
              "snapshot_cache_ms", "snapshot_cut_ms", "snapshot_reset_ms",
              "snapshot_end_ms", "snapshot_rest_ms")
# what the metric lane amends the row with once the sink had the batch
LANE_ROW_FIELDS = ("lane_sink_cpu_ms", "lane_gc_passes",
                   "lane_gc_full_passes", "lane_records",
                   "lane_records_native")
COLUMNS_PARTS = ("cache", "cut", "reset", "end", "rest")
SINK_PARTS = ("records", "splice", "put")


def _wait(cond, timeout_s=10.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def _digest_pb(name: str, tags=(), compression: float = 100.0):
    return metric_pb2.Metric(
        name=name, type=metric_pb2.Timer, tags=list(tags),
        histogram=metric_pb2.HistogramValue(
            t_digest=tdigest_pb2.MergingDigestData(
                main_centroids=[tdigest_pb2.Centroid(mean=m, weight=2.0)
                                for m in (1.0, 2.0, 4.0)],
                compression=compression, min=1.0, max=4.0,
                reciprocalSum=3.5)))


def _payload(i: int, n: int = 20, digests: int = 0) -> bytes:
    return forward_pb2.MetricList(metrics=[
        metric_pb2.Metric(name=f"led.c{j}", type=metric_pb2.Counter,
                          tags=[f"rpc:{i % 3}"],
                          counter=metric_pb2.CounterValue(value=1))
        for j in range(n)] + [
        _digest_pb(f"led.d{j}", [f"rpc:{i % 3}"])
        for j in range(digests)]).SerializeToString()


def _segments_sum(rows: list, key: str):
    return sum(r[key] for r in rows)


@pytest.mark.parametrize("native_scan", [True, False])
def test_ledger_swap_is_exact_under_concurrent_import_and_drain(native_scan):
    """Sums over intervals == sums over RPCs and drain calls: nothing is
    counted twice or lost across the cut, whichever side of a snapshot an
    RPC or a fold lands on."""
    agg = MetricAggregator(percentiles=[0.5])
    if not native_scan:
        agg._native_import = False      # the protobuf batch path
    nat = ingest_mod.NativeIngest(agg)
    tid = nat.engine.new_thread()
    stop = threading.Event()
    segs: list = []
    per_rpc: list = []          # (scan, lock wait, held) ns per RPC
    folds = {"calls": 0, "lines": 0}
    lock = threading.Lock()

    def importer(k: int) -> None:
        for i in range(40):
            ok, failed = agg.import_payload(
                _payload(k * 100 + i, digests=5))
            assert (ok, failed) == (25, 0)
            timing = agg.take_import_timing()
            assert timing is not None and agg.take_import_timing() is None
            with lock:
                per_rpc.append(timing)

    def drainer() -> None:
        i = 0
        while not stop.is_set():
            for _ in range(3):
                nat.engine.ingest(tid, b"led.t:%d|ms\nled.n:1|c" % i)
                i += 1
            batch = nat.drain_into()
            if not batch.empty:
                folds["calls"] += 1
                folds["lines"] += batch.processed

    def flusher() -> None:
        while not stop.is_set():
            agg.flush(is_local=False)
            segs.append(dict(agg.last_flush_segments))

    threads = [threading.Thread(target=importer, args=(k,))
               for k in range(4)]
    side = [threading.Thread(target=drainer),
            threading.Thread(target=flusher)]
    for t in threads + side:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    for t in side:
        t.join()
    agg.flush(is_local=False)           # closes whatever is still open
    segs.append(dict(agg.last_flush_segments))
    nat.close()

    assert len(segs) >= 2
    assert _segments_sum(segs, "import_rpcs") == len(per_rpc) == 160
    assert _segments_sum(segs, "fold_calls") == folds["calls"] > 0
    assert _segments_sum(segs, "fold_lines") == folds["lines"] > 0
    for i, key in enumerate(("import_scan_s", "import_lock_wait_s",
                             "import_held_s")):
        want = sum(t[i] for t in per_rpc) / 1e9
        assert _segments_sum(segs, key) == pytest.approx(want, rel=1e-9)
    assert _segments_sum(segs, "fold_s") > 0
    # every plain digest the wire scan staged is a hit or a miss of the
    # interval it landed in; the protobuf path counts none
    assert (_segments_sum(segs, "import_digest_hits")
            + _segments_sum(segs, "import_digest_misses")
            == (160 * 5 if native_scan else 0))
    assert (_segments_sum(segs, "import_digest_misses") >= 3 * 5) \
        == native_scan
    # the open ledger is empty again: the last flush took everything
    assert not any(agg._ledger.values())


def test_snapshot_lock_wait_reads_the_hold_and_parts_sum_to_snapshot():
    agg = MetricAggregator(percentiles=[0.5])
    for i in range(50):
        agg.import_pb_batch(forward_pb2.MetricList.FromString(
            _payload(i)).metrics)
    held = threading.Event()

    def hold() -> None:
        with agg.lock:
            held.set()
            time.sleep(0.05)

    t = threading.Thread(target=hold)
    t.start()
    assert held.wait(5.0)
    t_flush = time.perf_counter()
    agg.flush(is_local=False)
    t.join()
    seg = agg.last_flush_segments
    # the holder sleeps 50 ms from `held`; the flush began right after it
    assert 0.04 <= seg["snapshot_lock_wait_s"] \
        <= time.perf_counter() - t_flush
    parts = sum(seg[f"snapshot_{p}_s"]
                for p in ("lock_wait", "sync", "staged", "columns"))
    # snapshot_s keeps its meaning: the parts plus the deferred
    # unique-timeseries estimate and the lock's release
    assert parts <= seg["snapshot_s"] + 1e-9
    assert seg["snapshot_s"] - parts < 0.005
    assert min(seg[f"snapshot_{p}_s"]
               for p in ("lock_wait", "sync", "staged", "columns")) >= 0.0
    assert {k for k in seg if k.startswith(("snapshot_", "import_",
                                            "fold_", "set_import_",
                                            "key_birth_"))} \
        == LEDGER_SEGMENT_KEYS | {"snapshot_s"}


@pytest.fixture
def server():
    servers = []

    def boot(**kw):
        sink = simple_sinks.ChannelMetricSink()
        kw.setdefault("interval", 10.0)
        sink = kw.pop("sink", sink)
        cfg = config_mod.Config(
            statsd_listen_addresses=["udp://127.0.0.1:0"],
            percentiles=[0.5], hostname="ledger-test", **kw)
        srv = Server(cfg, extra_metric_sinks=[sink])
        servers.append(srv)
        return srv, sink

    yield boot
    for srv in servers:
        srv.shutdown()


def _send_and_drain(srv, lines: int = 40) -> None:
    _, addr = srv.statsd_addrs[0]
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.sendto(b"\n".join(b"led.t%d:%d|ms" % (i, i) for i in range(lines)),
              addr)
    tx.close()
    assert _wait(lambda: (srv._drain_native() or True)
                 and srv.native.engine.totals()[0] >= lines)


def _flush_and_spans(srv) -> list:
    """One flush, its lanes settled and its root span (which rides the
    span pipeline) in the ring."""
    srv.flush()
    assert srv.egress.settle(timeout_s=10.0)
    assert _wait(lambda: any(s["name"] == "flush"
                             for s in srv.flight_recorder.snapshot()))
    return srv.flight_recorder.snapshot()


def test_lane_spans_continue_the_flush_trace_under_the_sink_span(server):
    srv, sink = server()
    srv.start()
    _send_and_drain(srv)
    spans = _flush_and_spans(srv)
    root = [s for s in spans if s["name"] == "flush"][0]
    sink_span = [s for s in spans if s["name"] == "flush.sink.channel"][0]
    assert sink_span["trace_id"] == root["trace_id"]
    assert sink_span["parent_id"] == root["span_id"]
    lane = {s["name"]: s for s in spans
            if s["name"].startswith("flush.seg.lane.")
            and not s["name"].startswith("flush.seg.lane.sink.")}
    assert set(lane) == {"flush.seg.lane.wait", "flush.seg.lane.filter",
                         "flush.seg.lane.sink"}
    for s in lane.values():
        assert s["trace_id"] == root["trace_id"]
        assert s["parent_id"] == sink_span["span_id"]
        assert s["tags"]["sink"] == "channel"
        # real timestamps, inside the sink span
        assert s["start_ns"] >= sink_span["start_ns"]
    # durations are rounded to the microsecond, each
    assert sum(s["duration_ms"] for s in lane.values()) \
        <= sink_span["duration_ms"] + 0.004
    # only metric lanes carry them: one of each per trace with one sink
    # (and the sink call's own three parts under it)
    assert len([s for s in spans
                if s["name"].startswith("flush.seg.lane.")]) == 3 + 3


def test_critical_path_table_is_unchanged_by_the_new_grandchildren(server):
    srv, _sink = server()
    srv.start()
    _send_and_drain(srv)
    spans = _flush_and_spans(srv)
    root = [s for s in spans if s["name"] == "flush"][0]
    trace = [s for s in spans if s["trace_id"] == root["trace_id"]]
    new = [s for s in trace
           if s["name"].startswith(("flush.seg.snapshot.",
                                    "flush.seg.lane."))]
    # the snapshot's 4 parts + the columns' 5, the lane's 3 + the sink's 3
    assert len(new) == 4 + 5 + 3 + 3
    assert all(s["parent_id"] != root["span_id"] for s in new)
    with_new = assembly.interval_row(root, trace)
    without = assembly.interval_row(
        root, [s for s in trace if s not in new])
    assert with_new["sum_segments_ms"] == without["sum_segments_ms"]
    assert with_new["segments_ms"] == without["segments_ms"]
    assert with_new["critical_path_ms"] == without["critical_path_ms"]
    assert not assembly.find_orphans(trace)
    # the snapshot's parts are laid inside their parent
    snap = [s for s in trace if s["name"] == "flush.seg.snapshot"][0]
    parts = [s for s in trace
             if s["name"].startswith("flush.seg.snapshot.")
             and not s["name"].startswith("flush.seg.snapshot.columns.")]
    assert {s["parent_id"] for s in parts} == {snap["span_id"]}
    assert sum(s["duration_ms"] for s in parts) \
        <= snap["duration_ms"] + 0.004


def test_tracing_off_records_no_span_and_keeps_the_row_fields(server):
    srv, _sink = server(trace_flush_enabled=False)
    srv.start()
    _send_and_drain(srv)
    spans = _flush_and_spans(srv)
    assert not [s for s in spans if s["name"].startswith(
        ("flush.seg.", "flush.sink."))]
    row = srv.flush_timeline.snapshot()[-1]
    for field in ROW_FIELDS:
        assert field in row, field
    assert row["fold_calls"] >= 1 and row["fold_lines"] == 40
    assert row["import_rpcs"] == 0
    # no import ran: no digest was staged from a scan
    assert row["import_digest_hits"] == row["import_digest_misses"] == 0
    for field in ("udp_rcvbuf_drops", "ring_full_stalls",
                  "ring_peak_share"):
        assert field in row, field


def test_ledger_keys_become_no_self_metric(server):
    """The row and the spans are the ledger's outlet: a dozen new timers
    per flush would add rows to the digest arena the flush measures."""
    from tests.test_self_telemetry import FakeStatsd

    srv, _sink = server()
    srv.statsd = FakeStatsd()
    srv.start()
    _send_and_drain(srv)
    srv.flush()
    names = {c[1] for c in srv.statsd.calls}
    assert "flush.segment.snapshot_ms" in names         # as before
    for key in LEDGER_SEGMENT_KEYS:
        stem = key[:-2] if key.endswith("_s") else key
        assert f"flush.segment.{stem}_ms" not in names, key
        assert f"flush.{key}" not in names, key


def test_overflow_counters_on_the_row_and_in_debug_vars(server):
    srv, _sink = server()
    srv.start()
    _send_and_drain(srv)
    srv.flush()
    row = srv.flush_timeline.snapshot()[-1]
    assert row["udp_rcvbuf_drops"] == 0 and row["ring_full_stalls"] == 0
    assert 0.0 < row["ring_peak_share"] <= 1.0
    dv = http_api.debug_vars(srv)["ingest_overflow"]
    assert dv["ring_peak_share"] == row["ring_peak_share"]
    assert dv["udp_rcvbuf_drops_total"] == 0
    assert dv["ring_full_stalls_total"] == 0
    # a second, idle interval: the peak is per interval, read and reset
    srv.flush()
    assert srv.flush_timeline.snapshot()[-1]["ring_peak_share"] == 0.0


def test_ring_stats_count_full_rings_and_lose_nothing():
    """A 2-slot ring never drained: every publish past the second finds
    it full (the batch stays with the producer; nothing is dropped), and
    the peak reads the whole ring until it is read."""
    eng = ingest_mod.IngestEngine(4096, batch=1, ring_slots=2)
    tid = eng.new_thread()
    assert eng.ring_stats() == (0, 0, 2)
    for i in range(10):
        eng.ingest(tid, b"ring:%d|c" % i)
    full, peak, slots = eng.ring_stats()
    assert (full, peak, slots) == (8, 2, 2)
    batch = eng.drain()
    assert batch.processed == 10                # ... and no line lost
    full, peak, _ = eng.ring_stats()
    assert (full, peak) == (8, 0)               # monotonic; peak was reset
    eng.close()


def test_udp_rcvbuf_drops_are_read_from_the_socket(server):
    """The kernel's drop count of a UDP socket whose reader is not
    reading (the Python data plane's socket, never started)."""
    srv, _sink = server(read_buffer_size_bytes=4096)
    srv.start()
    # a second datagram socket nobody reads, registered like a listener
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    rx.bind(("127.0.0.1", 0))
    srv._listeners.append(rx)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    for _ in range(200):
        tx.sendto(b"x" * 1000, rx.getsockname())
    tx.close()
    srv.flush()
    row = srv.flush_timeline.snapshot()[-1]
    assert row["udp_rcvbuf_drops"] > 0
    srv.flush()
    assert srv.flush_timeline.snapshot()[-1]["udp_rcvbuf_drops"] == 0
    assert http_api.debug_vars(srv)["ingest_overflow"][
        "udp_rcvbuf_drops_total"] == row["udp_rcvbuf_drops"]


def test_global_import_span_carries_the_three_durations():
    """The import span is recorded only for a sender that sent a trace
    context; when it is, its tags say where the RPC's time went."""
    glob = Server(config_mod.Config(grpc_address="127.0.0.1:0",
                                    interval=10.0, percentiles=[0.5],
                                    hostname="g0"))
    glob.start()
    loc = Server(config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        forward_address=f"127.0.0.1:{glob.grpc_import.port}",
        interval=10.0, percentiles=[0.5], hostname="l0"))
    loc.start()
    try:
        _send_and_drain(loc, lines=4)
        loc.flush()
        assert _wait(lambda: any(
            r["name"] == "global.import"
            for r in glob.flight_recorder.snapshot()))
        imp = [r for r in glob.flight_recorder.snapshot()
               if r["name"] == "global.import"][0]
        for tag in ("scan_ms", "lock_wait_ms", "held_ms"):
            assert float(imp["tags"][tag]) >= 0.0
        assert float(imp["tags"]["held_ms"]) > 0.0
        glob.flush()
        row = glob.flush_timeline.snapshot()[-1]
        assert row["import_rpcs"] >= 1
        assert row["import_held_ms"] > 0.0
        assert row["imported"] >= 4
    finally:
        loc.shutdown()
        glob.shutdown()


def test_digest_hits_and_misses_add_up_to_the_plain_digests_imported():
    """The row of a flush that closed an importing interval says how
    often the columnar path engaged: per interval, first sightings and
    cache hits are disjoint and add up to the plain digests imported;
    a marker record (python merges it) and counters are in neither.
    The row cache outlives the flush (PR 41): a key is a first
    sighting once, not once an interval, and `import_row_hits` /
    `import_row_misses` count the same over every family the cache
    serves (here also the counter key)."""
    assert {"import_digest_hits", "import_digest_misses",
            "import_row_hits", "import_row_misses",
            "import_row_cache_clears"} \
        <= LEDGER_SEGMENT_KEYS <= ROW_ONLY_SEGMENT_KEYS
    glob = Server(config_mod.Config(grpc_address="127.0.0.1:0",
                                    interval=10.0, percentiles=[0.5],
                                    hostname="g0"))
    glob.start()
    client = ForwardClient(f"127.0.0.1:{glob.grpc_import.port}",
                           timeout_s=10.0, source="ledger-test")
    pbs = ([_digest_pb(f"led.h{j}", ["a:b"]) for j in range(30)]
           + [metric_pb2.Metric(name="led.c", type=metric_pb2.Counter,
                                counter=metric_pb2.CounterValue(value=1))
              for _ in range(5)]
           # a moments-family marker of the wrong length: refused by
           # merge_moments, and never a plain digest
           + [_digest_pb("led.m", compression=-8.0)])
    try:
        for epoch, sends in ((1, 3), (2, 1)):
            for k in range(sends):
                client.send_pbs(pbs, epoch=10 * epoch + k)
            glob.flush()
            row = glob.flush_timeline.snapshot()[-1]
            assert row["import_rpcs"] == sends
            first = epoch == 1      # the only interval that resolves keys
            assert row["import_digest_misses"] == 30 * first
            assert row["import_digest_hits"] == 30 * (sends - first)
            # + the counter key: 5 records a send, one row
            assert row["import_row_misses"] == 31 * first
            assert row["import_row_hits"] == 35 * sends - 31 * first
            assert row["import_row_cache_clears"] == 0
            assert row["imported"] == 35 * sends
        glob.flush()        # an interval nobody forwarded in
        row = glob.flush_timeline.snapshot()[-1]
        assert row["import_rpcs"] == 0
        assert row["import_digest_hits"] == row["import_digest_misses"] == 0
        assert row["import_row_hits"] == row["import_row_misses"] == 0
    finally:
        client.close()
        glob.shutdown()


# -- the cut and the hand-off, part by part ---------------------------------


def _trace_of(srv, spans: list) -> dict:
    """The last flush's spans by name (one metric sink: names are unique)."""
    tid = int(srv.flush_timeline.snapshot()[-1]["trace_id"], 16)
    return {s["name"]: s for s in spans if s["trace_id"] == tid}


def _inside(child: dict, parent: dict, slack_ms: float = 0.004) -> bool:
    # durations are rounded to the microsecond, each
    return (child["start_ns"] >= parent["start_ns"]
            and child["start_ns"] / 1e6 + child["duration_ms"]
            <= parent["start_ns"] / 1e6 + parent["duration_ms"] + slack_ms)


def test_columns_parts_sum_to_the_columns_span_and_lie_inside_it(server):
    srv, _sink = server()
    srv.start()
    _send_and_drain(srv)
    trace = _trace_of(srv, _flush_and_spans(srv))
    row = srv.flush_timeline.snapshot()[-1]
    parts_ms = [row[f"snapshot_{p}_ms"] for p in COLUMNS_PARTS]
    # five fields rounded to the microsecond against a sixth
    assert sum(parts_ms) == pytest.approx(row["snapshot_columns_ms"],
                                          abs=0.004)
    assert min(parts_ms[:4]) >= 0.0 and parts_ms[4] > -0.001
    # the aggregator's own seconds add up exactly: the last is what is left
    seg = srv.aggregator.last_flush_segments
    assert sum(seg[k] for k in COLUMNS_PART_KEYS) == pytest.approx(
        seg["snapshot_columns_s"], abs=1e-12)
    columns = trace["flush.seg.snapshot.columns"]
    children = [trace[f"flush.seg.snapshot.columns.{p}"]
                for p in COLUMNS_PARTS]
    assert all(c["parent_id"] == columns["span_id"] for c in children)
    assert all(_inside(c, columns, slack_ms=0.008) for c in children)
    # laid end to end, in order, from the parent's start
    assert children[0]["start_ns"] == columns["start_ns"]
    assert [c["start_ns"] for c in children] \
        == sorted(c["start_ns"] for c in children)
    for c, p in zip(children, COLUMNS_PARTS):
        assert c["duration_ms"] == pytest.approx(
            max(0.0, row[f"snapshot_{p}_ms"]), abs=0.002)


@pytest.mark.parametrize("part", ["cut", "reset", "end"])
def test_columns_parts_say_which_family(server, part):
    """A flush that touched timers and counters: the span's tags carry
    the milliseconds of each family the step walked, and they add up to
    the row's field."""
    srv, _sink = server()
    srv._FAMILY_TAG_MIN_MS = 0.0       # tag every family, however small
    srv.start()
    _, addr = srv.statsd_addrs[0]
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.sendto(b"\n".join([b"led.t%d:%d|ms" % (i, i) for i in range(20)]
                         + [b"led.c%d:1|c" % i for i in range(20)]), addr)
    tx.close()
    assert _wait(lambda: (srv._drain_native() or True)
                 and srv.native.engine.totals()[0] >= 40)
    trace = _trace_of(srv, _flush_and_spans(srv))
    row = srv.flush_timeline.snapshot()[-1]
    tags = trace[f"flush.seg.snapshot.columns.{part}"]["tags"]
    assert set(tags) == set(MetricAggregator._FAMILIES)
    assert sum(float(v) for v in tags.values()) == pytest.approx(
        row[f"snapshot_{part}_ms"], abs=0.005)
    # the default threshold leaves a family under 0.05 ms out
    assert Server._FAMILY_TAG_MIN_MS == 0.05


def _serve(srv, flushes: int, timeout_s: float = 20.0):
    """Run the ticker until `flushes` rows are in and their lanes done."""
    t = threading.Thread(target=srv.serve, daemon=True)
    t.start()
    assert _wait(lambda: len(srv.flush_timeline) >= flushes, timeout_s)
    srv.stop_serving()
    t.join(10)
    assert srv.egress.settle(timeout_s=10.0)
    return [r for r in srv.flush_timeline.snapshot() if "event" not in r]


def test_a_served_flush_carries_its_tick_and_a_hand_called_one_does_not(
        server):
    srv, _sink = server(interval=0.2)
    srv.start()
    _send_and_drain(srv)
    rows = _serve(srv, 2)
    for row in rows[:2]:
        # the ticker wakes at or after its tick: milliseconds as a rule,
        # a few hundred under six test workers (205 and 211 ms were read
        # there); what is held is the unit and the tick it is taken from
        assert 0.0 <= row["tick_late_ms"] < 2000.0
        assert row["tick_to_sink_ms"] >= row["tick_late_ms"]
        # one process, one clock: tick -> flush start -> flush -> lane
        assert row["tick_to_sink_ms"] <= (row["tick_late_ms"]
                                          + row["total_ms"] + 2000.0)
        for field in LANE_ROW_FIELDS:
            assert field in row, field
    srv.flush()
    assert srv.egress.settle(timeout_s=10.0)
    row = srv.flush_timeline.snapshot()[-1]
    assert "tick_late_ms" not in row and "tick_to_sink_ms" not in row
    for field in LANE_ROW_FIELDS:
        assert field in row, field


def test_a_lane_done_before_the_row_is_appended_still_reaches_it(server):
    """The flush thread appends the row after it has enqueued the job: a
    small batch's lane is done first, and its fields join the row as it
    is appended."""
    srv, _sink = server(interval=0.2)
    srv.start()
    record = srv.flush_timeline.record
    early = []

    def record_after_the_lane(**kw):
        assert srv.egress.settle(timeout_s=10.0)
        early.append(dict(srv.flush_timeline._early))
        return record(**kw)

    srv.flush_timeline.record = record_after_the_lane
    rows = _serve(srv, 2)
    # the lane's fields were waiting when the row came
    assert early and all(e for e in early)
    assert not srv.flush_timeline._early
    for row in rows[:2]:
        assert row["tick_to_sink_ms"] >= row["tick_late_ms"] >= 0.0
        for field in LANE_ROW_FIELDS:
            assert field in row, field


@pytest.mark.parametrize("order", ["row_first", "lane_first"])
def test_amend_reaches_the_row_whichever_came_first(order):
    tl = FlushTimeline(capacity=4)
    tl.record(interval=7, unix_ts=1.0, total_s=0.0, event="checkpoint")
    if order == "row_first":
        tl.record(interval=7, unix_ts=1.0, total_s=0.001)
        tl.amend(7, tick_to_sink_ms=3.0, lane_gc_passes=0)
    else:
        tl.amend(7, tick_to_sink_ms=3.0, lane_gc_passes=0)
        # an event row of the same interval takes nothing
        tl.record(interval=7, unix_ts=1.0, total_s=0.0, event="restore")
        tl.record(interval=7, unix_ts=1.0, total_s=0.001)
    rows = tl.snapshot()
    assert [r for r in rows if "event" in r and "tick_to_sink_ms" in r] == []
    row = [r for r in rows if "event" not in r][0]
    assert row["tick_to_sink_ms"] == 3.0 and row["lane_gc_passes"] == 0
    # the lane that finished last stays on the row
    tl.amend(7, tick_to_sink_ms=4.5)
    assert tl.snapshot()[-1]["tick_to_sink_ms"] == 4.5
    assert not tl._early
    # rows that never come do not pile up
    for interval in range(100, 100 + 3 * tl.AMEND_PENDING_MAX):
        tl.amend(interval, tick_to_sink_ms=1.0)
    assert len(tl._early) == tl.AMEND_PENDING_MAX
    assert min(tl._early) == 100 + 2 * tl.AMEND_PENDING_MAX


def _thread_clock_step_ms() -> float:
    """The smallest step time.thread_time_ns() takes over 30 ms of work."""
    steps, last = [], time.thread_time_ns()
    deadline = time.perf_counter() + 0.03
    while time.perf_counter() < deadline:
        now = time.thread_time_ns()
        if now != last:
            steps.append(now - last)
            last = now
    return min(steps) / 1e6 if steps else 30.0


def test_lane_cpu_time_is_inside_the_sink_span_and_its_parts_too(server):
    srv, _sink = server()
    srv.start()
    _send_and_drain(srv)
    trace = _trace_of(srv, _flush_and_spans(srv))
    row = srv.flush_timeline.snapshot()[-1]
    sink_span = trace["flush.seg.lane.sink"]
    # a thread cannot use more CPU than wall time passes (the clocks
    # differ: a millisecond of room, or one step of the CPU clock where
    # the host counts thread time in ticks — 10 ms under gVisor)
    assert 0.0 <= row["lane_sink_cpu_ms"] \
        <= sink_span["duration_ms"] + max(1.0, _thread_clock_step_ms())
    assert row["lane_gc_passes"] >= row["lane_gc_full_passes"] >= 0
    children = [trace[f"flush.seg.lane.sink.{p}"] for p in SINK_PARTS]
    for c in children:
        assert c["parent_id"] == sink_span["span_id"]
        assert c["tags"]["sink"] == "channel"
        assert _inside(c, sink_span)
    # real timestamps, one after the other, up to the sink's return
    records, splice, put = children
    assert records["start_ns"] >= sink_span["start_ns"]
    assert records["start_ns"] <= splice["start_ns"] <= put["start_ns"]
    assert sum(c["duration_ms"] for c in children) \
        <= sink_span["duration_ms"] + 0.004
    assert put["start_ns"] / 1e6 + put["duration_ms"] == pytest.approx(
        sink_span["start_ns"] / 1e6 + sink_span["duration_ms"], abs=0.004)
    # /debug/vars: each lane's own last call
    last = http_api.debug_vars(srv)["egress"]["per_sink"][
        "metric:channel"]["last_sink_call"]
    assert last["interval"] == row["interval"]
    assert last["lane_sink_cpu_ms"] == row["lane_sink_cpu_ms"]


def test_the_lane_counts_the_records_it_built_and_which_way(server,
                                                            monkeypatch):
    """lane_records: what the sink's call built from the batch's
    segments; lane_records_native: how many of them in native code —
    all of them where this host has the builder, none where it has not;
    the parts of the sink's span still add up to it."""
    from veneur_tpu.samplers import samplers as sm

    srv, sink = server()
    srv.start()
    have_native = sm._record_builder() is not None
    for way in ("native", "interpreter"):
        with monkeypatch.context() as patch:
            if way == "interpreter":
                patch.setattr(sm, "_builder", None)
            _send_and_drain(srv)
            trace = _trace_of(srv, _flush_and_spans(srv))
        got = sink.queue.get_nowait()
        loose = sum(1 for m in got if m.type == sm.STATUS)
        row = srv.flush_timeline.snapshot()[-1]
        assert row["lane_records"] == len(got) - loose > 0
        assert row["lane_records_native"] == (
            row["lane_records"] if way == "native" and have_native else 0)
        last = http_api.debug_vars(srv)["egress"]["per_sink"][
            "metric:channel"]["last_sink_call"]
        assert last["interval"] == row["interval"]
        assert last["lane_records"] == row["lane_records"]
        assert last["lane_records_native"] == row["lane_records_native"]
        sink_span = trace["flush.seg.lane.sink"]
        parts = [trace[f"flush.seg.lane.sink.{p}"] for p in SINK_PARTS]
        assert sum(c["duration_ms"] for c in parts) == pytest.approx(
            sink_span["duration_ms"], abs=0.5)
        assert all(_inside(c, sink_span) for c in parts)


class _IteratingSink(simple_sinks.ChannelMetricSink):
    """A sink that walks the batch and never materialises it."""

    def flush(self, metrics):
        self.queue.put([m.name for m in metrics])
        return simple_sinks.sink_mod.MetricFlushResult(flushed=len(metrics))


def test_a_sink_that_iterates_the_batch_gets_no_children(server):
    srv, _sink = server(sink=_IteratingSink())
    srv.start()
    _send_and_drain(srv)
    trace = _trace_of(srv, _flush_and_spans(srv))
    assert "flush.seg.lane.sink" in trace
    assert not [n for n in trace if n.startswith("flush.seg.lane.sink.")]
    # the row's lane fields do not depend on the sink's way; it built
    # its records a segment at a time, outside materialize()'s count
    row = srv.flush_timeline.snapshot()[-1]
    for field in LANE_ROW_FIELDS:
        assert field in row, field
    assert row["lane_records"] == row["lane_records_native"] == 0


def test_a_stamp_from_outside_the_call_lays_no_children(server):
    """Stamps are the batch's LAST materialize(): one made before this
    lane's sink call (another lane's, an earlier attempt's owner) is not
    this call's."""
    from veneur_tpu.samplers.samplers import MetricBatch

    class _Stale(_IteratingSink):
        def flush(self, metrics):
            if isinstance(metrics, MetricBatch):
                metrics.stamps = (1, 2, 3)
            return super().flush(metrics)

    srv, _sink = server(sink=_Stale())
    srv.start()
    _send_and_drain(srv)
    trace = _trace_of(srv, _flush_and_spans(srv))
    assert not [n for n in trace if n.startswith("flush.seg.lane.sink.")]


def test_the_new_keys_become_no_self_metric(server):
    from tests.test_self_telemetry import FakeStatsd

    assert set(COLUMNS_PART_KEYS) <= LEDGER_SEGMENT_KEYS \
        <= ROW_ONLY_SEGMENT_KEYS
    srv, _sink = server(interval=0.2)
    srv.statsd = FakeStatsd()
    srv.start()
    _send_and_drain(srv)
    _serve(srv, 2)
    names = {c[1] for c in srv.statsd.calls}
    assert "flush.segment.snapshot_ms" in names         # as before
    for stem in ("snapshot_cache", "snapshot_cut", "snapshot_reset",
                 "snapshot_end", "snapshot_rest", "columns_by_family",
                 "tick_late", "tick_to_sink", "lane_sink_cpu",
                 "lane_gc"):
        assert not [n for n in names if stem in n], stem


def test_tracing_off_records_no_new_span_and_keeps_the_new_fields(server):
    srv, _sink = server(trace_flush_enabled=False, interval=0.2)
    srv.start()
    _send_and_drain(srv)
    rows = _serve(srv, 2)
    assert not [s for s in srv.flight_recorder.snapshot()
                if s["name"].startswith(("flush.seg.", "flush.sink."))]
    for field in (*(f"snapshot_{p}_ms" for p in COLUMNS_PARTS),
                  *LANE_ROW_FIELDS, "tick_late_ms", "tick_to_sink_ms"):
        assert field in rows[0], field


def test_debug_vars_has_the_collector_counters(server):
    srv, _sink = server()
    srv.start()
    dv = http_api.debug_vars(srv)["gc"]
    assert len(dv["generations"]) == 3
    for gen in dv["generations"]:
        assert set(gen) == {"collections", "collected", "uncollectable"}
    assert len(dv["count"]) == 3 and dv["frozen"] >= 0
    assert dv["enabled"] is True
    # read when asked for: a collection made in between shows
    import gc
    before = dv["generations"][2]["collections"]
    gc.collect()
    after = http_api.debug_vars(srv)["gc"]["generations"][2]["collections"]
    assert after == before + 1
