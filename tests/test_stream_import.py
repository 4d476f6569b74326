"""The V2 stream import on the columnar path (PR 42).

`sources/proxy.py` `send_metrics_v2` takes a stream's messages as raw
bytes, frames them as `MetricList` wire bytes and hands them to
`MetricAggregator.import_payload` in chunks.  The plain reference is
what the handler did before: each message parsed alone and merged by
`MetricAggregator.import_metric`.  Held here: the framing byte for
byte, equality of state with the per-message reference over seeded
mixes, and the stream's guarantees (a)-(e) against a real gRPC server.
"""

from __future__ import annotations

import threading
import time

import grpc
import numpy as np
import pytest

from tests.test_forwarding import _arena_state, _histo, _seeded_digests, _td
from veneur_tpu.forward import convert
from veneur_tpu.protocol import forward_pb2, metric_pb2
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricScope
from veneur_tpu.sources import proxy as proxy_mod
from veneur_tpu.sources.proxy import (GrpcImportServer, StreamChunk,
                                      frame_metric_list)

V2 = "/forwardrpc.Forward/SendMetricsV2"


# -- seeded messages -------------------------------------------------------

def _counter(name, v, tags=("t:1",), type=metric_pb2.Counter):
    return metric_pb2.Metric(name=name, type=type, tags=list(tags),
                             counter=metric_pb2.CounterValue(value=v))


def _gauge(name, v, tags=("zone:a",)):
    return metric_pb2.Metric(name=name, type=metric_pb2.Gauge,
                             tags=list(tags),
                             gauge=metric_pb2.GaugeValue(value=v))


def _set(name, members, tags=("svc:x",), scope=metric_pb2.Mixed,
         precision=14):
    from veneur_tpu.sketches import hll as hll_mod

    sk = hll_mod.HLLSketch(precision)
    for m in members:
        sk.insert(m)
    return metric_pb2.Metric(name=name, type=metric_pb2.Set,
                             tags=list(tags), scope=scope,
                             set=metric_pb2.SetValue(
                                 hyper_log_log=sk.marshal()))


def _markers():
    from veneur_tpu.sketches import compactor as cs
    from veneur_tpu.sketches import moments as mo

    rng = np.random.default_rng(11)
    ms = mo.MomentsSketch()
    ms.add_batch(rng.gamma(2.0, 10.0, 300))
    ck = cs.CompactorSketch()
    ck.add_batch(rng.gamma(2.0, 10.0, 300))
    return [convert.to_pb(sm.ForwardMetric(
                name="mk.m", tags=["a:b"], kind="histogram",
                scope=int(MetricScope.MIXED), moments=ms.vec.tolist())),
            convert.to_pb(sm.ForwardMetric(
                name="mk.c", tags=["a:b"], kind="timer",
                scope=int(MetricScope.MIXED),
                compactor=ck.to_vector().tolist()))]


def _mix(seed: int) -> list:
    """All four wire families, the two sketch-family markers, a dense
    and a sparse set, keys that repeat (cached after their first
    sighting) and a nil-valued message, in a seeded order."""
    rng = np.random.default_rng(seed)
    msgs = _seeded_digests(seed, n_keys=10) + _seeded_digests(seed + 1,
                                                              n_keys=10)
    msgs += [_counter(f"c{i % 4}", i + 1) for i in range(12)]
    msgs += [_gauge(f"g{i % 3}", i / 4) for i in range(9)]
    msgs += [_set("users", [b"u%d" % i for i in range(40)]),
             _set("users", [b"u%d" % i for i in range(20, 90)]),
             _set("big", [b"m%d" % i for i in range(6000)])]
    msgs += _markers()
    msgs.append(metric_pb2.Metric(name="nil", type=metric_pb2.Gauge))
    order = rng.permutation(len(msgs))
    return [msgs[i].SerializeToString() for i in order]


def _case_all_families():
    return _mix(21), 7


def _case_chunk_boundary_inside_a_key_run():
    # one key six times in a row, cut after the 2nd and the 4th
    td = _td([1.0, 5.0, 9.0], [2.0, 1.0, 1.0], min=1.0, max=9.0,
             reciprocalSum=2.3)
    run = [_histo("run", td).SerializeToString()] * 6
    return run + _mix(22)[:5], 2


def _case_unknown_then_cached_keys():
    # the same payload twice: every key of the second pass is cached
    once = _mix(23)
    return once + once, 16


def _case_malformed_message():
    msgs = _mix(24)
    # a length-delimited field that runs past the message's end
    msgs.insert(5, b"\x0a\x7fshort")
    return msgs, 8


def _case_local_scope_message():
    td = _td([2.0, 4.0], [1.0, 3.0], min=2.0, max=4.0, reciprocalSum=1.25)
    local = [_histo("lo", td, scope=metric_pb2.Local),
             _set("lo.s", [b"a", b"b"], scope=metric_pb2.Local)]
    msgs = _mix(25)
    for i, m in enumerate(local):
        msgs.insert(3 + 4 * i, m.SerializeToString())
    return msgs, 6


def _case_set_at_another_precision():
    # python's unmarshal has to look at it (route 7): merged or refused
    # as the per-message path does
    return ([_set("p12", [b"x%d" % i for i in range(30)],
                  precision=12).SerializeToString()] + _mix(26)[:8]), 4


def _case_one_message_chunks():
    return _mix(27), 1


_CASES = {
    "all_families_and_markers": _case_all_families,
    "chunk_boundary_inside_a_key_run": _case_chunk_boundary_inside_a_key_run,
    "unknown_then_cached_keys": _case_unknown_then_cached_keys,
    "malformed_message": _case_malformed_message,
    "local_scope_message": _case_local_scope_message,
    "set_at_another_precision": _case_set_at_another_precision,
    "one_message_chunks": _case_one_message_chunks,
}


# -- the two imports -------------------------------------------------------

def _stream_import(agg, msgs: list, chunk: int) -> tuple:
    """What send_metrics_v2 does with a stream's messages."""
    ok = failed = 0
    for i in range(0, len(msgs), chunk):
        part = msgs[i:i + chunk]
        o, f = agg.import_payload(frame_metric_list(part),
                                  StreamChunk(part, 0, 0, i == 0))
        ok, failed = ok + o, failed + f
    return ok, failed


def _per_message_import(agg, msgs: list) -> tuple:
    """The plain reference: every message parsed and merged alone."""
    ok = failed = 0
    for raw in msgs:
        try:
            agg.import_metric(convert.from_pb(
                metric_pb2.Metric.FromString(raw)))
            ok += 1
        except Exception:
            failed += 1
    return ok, failed


def _scalar_state(arena) -> dict:
    return {"kdict": dict(arena.kdict), "values": arena.values.copy(),
            "touched": arena.touched.copy()}


def _set_state(arena) -> dict:
    arena.sync()
    rows = arena.touched_rows()
    return {"kdict": dict(arena.kdict), "rows": rows.tolist(),
            "regs": arena.host_regs_copy(rows)}


def _state(agg) -> dict:
    out = {"counters": _scalar_state(agg.counters),
           "gauges": _scalar_state(agg.gauges),
           "sets": _set_state(agg.sets)}
    for fam in ("digests", "moments", "compactors"):
        arena = getattr(agg, fam)
        out[fam] = dict(_arena_state(arena), kdict=dict(arena.kdict))
    return out


def _assert_same(got, want, path="") -> None:
    if isinstance(want, dict):
        assert got.keys() == want.keys(), path
        for k in want:
            _assert_same(got[k], want[k], f"{path}.{k}")
    elif isinstance(want, np.ndarray):
        assert got.dtype == want.dtype, path
        assert np.array_equal(got, want,
                              equal_nan=want.dtype.kind == "f"), path
    else:
        assert got == want, path


def _mk_agg():
    import veneur_tpu.ingest as ingest_mod
    from veneur_tpu.core.aggregator import MetricAggregator

    ingest_mod.load_library()   # loud if the engine can't build
    return MetricAggregator(percentiles=[0.5, 0.9])


@pytest.mark.parametrize("case", sorted(_CASES))
def test_stream_import_equals_per_message_import(case):
    """Same rows, same staged centroids and scalars, same counters,
    gauges and set registers, same counts — and the same flush."""
    msgs, chunk = _CASES[case]()
    a, b = _mk_agg(), _mk_agg()
    ok, failed = _stream_import(a, msgs, chunk)
    assert (ok, failed) == _per_message_import(b, msgs)
    assert ok + failed == len(msgs)
    # (import_metric counts a message before its merge can refuse it)
    assert a.imported == ok <= b.imported
    _assert_same(_state(a), _state(b))
    flushes = [sorted((m.name, tuple(m.tags), m.value)
                      for m in agg.flush(is_local=False).metrics)
               for agg in (a, b)]
    assert flushes[0] == flushes[1] and flushes[0]
    # the columnar path engaged for the stream and not for the reference
    assert a.last_flush_segments["import_stream_chunks"] == -(
        -len(msgs) // chunk)
    assert a.last_flush_segments["import_stream_msgs"] == len(msgs)
    assert a.last_flush_segments["import_stream_rpcs"] == 1
    assert b.last_flush_segments["import_stream_chunks"] == 0


@pytest.mark.parametrize("path", ["native_scan", "guard_armed",
                                  "no_native_engine"])
def test_stream_chunk_takes_the_batch_path_of_a_v1_payload(path):
    """With the cardinality guard armed or the native engine absent a
    chunk takes import_pb_batch, as a V1 payload does; the state is the
    per-message reference's on every path."""
    msgs = _mix(31)
    agg, ref = _mk_agg(), _mk_agg()
    calls = {"pb_batch": 0, "import_metric": 0}
    pb_batch, import_metric = agg.import_pb_batch, agg.import_metric

    def counted_pb_batch(*a, **kw):
        calls["pb_batch"] += 1
        return pb_batch(*a, **kw)

    def counted_import_metric(*a, **kw):
        calls["import_metric"] += 1
        return import_metric(*a, **kw)

    agg.import_pb_batch = counted_pb_batch
    agg.import_metric = counted_import_metric
    if path == "guard_armed":
        from veneur_tpu.core.cardinality import CardinalityGuard
        for g in (agg, ref):
            g.cardinality = CardinalityGuard(10_000)
    elif path == "no_native_engine":
        agg._native_import = False
    assert _stream_import(agg, msgs, 9) == _per_message_import(ref, msgs)
    _assert_same(_state(agg), _state(ref))
    assert calls["import_metric"] == 0
    assert calls["pb_batch"] == (0 if path == "native_scan"
                                 else -(-len(msgs) // 9))


def test_type_oneof_disagreement_is_rejected_on_the_stream():
    """The rule the stream follows since PR 42: a record whose `type`
    disagrees with its value oneof is REJECTED (counted failed, landed
    in no family), as on the V1 batch paths.  The per-message path
    merged it by `type`: a counter value into a digest row."""
    td = _td([1.0], [1.0], min=1.0, max=1.0, reciprocalSum=1.0)
    good = [_counter("okc", 5), _gauge("okg", 2.5),
            _histo("okt", td, type=metric_pb2.Timer)]
    bad = [_counter("t.as.c", 9, type=metric_pb2.Timer),
           metric_pb2.Metric(name="s.as.g", type=metric_pb2.Set,
                             gauge=metric_pb2.GaugeValue(value=7.0)),
           _histo("c.as.h", td, type=metric_pb2.Counter)]
    msgs = [m.SerializeToString() for m in good + bad]
    agg = _mk_agg()
    assert _stream_import(agg, msgs, 4) == (len(good), len(bad))
    names = {m.name for m in agg.flush(is_local=False).metrics}
    assert all(any(n.startswith(w) for n in names)
               for w in ("okc", "okg", "okt"))
    assert not any(n.startswith(r) for n in names
                   for r in ("t.as.c", "s.as.g", "c.as.h"))
    # the legacy path lands the first in the digest family instead
    legacy = _mk_agg()
    assert _per_message_import(legacy, msgs)[0] > len(good)


# -- the framing -----------------------------------------------------------

def _framing_cases():
    big = _histo("big", _td(np.arange(1200.0), min=0.0, max=1199.0))
    return {
        "seeded_mix": _mix(41),
        "empty_message": [b"", _counter("c", 1).SerializeToString(), b""],
        "127_128_bytes": [b"\x0a" + bytes([n - 2]) + b"x" * (n - 2)
                          for n in (126, 127, 128, 129)],
        "past_16383_bytes": [big.SerializeToString(),
                             _set("s", [b"m%d" % i for i in range(9000)]
                                  ).SerializeToString()],
        "no_message": [],
    }


@pytest.mark.parametrize("case", sorted(_framing_cases()))
def test_framing_is_the_metric_list_serialization(case):
    msgs = _framing_cases()[case]
    want = forward_pb2.MetricList(metrics=[
        metric_pb2.Metric.FromString(raw) for raw in msgs])
    assert frame_metric_list(msgs) == want.SerializeToString(
        deterministic=True)
    if case == "past_16383_bytes":
        assert max(map(len, msgs)) > 16383
    # and it parses back to the same messages
    back = forward_pb2.MetricList.FromString(frame_metric_list(msgs))
    assert [m.SerializeToString() for m in back.metrics] == [
        metric_pb2.Metric.FromString(raw).SerializeToString()
        for raw in msgs]


# -- the guarantees, against a real gRPC server ----------------------------

class _Global:
    """A GrpcImportServer over a real aggregator, and a plain channel
    that speaks the reference's wire: pre-serialized messages, identity
    serializer, one stream_unary call."""

    def __init__(self, monkeypatch, chunk_msgs=8, max_wait=None,
                 import_delay=0.0):
        if chunk_msgs is not None:
            monkeypatch.setattr(proxy_mod, "STREAM_CHUNK_MSGS", chunk_msgs)
        if max_wait is not None:
            monkeypatch.setattr(proxy_mod, "STREAM_MAX_WAIT_S", max_wait)
            monkeypatch.setattr(proxy_mod, "STREAM_SWEEP_S", max_wait / 5)
        self.agg = _mk_agg()
        self.chunks: list = []      # (time the import returned, messages)
        self.import_metric_calls = 0

        def import_payload(payload, stream=None):
            if import_delay:
                time.sleep(import_delay)
            out = self.agg.import_payload(payload, stream)
            self.chunks.append((time.monotonic(), len(stream.messages)))
            return out

        def import_metric(fm):
            self.import_metric_calls += 1
            self.agg.import_metric(fm)

        self.spans: list = []
        self.srv = GrpcImportServer(
            "127.0.0.1:0", import_metric, import_payload=import_payload,
            trace_hook=lambda *a: self.spans.append(a))
        self.srv.start()
        self.channel = grpc.insecure_channel(f"127.0.0.1:{self.srv.port}")
        self.v2 = self.channel.stream_unary(V2)

    def close(self):
        self.channel.close()
        self.srv.stop()


@pytest.fixture
def make_global(monkeypatch):
    made = []

    def make(**kw):
        made.append(_Global(monkeypatch, **kw))
        return made[-1]

    yield make
    for g in made:
        g.close()


def _wait(cond, timeout=10.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def test_ack_comes_after_every_message_is_imported(make_global):
    """(a) + (b): when the stream's response arrives, `imported` holds
    the stream's count — no wait — and the last chunk's import returned
    before it."""
    g = make_global(chunk_msgs=8, import_delay=0.02)
    msgs = [m.SerializeToString() for m in _seeded_digests(51, n_keys=30)]
    g.v2(iter(msgs), timeout=30)
    t_ack = time.monotonic()
    assert g.agg.imported == len(msgs)
    assert g.srv.imported_count == len(msgs) and g.srv.import_errors == 0
    assert [n for _, n in g.chunks] == [8, 8, 8, 6]
    assert g.chunks[-1][0] <= t_ack


def test_plain_records_never_reach_import_metric(make_global):
    """The stream import is the normal path: with the native engine
    present no plain digest, counter, gauge or scannable set is parsed
    into a ForwardMetric."""
    g = make_global(chunk_msgs=16)
    msgs = [m.SerializeToString() for m in
            _seeded_digests(52, n_keys=20)
            + [_counter(f"c{i}", i) for i in range(10)]
            + [_gauge(f"g{i}", i / 2) for i in range(10)]
            + [_set("users", [b"u%d" % i for i in range(50)]),
               _set("dense", [b"m%d" % i for i in range(6000)])]]
    g.v2(iter(msgs), timeout=30)
    assert g.import_metric_calls == 0
    assert g.agg.imported == len(msgs)
    led = g.agg._ledger
    assert led["import_digest_hits"] + led["import_digest_misses"] == 20
    assert led["set_import_sparse"] + led["set_import_dense"] == 2


def test_a_malformed_message_fails_alone(make_global):
    """(c): counted, its chunk and its stream go on, the ack comes."""
    g = make_global(chunk_msgs=8)
    msgs = [m.SerializeToString() for m in _seeded_digests(53, n_keys=20)]
    msgs.insert(11, b"\x0a\x7fshort")
    g.v2(iter(msgs), timeout=30)
    assert g.agg.imported == 20
    assert (g.srv.imported_count, g.srv.import_errors) == (20, 1)
    assert sum(n for _, n in g.chunks) == 21


def test_an_empty_stream_is_acked(make_global):
    g = make_global()
    g.v2(iter([]), timeout=30)
    assert g.chunks == [] and g.agg.imported == 0
    assert g.srv.stream_stats()["rpcs"] == 0


class _HeldStream:
    """Yields its messages, then holds the stream open until told."""

    def __init__(self, msgs):
        self.msgs, self.release = msgs, threading.Event()
        self.sent_all = 0.0

    def __iter__(self):
        yield from self.msgs
        self.sent_all = time.monotonic()
        self.release.wait(30)


def test_a_stream_cut_midway_imports_what_was_received(make_global):
    """No ack; nothing received is lost."""
    g = make_global(chunk_msgs=8, max_wait=30.0)    # no sweep in the way
    msgs = [m.SerializeToString() for m in _seeded_digests(54, n_keys=13)]
    held = _HeldStream(msgs)
    fut = g.v2.future(iter(held), timeout=30)
    assert _wait(lambda: g.agg.imported == 8)       # the full chunk
    # ... and the other five have arrived and wait for their chunk
    assert _wait(lambda: [len(st.pending)
                          for st in list(g.srv._streams)] == [5])
    assert not fut.done()
    fut.cancel()
    held.release.set()
    assert _wait(lambda: g.agg.imported == 13)
    assert fut.cancelled()
    assert g.srv.imported_count == 13
    assert _wait(lambda: g.srv.stream_stats()["open"] == 0)


def test_an_open_stream_imports_within_the_bound(make_global):
    """(d): a message received on a stream that stays open is imported
    within STREAM_MAX_WAIT_S (plus the import), without a full chunk
    and without the stream's end."""
    bound = 0.2
    g = make_global(chunk_msgs=1000, max_wait=bound)
    msgs = [m.SerializeToString() for m in _seeded_digests(55, n_keys=5)]
    held = _HeldStream(msgs)
    fut = g.v2.future(iter(held), timeout=30)
    assert _wait(lambda: len(g.chunks) == 1, timeout=5.0)
    assert g.agg.imported == 5
    assert not fut.done() and g.srv.stream_stats()["open"] == 1
    # generous for a loaded CI host; the sweep's own arithmetic is exact
    assert g.chunks[0][0] - held.sent_all <= bound + 1.0
    held.release.set()
    fut.result(timeout=10)
    assert g.agg.imported == 5 and len(g.chunks) == 1


def test_the_sweep_waits_out_the_bound(make_global):
    """The sweeper leaves a message alone until it has waited
    STREAM_MAX_WAIT_S - STREAM_SWEEP_S: nothing is imported early."""
    bound = 1.0
    g = make_global(chunk_msgs=1000, max_wait=bound)
    msgs = [m.SerializeToString() for m in _seeded_digests(60, n_keys=3)]
    held = _HeldStream(msgs)
    fut = g.v2.future(iter(held), timeout=30)
    assert _wait(lambda: held.sent_all > 0)
    time.sleep(0.3)
    assert g.chunks == [] and g.agg.imported == 0
    assert _wait(lambda: len(g.chunks) == 1, timeout=5.0)
    assert g.agg.imported == 3
    assert g.chunks[0][0] - held.sent_all >= bound * 0.8 - 0.3
    held.release.set()
    fut.result(timeout=10)


def test_many_streams_at_once_lose_nothing(make_global):
    """(b) under contention: more streams than cores, small chunks, a
    short switch interval — every message is merged once, the counts of
    the handler, the aggregator and the ledger agree."""
    import sys

    g = make_global(chunk_msgs=16)
    msgs = [m.SerializeToString() for m in _seeded_digests(61, n_keys=150)]
    streams, errors = 16, []

    def one():
        try:
            g.v2(iter(msgs), timeout=60)
        except Exception as e:      # noqa: BLE001 - asserted below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=one) for _ in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads) and not errors
    finally:
        sys.setswitchinterval(old)
    total = streams * len(msgs)
    assert g.agg.imported == g.srv.imported_count == total
    assert g.srv.import_errors == 0
    assert sum(n for _, n in g.chunks) == total
    stats = g.srv.stream_stats()
    assert (stats["rpcs"], stats["msgs"], stats["open"]) == (streams,
                                                             total, 0)
    led = g.agg._ledger
    assert led["import_stream_msgs"] == total
    assert led["import_stream_rpcs"] == led["import_rpcs"] == streams
    # 150 keys: one first sighting each, every other digest a cache hit
    assert led["import_digest_misses"] == 150
    assert led["import_digest_hits"] == total - 150


def test_imported_count_is_current_per_chunk(make_global):
    """The handler's totals move with every chunk, not at the stream's
    end (/debug/vars agrees with the aggregator mid-stream)."""
    g = make_global(chunk_msgs=4, max_wait=30.0)
    msgs = [m.SerializeToString() for m in _seeded_digests(56, n_keys=10)]
    held = _HeldStream(msgs)
    fut = g.v2.future(iter(held), timeout=30)
    assert _wait(lambda: g.srv.imported_count == 8)
    assert g.agg.imported == 8 and not fut.done()
    stats = g.srv.stream_stats()
    assert (stats["rpcs"], stats["msgs"], stats["chunks"]) == (1, 8, 2)
    held.release.set()
    fut.result(timeout=10)
    assert g.srv.imported_count == 10


def test_the_v2_span_says_messages_and_chunks(make_global):
    from veneur_tpu.trace import recorder as trace_rec

    g = make_global(chunk_msgs=8)
    msgs = [m.SerializeToString() for m in _seeded_digests(57, n_keys=20)]
    g.v2(iter(msgs), timeout=30,
         metadata=trace_rec.ctx_metadata(0xABC, 0xDEF))
    (ctxs, count, _start, transport, tags), = g.spans
    assert ctxs == [(0xABC, 0xDEF)] and count == 20 and transport == "v2"
    assert tags == {"messages": "20", "chunks": "3"}


@pytest.mark.parametrize("wire", ["v2", "v1"])
def test_without_a_batch_import_each_message_is_merged_alone(wire):
    """A GrpcImportServer given only `import_metric` takes the one
    chunked path too: `_per_metric_payload` stands in for the batch
    import and calls `import_metric` message by message."""
    got = []

    def import_metric(fm):
        if fm.name == "lat.3":
            raise ValueError("refused")
        got.append(fm)

    srv = GrpcImportServer("127.0.0.1:0", import_metric)
    srv.start()
    try:
        with grpc.insecure_channel(f"127.0.0.1:{srv.port}") as ch:
            pbs = _seeded_digests(58, n_keys=6)
            if wire == "v2":
                msgs = [m.SerializeToString() for m in pbs]
                ch.stream_unary(V2)(iter(msgs + [b"\x0a\x7fshort"]),
                                    timeout=30)
            else:
                ch.unary_unary("/forwardrpc.Forward/SendMetrics")(
                    forward_pb2.MetricList(metrics=pbs).SerializeToString(),
                    timeout=30)
        assert [fm.name for fm in got] == [f"lat.{k}" for k in (0, 1, 2, 4, 5)]
        assert (srv.imported_count, srv.import_errors) == \
            (5, 2 if wire == "v2" else 1)
    finally:
        srv.stop()


@pytest.mark.parametrize("by", ["handler", "sweeper"])
def test_an_import_that_raises_fails_its_chunk_alone(make_global, by):
    """A chunk whose import raises is counted failed, message for
    message; its stream goes on to its ack, and the sweeper goes on
    sweeping (guarantee (d) outlives the fault)."""
    g = make_global(chunk_msgs=4 if by == "handler" else 1000,
                    max_wait=30.0 if by == "handler" else 0.1)
    real, raised = g.srv.import_payload, []

    def raises_once(payload, stream=None):
        if not raised:
            raised.append(len(stream.messages))
            raise RuntimeError("the import's fault")
        return real(payload, stream)

    g.srv.import_payload = raises_once
    msgs = [m.SerializeToString() for m in _seeded_digests(61, n_keys=10)]
    if by == "handler":
        g.v2(iter(msgs), timeout=30)            # acked all the same
        assert raised == [4]
        assert (g.srv.imported_count, g.srv.import_errors) == (6, 4)
        assert g.agg.imported == 6
        return
    first = _HeldStream(msgs[:3])
    f1 = g.v2.future(iter(first), timeout=30)
    assert _wait(lambda: g.srv.import_errors == 3, timeout=5.0)
    assert raised == [3] and not f1.done()
    # the sweeper lives: a second open stream's messages are imported
    second = _HeldStream(msgs[3:])
    f2 = g.v2.future(iter(second), timeout=30)
    assert _wait(lambda: g.agg.imported == 7, timeout=5.0)
    assert not g.srv._sweep_task.done()
    first.release.set(), second.release.set()
    f1.result(timeout=10), f2.result(timeout=10)
    assert (g.srv.imported_count, g.srv.import_errors) == (7, 3)
    assert g.srv.stream_stats()["msgs"] == 10


def test_the_sweeper_does_not_wait_for_a_held_import(make_global):
    """One stream's import held up (the aggregator lock, the
    checkpoint's pause gate) does not delay the start of another open
    stream's: the sweeper starts the due imports and awaits none."""
    g = make_global(chunk_msgs=1000, max_wait=0.1)
    real, gate, held = g.srv.import_payload, threading.Event(), []

    def first_is_held(payload, stream=None):
        if not held:
            held.append(1)
            gate.wait(20)
        return real(payload, stream)

    g.srv.import_payload = first_is_held
    msgs = [m.SerializeToString() for m in _seeded_digests(62, n_keys=9)]
    a, b = _HeldStream(msgs[:4]), _HeldStream(msgs[4:])
    fa = g.v2.future(iter(a), timeout=30)
    assert _wait(lambda: held == [1], timeout=5.0)
    fb = g.v2.future(iter(b), timeout=30)
    try:
        assert _wait(lambda: g.agg.imported == 5, timeout=5.0)
    finally:
        gate.set()
    a.release.set(), b.release.set()
    fa.result(timeout=10), fb.result(timeout=10)
    assert g.agg.imported == 9 and g.srv.import_errors == 0


def test_a_stream_is_paced_by_its_window(make_global):
    """The server's HTTP/2 window is fixed (the bandwidth-delay probe is
    off): while a stream's import is held, its sender gets no further
    than STREAM_WINDOW_BYTES (and its own write buffer) ahead of what
    the server took off the stream — a whole interval is not parked in
    the global's heap — and when the import goes on every message
    arrives and is imported."""
    opts = dict(proxy_mod.SERVER_OPTIONS)
    assert opts == {"grpc.http2.bdp_probe": 0,
                    "grpc.http2.lookahead_bytes":
                        proxy_mod.STREAM_WINDOW_BYTES}
    g = make_global(chunk_msgs=8, max_wait=30.0)
    real, gate = g.srv.import_payload, threading.Event()

    def held(payload, stream=None):
        gate.wait(20)
        return real(payload, stream)

    one = _counter("paced", 1, tags=("pad:" + "x" * 4000,)
                   ).SerializeToString()
    n, taken = 1024, []             # 4 MB on a 256 KiB window
    # a first stream flows freely: what a probe would grow its window on
    g.v2(iter([one] * n), timeout=60)
    g.srv.import_payload = held

    def sender():
        for i in range(n):
            taken.append(i)
            yield one

    fut = g.v2.future(sender(), timeout=60)
    try:
        assert _wait(lambda: len(taken) >= 8, timeout=5.0)
        time.sleep(0.5)
        ahead = len(taken) * len(one)
        assert ahead <= 2 * proxy_mod.STREAM_WINDOW_BYTES, ahead
    finally:
        gate.set()
    fut.result(timeout=60)
    assert len(taken) == n
    assert (g.srv.imported_count, g.srv.import_errors) == (2 * n, 0)


def test_the_flush_row_and_debug_vars_carry_the_streams_share():
    """The interval ledger's import_stream_* fields on the timeline
    row, the same totals in /debug/vars, and the stream's scan / lock
    time inside the existing import_* fields."""
    from veneur_tpu import http_api
    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import ChannelMetricSink

    srv = Server(Config(grpc_address="127.0.0.1:0", interval=60.0,
                        statsd_listen_addresses=[]),
                 extra_metric_sinks=[ChannelMetricSink()])
    srv.start()
    try:
        msgs = [m.SerializeToString()
                for m in _seeded_digests(59, n_keys=40)]
        with grpc.insecure_channel(
                f"127.0.0.1:{srv.grpc_import.port}") as ch:
            ch.stream_unary(V2)(iter(msgs), timeout=30)
            ch.stream_unary(V2)(iter(msgs[:7]), timeout=30)
        assert srv.aggregator.imported == 47
        srv.flush()
        row = srv.flush_timeline.snapshot(last=1)[0]
        assert (row["import_stream_rpcs"], row["import_stream_msgs"],
                row["import_stream_chunks"]) == (2, 47, 2)
        assert row["import_rpcs"] == 2 and row["imported"] == 47
        assert row["import_stream_recv_ms"] > 0
        assert row["import_stream_frame_ms"] > 0
        assert row["import_scan_ms"] > 0 and row["import_held_ms"] > 0
        stats = http_api.debug_vars(srv)
        assert stats["import_stream"]["msgs"] == 47
        assert stats["import_stream"]["chunks"] == 2
        assert stats["imported_total"] == 47
        # an interval without a stream says 0, like import_rpcs
        srv.flush()
        assert srv.flush_timeline.snapshot(last=1)[0][
            "import_stream_msgs"] == 0
    finally:
        srv.shutdown()
