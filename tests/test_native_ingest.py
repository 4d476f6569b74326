"""Native ingest engine tests: hash parity, parser parity with the Python
reference implementation, drain application equivalence, intern GC, and the
UDP reader path.

The Python parser (veneur_tpu/samplers/parser.py) is the semantic reference
(itself matching parser.go:349-503 error-for-error); the C++ engine must
stage exactly what the Python chain would have aggregated.
"""

import os
import socket
import time

import numpy as np
import pytest

from veneur_tpu import config as config_mod
from veneur_tpu import ingest as ingest_mod
from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.samplers import parser as parser_mod
from veneur_tpu.samplers.metric_key import MetricScope
from veneur_tpu.sketches import hll as hll_mod
from veneur_tpu.util import tagging


# ---------------------------------------------------------------------------
# metro64 parity
# ---------------------------------------------------------------------------

def test_metro64_matches_python_hash64():
    rng = np.random.default_rng(7)
    cases = [b"", b"a", b"ab", b"abc", b"user@example.com"]
    cases += [bytes(rng.integers(0, 256, n, dtype=np.uint8))
              for n in (1, 2, 3, 4, 7, 8, 9, 15, 16, 17, 31, 32, 33, 64, 100)]
    for m in cases:
        assert ingest_mod.metro64(m) == hll_mod.hash64(m)


# ---------------------------------------------------------------------------
# parser parity
# ---------------------------------------------------------------------------

VALID_LINES = [
    b"a.b.c:1|c",
    b"x:2.5|g",
    b"lat:3.5|h",
    b"lat2:9|d",
    b"t:12|ms",
    b"s1:member|s",
    b"s1:|s",                       # empty set member is legal
    b"multi:1:2:3|c",
    b"rate:10|c|@0.1",
    b"rh:4.5|h|@0.25|#svc:web",
    b"tagged:1|c|#b:2,a:1,c",
    b"scoped:1|h|#veneurlocalonly,x:y",
    b"scoped2:1|h|#x:y,veneurglobalonly",
    b"gauge.rated:7|g|@0.5",
    b"neg:-42.5|g",
    b"exp:1e3|c",
]

INVALID_LINES = [
    b"foo",
    b"foo:1",
    b"foo:1||",
    b"foo:|c|",
    b"bad:nan|g|#shell",
    b"bad:NaN|g",
    b"bad:-inf|g",
    b"bad:+inf|g",
    b"foo:1|foo|",
    b"foo:1|c||",
    b"foo:1|c|foo",
    b"foo:1|c|@-0.1",
    b"foo:1|c|@1.1",
    b"foo:1|c|@0.5|@0.2",
    b"foo:1|c|#foo|#bar",
    b":1|c",
    b"foo:1_0|c",
    b"foo:0x10|c",
]


def python_reference_parse(lines, extend_tags=None):
    """Run lines through the Python parser, returning the staged-sample
    view: {(name, type, joined, scope): [(value_or_member, weight)]}."""
    p = parser_mod.Parser(extend_tags)
    out = {}
    for line in lines:
        try:
            p.parse_metric(line, lambda m: out.setdefault(
                (m.name, m.type, m.joined_tags, m.scope), []).append(
                    (m.value, m.sample_rate)))
        except parser_mod.ParseError:
            pass
    return out


def native_parse(lines, implicit_tags=None):
    eng = ingest_mod.IngestEngine(4096, implicit_tags)
    tid = eng.new_thread()
    eng.ingest(tid, b"\n".join(lines))
    batch = eng.drain()
    eng.close()
    return batch


def test_valid_lines_match_python_parser():
    ref = python_reference_parse(VALID_LINES)
    batch = native_parse(VALID_LINES)
    keys = {k.id: k for k in batch.new_keys}

    got = {}
    for i, kid in enumerate(batch.c_ids):
        k = keys[kid]
        got.setdefault((k.name, "counter", k.joined_tags, k.scope),
                       []).append(batch.c_vals[i])
    for i, kid in enumerate(batch.g_ids):
        k = keys[kid]
        got.setdefault((k.name, "gauge", k.joined_tags, k.scope),
                       []).append(batch.g_vals[i])
    for i, kid in enumerate(batch.h_ids):
        k = keys[kid]
        got.setdefault((k.name, k.mtype, k.joined_tags, k.scope),
                       []).append((batch.h_vals[i], batch.h_wts[i]))
    for i, kid in enumerate(batch.s_ids):
        k = keys[kid]
        got.setdefault((k.name, "set", k.joined_tags, k.scope),
                       []).append(batch.s_hashes[i])

    assert batch.malformed == 0
    for (name, mtype, joined, scope), samples in ref.items():
        gk = (name, mtype, joined, scope)
        assert gk in got, f"missing {gk}"
        if mtype == "counter":
            want = [float(int(v / r)) for v, r in samples]
            assert got[gk] == pytest.approx(want)
        elif mtype == "gauge":
            assert got[gk] == pytest.approx([v for v, _ in samples])
        elif mtype in ("histogram", "timer"):
            want = [(v, 1.0 / r) for v, r in samples]
            assert got[gk] == pytest.approx(want)
        else:  # set: members must hash identically
            want = [hll_mod.hash64(str(v).encode()) for v, _ in samples]
            assert got[gk] == want
    assert len(got) == len(ref)


def test_invalid_lines_counted_not_staged():
    batch = native_parse(INVALID_LINES)
    assert batch.malformed == len(INVALID_LINES)
    assert len(batch.c_ids) == len(batch.g_ids) == len(batch.h_ids) == 0


def test_multi_value_partial_emit():
    # values before a malformed one are kept (parser.py values loop)
    batch = native_parse([b"x:1:2:bad:4|c"])
    assert batch.malformed == 1
    assert batch.c_vals.tolist() == [1.0, 2.0]


def test_implicit_tags_match_python():
    implicit = ["env:prod", "svc:ignored-overrides"]
    lines = [b"m1:1|c|#svc:web,b:2", b"m2:2|g"]
    ref = python_reference_parse(lines, tagging.ExtendTags(implicit))
    batch = native_parse(lines, implicit)
    got = {(k.name, k.joined_tags) for k in batch.new_keys}
    assert got == {(name, joined) for (name, _, joined, _) in ref}


def test_events_and_service_checks_punted():
    batch = native_parse([b"_e{5,4}:title|text", b"_sc|svc|0|m:ok"])
    assert batch.other == [b"_e{5,4}:title|text", b"_sc|svc|0|m:ok"]
    assert batch.processed == 0


# ---------------------------------------------------------------------------
# drain application equivalence
# ---------------------------------------------------------------------------

PACKETS = [
    b"api.latency:3.5|h|#svc:web\napi.latency:9.1|h|#svc:web",
    b"reqs:17|c\nreqs:3|c|@0.5",
    b"cpu:64|g\ncpu:70|g",
    b"users:u1|s\nusers:u2|s\nusers:u1|s",
    b"g.only:5|h|#veneurglobalonly",
    b"l.only:5|h|#veneurlocalonly",
    b"rate.hist:1:2:3|ms|@0.25",
]


def flush_view(agg, is_local):
    res = agg.flush(is_local=is_local, now=1234)
    metrics = sorted((m.name, tuple(m.tags), m.type, round(m.value, 9))
                     for m in res.metrics)
    fwd = sorted((f.name, tuple(f.tags), f.kind, int(f.scope),
                  round(f.digest_sum or 0, 6),
                  round(sum(f.digest_weights or []), 6),
                  f.counter_value, round(f.gauge_value or 0, 6))
                 for f in res.forward)
    return metrics, fwd


@pytest.mark.parametrize("is_local", [True, False])
def test_native_drain_equals_python_path(is_local):
    pct = [0.5, 0.99]

    agg_py = MetricAggregator(percentiles=pct)
    p = parser_mod.Parser()
    for pkt in PACKETS:
        for line in pkt.split(b"\n"):
            p.parse_metric(line, agg_py.process_metric)

    agg_nat = MetricAggregator(percentiles=pct)
    nat = ingest_mod.NativeIngest(agg_nat)
    tid = nat.engine.new_thread()
    for pkt in PACKETS:
        nat.engine.ingest(tid, pkt)
    nat.drain_into()
    nat.close()

    assert agg_py.processed == agg_nat.processed
    m_py, f_py = flush_view(agg_py, is_local)
    m_nat, f_nat = flush_view(agg_nat, is_local)
    assert m_nat == m_py
    assert f_nat == f_py


def test_unique_timeseries_counted_on_drain():
    agg = MetricAggregator(count_unique_timeseries=True, is_local=False)
    nat = ingest_mod.NativeIngest(agg)
    tid = nat.engine.new_thread()
    for i in range(50):
        nat.engine.ingest(tid, b"m%d:1|c" % (i % 10))
    nat.drain_into()
    res = agg.flush(is_local=False)
    nat.close()
    assert res.unique_ts == pytest.approx(10, abs=1)


def test_intern_gc_reset_preserves_samples_and_identity():
    agg = MetricAggregator()
    nat = ingest_mod.NativeIngest(agg)
    tid = nat.engine.new_thread()
    nat.engine.ingest(tid, b"k1:1|c\nk2:5|c")
    nat.reset_interning()          # applies the staged batch, then clears
    assert nat.engine.intern_count() == 0
    nat.engine.ingest(tid, b"k1:2|c\nk3:7|c")  # k1 re-interns under new id
    batch = nat.drain_into()
    # id space restarts at 0 after GC so the Python cache stays bounded
    assert min(k.id for k in batch.new_keys) == 0
    res = agg.flush(is_local=False)
    nat.close()
    by = {m.name: m.value for m in res.metrics}
    assert by == {"k1": 3.0, "k2": 5.0, "k3": 7.0}


def test_row_gc_revalidation():
    """A row recycled by arena idle-GC must re-upsert, not scribble on a
    stranger's row."""
    from veneur_tpu.core import arena as arena_mod

    agg = MetricAggregator()
    nat = ingest_mod.NativeIngest(agg)
    tid = nat.engine.new_thread()
    nat.engine.ingest(tid, b"gc.me:1|c")
    nat.drain_into()
    agg.flush(is_local=False)
    # idle long enough for the row to be collected
    for _ in range(arena_mod.IDLE_GC_INTERVALS + 1):
        agg.flush(is_local=False)
    # a different key takes the freed row, then the old id comes back
    agg.process_metric(parse_one(b"squatter:9|c"))
    nat.engine.ingest(tid, b"gc.me:4|c")
    nat.drain_into()
    res = agg.flush(is_local=False)
    nat.close()
    by = {m.name: m.value for m in res.metrics}
    assert by["gc.me"] == 4.0
    assert by["squatter"] == 9.0


def parse_one(line):
    out = []
    parser_mod.Parser().parse_metric(line, out.append)
    return out[0]


# ---------------------------------------------------------------------------
# stage counters (profiling subsystem: recvmmsg/parse/intern/stage/drain)
# ---------------------------------------------------------------------------

def test_stage_counters_conserve_and_stay_monotonic():
    """Per-stage counters must reconcile with the engine's own totals:
    parse packets == datagrams ingested, staged values == processed,
    intern calls == metric lines that reached interning — and every
    counter is monotonic across drains (including an intern-clearing
    GC drain)."""
    eng = ingest_mod.IngestEngine(4096)
    tid = eng.new_thread()
    reps = 3
    for _ in range(reps):
        eng.ingest(tid, b"\n".join(VALID_LINES))
    batch = eng.drain()
    st = eng.stage_stats()
    tot = st["totals"]
    # one vn_ingest call per rep == one datagram each
    assert tot["parse"]["packets"] == reps == batch.packets
    assert tot["stage"]["values"] == batch.processed
    # every VALID_LINE interns exactly once (multi-value lines intern
    # once; none of these are events/service checks)
    assert tot["intern"]["calls"] == reps * len(VALID_LINES)
    assert tot["drain"]["calls"] == 1
    assert tot["drain"]["packets"] == reps
    # a vn_ingest-fed thread never touches recvmmsg
    assert tot["recvmmsg"]["packets"] == 0
    for stage in ("parse", "intern", "stage", "drain"):
        assert tot[stage]["ns"] > 0, f"{stage} accrued no time"

    # malformed lines and punted events still count parse packets but
    # stage no values
    eng.ingest(tid, b"\n".join(INVALID_LINES))
    eng.ingest(tid, b"_e{5,4}:title|text")
    batch2 = eng.drain(clear_intern=True)     # GC drain keeps counting
    assert batch2.processed == 0
    st2 = eng.stage_stats()
    tot2 = st2["totals"]
    assert tot2["parse"]["packets"] == reps + 2
    assert tot2["stage"]["values"] == tot["stage"]["values"]
    assert tot2["drain"]["calls"] == 2
    # monotonicity: nothing ever decreases, drain included
    for stage, counters in tot2.items():
        for k, v in counters.items():
            assert v >= tot[stage][k], f"{stage}.{k} went backwards"
    # engine-total reconciliation after all drains
    processed, malformed, packets, _ = eng.totals()
    assert tot2["parse"]["packets"] == packets
    assert tot2["stage"]["values"] == processed
    assert tot2["drain"]["packets"] == packets
    assert malformed == len(INVALID_LINES)
    eng.close()


def test_stage_counters_cover_udp_reader_path():
    """recvmmsg accounting: packets received by the C++ reader loop show
    up in both the recvmmsg and parse stages, reconciling with the
    drained totals."""
    agg = MetricAggregator()
    nat = ingest_mod.NativeIngest(agg)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    addr = sock.getsockname()
    nat.engine.add_udp_reader(sock.fileno())

    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    for _ in range(100):
        tx.sendto(b"stg.udp:1|c\nstg.lat:5|ms", addr)
    tx.close()
    deadline = time.time() + 5.0
    while time.time() < deadline and agg.processed < 200:
        time.sleep(0.05)
        nat.drain_into()
    nat.stop()
    sock.close()
    nat.drain_into()   # consolidate the tail so totals cover every packet
    st = nat.stage_stats()
    tot = st["totals"]
    _, _, packets, _ = nat.engine.totals()
    assert packets > 0
    assert tot["recvmmsg"]["packets"] == packets
    assert tot["parse"]["packets"] == packets
    assert tot["drain"]["packets"] == packets
    assert tot["stage"]["values"] == 2 * packets  # two lines per packet
    # recvmmsg time includes the poll wait, so it accrues regardless;
    # parse must have accrued real work too
    assert tot["recvmmsg"]["ns"] > 0 and tot["parse"]["ns"] > 0
    # the reader thread appears in the per-thread view
    assert any(t["recvmmsg"]["packets"] == packets for t in st["threads"])
    nat.close()
    assert nat.stage_stats() is None  # safe after teardown


# ---------------------------------------------------------------------------
# UDP reader path (end-to-end through a real socket)
# ---------------------------------------------------------------------------

def test_native_udp_reader_end_to_end():
    agg = MetricAggregator()
    nat = ingest_mod.NativeIngest(agg)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    addr = sock.getsockname()
    nat.engine.add_udp_reader(sock.fileno())

    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    for _ in range(200):
        tx.sendto(b"udp.native:1|c\nudp.lat:5|ms", addr)
    tx.close()

    deadline = time.time() + 5.0
    total = 0
    while time.time() < deadline and total < 400:
        time.sleep(0.05)
        nat.drain_into()
        total = agg.processed
    nat.stop()
    sock.close()
    res = agg.flush(is_local=False)
    nat.close()
    by = {m.name: m.value for m in res.metrics}
    assert by["udp.native"] == 200.0
    assert by["udp.lat.count"] == 200.0


def test_blast_udp_sender():
    """The benchmark sender delivers packets the engine can parse."""
    agg = MetricAggregator()
    nat = ingest_mod.NativeIngest(agg)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4 << 20)
    addr = sock.getsockname()
    nat.engine.add_udp_reader(sock.fileno())

    sent = ingest_mod.blast_udp(addr[0], addr[1], 500,
                                [b"blast:1|c", b"blast:2|c\nblast.h:3|h"])
    assert sent == 500
    deadline = time.time() + 5.0
    while time.time() < deadline:
        time.sleep(0.05)
        nat.drain_into()
        _, _, packets, _ = nat.engine.totals()
        if packets >= sent * 0.9:  # loopback may shed under pressure
            break
    nat.stop()
    sock.close()
    res = agg.flush(is_local=False)
    nat.close()
    by = {m.name: m.value for m in res.metrics}
    assert by["blast"] > 0


def test_intern_key_no_separator_aliasing():
    """Names/tags containing 0x1F must not alias distinct identities
    (length-prefixed intern keys)."""
    batch = native_parse([b"a\x1f0\x1fb:1|c|#c", b"a:2|c|#b\x1f0\x1fc"])
    names = sorted((k.name, k.joined_tags) for k in batch.new_keys)
    assert names == [("a", "b\x1f0\x1fc"), ("a\x1f0\x1fb", "c")]
    assert len(batch.c_ids) == 2 and len(set(batch.c_ids)) == 2


def test_blast_udp_empty_payloads():
    assert ingest_mod.blast_udp("127.0.0.1", 1, 10, []) == 0


def test_reference_vectors_cross_path():
    """Vectors lifted from the reference's parser_test.go matrix: both
    paths accept/reject identically, and raw tag ORDER canonicalizes to
    one identity (UpdateTags sorts, parser.go:44-61)."""
    valid = [
        b"a.b.c:0.1716441474854946|d|#filter:flatulent",
        b"a.b.c:1.234|ms",
        b"a.b.c:1:2:3:4|ms|@0.1|#result:success,op:frob",
        b"a.b.c:1|c|#",                  # empty tag section is legal
        b"a.b.c:1|c|#baz:gorch,foo:bar",
        b"a.b.c:1|c|@0.1|#foo:bar,baz:gorch",
        b"a.b.c:1|h|#veneurglobalonly,tag2:quacks",
        b"a.b.c:1|h|#veneurlocalonly,tag2:quacks",
        b"a.b.c:foo|s",
    ]
    invalid = [b"a.b.c:fart|c", b"foo.bar|0", b"_sc"]
    ref = python_reference_parse(valid + invalid)
    batch = native_parse(valid + invalid)
    # same accept count (per metric value) and same reject count
    n_ref = sum(len(v) for v in ref.values())
    assert batch.processed == n_ref
    # "_sc" punts to the slow path (service-check prefix), the other two
    # are malformed metric lines
    assert batch.malformed == 2
    assert batch.other == [b"_sc"]
    # tag order canonicalization: both orderings intern to ONE identity
    keys = {(k.name, k.joined_tags) for k in batch.new_keys
            if k.mtype == "counter" and k.joined_tags}
    assert ("a.b.c", "baz:gorch,foo:bar") in keys
    # both raw orderings canonicalize to the same joined identity (the
    # engine interns raw bytes, so two ids may exist; the Python drain
    # dedupes them onto one arena row via the canonical MetricKey)
    orderings = [k for k in batch.new_keys
                 if k.mtype == "counter"
                 and k.joined_tags == "baz:gorch,foo:bar"]
    assert len(orderings) == 2
    agg = MetricAggregator()
    nat = ingest_mod.NativeIngest(agg)
    tid = nat.engine.new_thread()
    nat.engine.ingest(tid, b"a.b.c:1|c|#baz:gorch,foo:bar")
    nat.engine.ingest(tid, b"a.b.c:2|c|#foo:bar,baz:gorch")
    nat.drain_into()
    res = agg.flush(is_local=False)
    nat.close()
    assert [round(m.value, 6) for m in res.metrics
            if m.name == "a.b.c"] == [3.0]  # ONE row, summed


def test_native_dense_fill_matches_numpy_builder():
    """The native dense build (vn_build_tiers, through
    DigestArena.build_dense) must produce the numpy builder's operand:
    the same per-row depth counts, every row's values in arrival order,
    the same casts — bit for bit, for both the uniform and weighted
    forms."""
    import numpy as np

    from veneur_tpu.core import arena as arena_mod

    # load the native library LOUDLY first: if it cannot build, this
    # test must fail, not silently compare numpy against numpy
    import veneur_tpu.ingest as ingest_mod
    ingest_mod.load_library()

    rng = np.random.default_rng(7)
    n_keys = 3000
    a = arena_mod.DigestArena(capacity=1 << 12)
    touched = np.arange(n_keys, dtype=np.int64)
    a.touched[touched] = True
    # ragged depths, shuffled arrival order
    reps = rng.integers(1, 9, n_keys)
    staged_rows = np.repeat(touched, reps)
    perm = rng.permutation(len(staged_rows))
    staged_rows = staged_rows[perm]
    vals = rng.gamma(2.0, 10.0, len(staged_rows))
    wts = rng.integers(1, 5, len(staged_rows)).astype(np.float64)
    d_min = np.zeros(n_keys)
    d_max = np.full(n_keys, 1e3)

    for uniform in (True, False):
        w_in = np.ones_like(wts) if uniform else wts
        staged = (staged_rows, vals, w_in)
        got, = a.build_dense(staged, touched, d_min, d_max,
                             uniform=uniform)
        assert a.take_build_stats()["onepass"] == 1   # the native call
        a.hold_dense([])
        want = a.build_dense_numpy(staged, touched, d_min, d_max,
                                   uniform=uniform)
        assert got[0].shape == (4096, 8)
        assert (got[2] is None) == uniform
        for g, w in zip(got, want):
            if w is not None:
                assert g.dtype == w.dtype and g.shape == w.shape
                assert np.array_equal(g, w)
        counts = np.bincount(staged_rows, minlength=n_keys)
        if uniform:
            assert np.array_equal(
                np.asarray(got[1][:n_keys], np.int64), counts)
        for row in rng.integers(0, n_keys, 50):
            assert np.array_equal(
                got[0][row][:counts[row]],
                vals[staged_rows == row].astype(np.float32))


# ---------------------------------------------------------------------------
# SIMD dispatch parity + SPSC staging (round 19)
# ---------------------------------------------------------------------------

def _simd_modes_under_test():
    return [m for m in ("sse2", "avx2") if ingest_mod.simd_supported(m)]


def _parity_corpus(seed=0xC0FFEE):
    """Seeded fuzz corpus: well-formed lines across every metric family,
    truncations at random offsets, single bit-flips, and degenerate tag
    sections.  Deterministic, so every engine under test sees identical
    bytes."""
    rng = np.random.default_rng(seed)
    corpus = [
        b"par.d1:1|c|#", b"par.d2:2|c|#,,", b"par.d3:3|g|#:,x:",
        b"par.d4:4|ms|@0.5|#a:b,a:b", b"par.d5:1:2:3|h|#t:u",
        b"par.d6:nan|g", b"par.d7:+1e3|c", b"par.d8:1_0|c",
        b":|", b"a:|c", b"par.d9:1|q", b"", b"\n\n", b"#only:tags",
        b"par.d10:1|c|@", b"par.d11:1|",
    ]
    types = [b"c", b"g", b"h", b"ms", b"d", b"s"]
    for i in range(150):
        line = b"par.m%d:%d|%s|#k%d:v%d,env:prod\npar.x:%d|ms|@0.25" % (
            rng.integers(37), rng.integers(100000),
            types[rng.integers(len(types))], rng.integers(11),
            rng.integers(13), rng.integers(997))
        corpus.append(line)
        corpus.append(line[:rng.integers(len(line) + 1)])      # truncation
        flip = bytearray(line)
        flip[rng.integers(len(flip))] ^= 1 << rng.integers(8)  # bit flip
        corpus.append(bytes(flip))
    return corpus


def _drain_fingerprint(batch):
    return (
        batch.c_ids.tobytes(), batch.c_vals.tobytes(),
        batch.g_ids.tobytes(), batch.g_vals.tobytes(),
        batch.h_ids.tobytes(), batch.h_vals.tobytes(),
        batch.h_wts.tobytes(), batch.s_ids.tobytes(),
        batch.s_hashes.tobytes(),
        [(k.id, k.mtype, k.scope, k.name, k.joined_tags)
         for k in batch.new_keys],
        batch.other, batch.processed, batch.malformed, batch.packets,
        batch.too_long,
    )


def test_simd_scalar_drain_parity_fuzz():
    """The SIMD tokenizer must be a pure speedup: identical fuzz bytes
    through a scalar engine and each supported SIMD engine drain
    byte-for-byte the same — same intern ids in the same order, same
    staged values/weights, same rejects and punted lines."""
    modes = _simd_modes_under_test()
    if not modes:
        pytest.skip("no SIMD mode supported on this host")
    corpus = _parity_corpus()
    for mode in modes:
        engines = [ingest_mod.IngestEngine(4096, simd="scalar"),
                   ingest_mod.IngestEngine(4096, simd=mode)]
        fps = []
        for eng in engines:
            tid = eng.new_thread()
            for dgram in corpus:
                eng.ingest(tid, dgram)
            fps.append(_drain_fingerprint(eng.drain()))
            assert eng.drain().empty  # fully drained
            eng.close()
        assert fps[0] == fps[1], f"scalar vs {mode} drains diverge"


def test_key_hash_parity_all_modes():
    """Intern-key lane hash: scalar/SSE2/AVX2 must compute the identical
    function at every length that straddles the 16B/32B vector tails."""
    rng = np.random.default_rng(11)
    for n in list(range(0, 70)) + [127, 128, 129, 160]:
        data = bytes(rng.integers(0, 256, n, dtype=np.uint8))
        ref = ingest_mod.key_hash(data, "scalar")
        for mode in _simd_modes_under_test():
            assert ingest_mod.key_hash(data, mode) == ref, (mode, n)


def test_scan_tokens_parity_and_reference():
    """Tokenizer: every mode must report exactly the '\\n' ':' '|'
    positions, in order, for random bytes (which naturally contain the
    delimiters) and for real statsd lines."""
    rng = np.random.default_rng(13)
    delims = {0x0A: "\n", 0x3A: ":", 0x7C: "|"}
    samples = [bytes(rng.integers(0, 256, n, dtype=np.uint8))
               for n in (0, 1, 15, 16, 17, 31, 32, 33, 63, 64, 65, 200)]
    samples += [b"a.b:1|c|#t:v\nx:2|g", b":::|||", b"\n" * 40]
    for data in samples:
        ref = [(i, delims[b]) for i, b in enumerate(data) if b in delims]
        assert ingest_mod.scan_tokens(data, "scalar") == ref
        for mode in _simd_modes_under_test():
            assert ingest_mod.scan_tokens(data, mode) == ref, mode


def test_conservation_under_concurrent_drain():
    """Packets must be conserved exactly while drains race the
    producers: every datagram ingested is returned by exactly one
    drain (the SPSC handoff loses nothing, duplicates nothing)."""
    import threading

    eng = ingest_mod.IngestEngine(4096, batch=4, ring_slots=4)
    n_threads, n_iters = 3, 4000
    drained = []
    drained_lock = threading.Lock()
    stop = threading.Event()

    def produce(tid, t):
        for i in range(n_iters):
            eng.ingest(tid, b"spsc.m%d:%d|c|#thr:%d" % (i % 29, i, t))

    def drain_loop():
        while not stop.is_set():
            pkts = eng.drain().packets
            with drained_lock:
                drained.append(pkts)

    tids = [eng.new_thread() for _ in range(n_threads)]
    workers = [threading.Thread(target=produce, args=(tids[t], t))
               for t in range(n_threads)]
    drainers = [threading.Thread(target=drain_loop) for _ in range(2)]
    for th in workers + drainers:
        th.start()
    for th in workers:
        th.join()
    stop.set()
    for th in drainers:
        th.join()
    drained.append(eng.drain().packets)  # consolidate the tail
    want = n_threads * n_iters
    assert sum(drained) == want
    assert eng.totals()[2] == want
    eng.close()


def test_ring_wraparound_single_thread():
    """A 2-slot staging ring with batch=1 forces constant ring-full
    backpressure; the producer-side accumulate path must not drop."""
    eng = ingest_mod.IngestEngine(4096, batch=1, ring_slots=2)
    tid = eng.new_thread()
    for i in range(500):
        eng.ingest(tid, b"wrap:%d|c" % i)
    batch = eng.drain()
    assert batch.packets == 500 and batch.processed == 500
    assert len(batch.c_ids) == 500
    eng.close()


def test_engine_option_validation():
    """Unknown option keys and unsupported explicit SIMD modes must be
    rejected loudly, never silently downgraded."""
    eng = ingest_mod.IngestEngine(4096)
    with pytest.raises(ValueError):
        eng._set_opt("no_such_knob", 1)
    with pytest.raises(ValueError):
        eng._set_opt("simd", 99)
    eng.close()
    with pytest.raises(KeyError):
        ingest_mod.IngestEngine(4096, simd="neon")
    assert ingest_mod.simd_supported("scalar")
    for mode in ("sse2", "avx2"):
        if not ingest_mod.simd_supported(mode):
            with pytest.raises(ValueError):
                ingest_mod.IngestEngine(4096, simd=mode)
    # resolved dispatch is reported by name
    eng = ingest_mod.IngestEngine(4096, simd="scalar")
    assert eng.simd_mode() == "scalar"
    eng.close()
    eng = ingest_mod.IngestEngine(4096)
    assert eng.simd_mode() in ("scalar", "sse2", "avx2")
    eng.close()


def test_reader_backend_forced_recvmmsg():
    """backend="recvmmsg" must pin the reader loop to the portable
    syscall path and report it via reader_backend()."""
    eng = ingest_mod.IngestEngine(4096, backend="recvmmsg")
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    tid = eng.add_udp_reader(sock.fileno())
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send.sendto(b"rb:1|c", ("127.0.0.1", port))
    deadline = time.time() + 5.0
    got = 0
    while got < 1 and time.time() < deadline:
        time.sleep(0.01)
        got += eng.drain().packets  # totals update at drain
    assert eng.reader_backend(tid) == "recvmmsg"
    assert got >= 1
    eng.stop()
    send.close()
    sock.close()
    eng.close()


def test_server_refuses_to_start_without_buildable_engine(tmp_path,
                                                          monkeypatch):
    """`native_ingest: true` with an engine that cannot be built is a
    BOOT ERROR carrying the compiler's words — never a warning and a
    silent Python packet path.  `native_ingest: false` still boots."""
    from veneur_tpu.core.server import Server

    broken = tmp_path / "ingest_engine.cpp"
    broken.write_text("this is not C++;\n")
    monkeypatch.setattr(ingest_mod, "_SRC", str(broken))
    monkeypatch.setattr(ingest_mod, "_SO",
                        str(tmp_path / ".build" / "libvningest.so"))
    monkeypatch.setattr(ingest_mod, "_lib", None)
    cfg = dict(statsd_listen_addresses=["udp://127.0.0.1:0"],
               interval=600.0, hostname="t")
    srv = Server(config_mod.Config(native_ingest=True, **cfg))
    try:
        with pytest.raises(RuntimeError, match="build failed"):
            srv.start()
        assert srv.native is None and not srv.statsd_addrs
    finally:
        srv.shutdown()
    srv = Server(config_mod.Config(native_ingest=False, **cfg))
    try:
        srv.start()
        assert srv.native is None and srv.statsd_addrs
    finally:
        srv.shutdown()
