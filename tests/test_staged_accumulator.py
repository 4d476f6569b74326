"""The staged accumulator (ISSUE 31): a histogram arena keeps an
interval's consolidated staged points in ONE growing buffer that the
drain ticks' sync() fills, so take_staged() under the aggregator lock is
a hand-off and joins nothing.

(a) ORDER — whatever mix of sample / sample_batch / merge_digest /
    merge_digest_batch staged the points and however many sync() calls
    fell between them, take_staged() returns the parent commit's
    concatenation bit for bit (per sync: the list-staged points, then
    the chunks, each in arrival order; syncs in order), and build_dense
    over it is bit-equal, uniform and weighted.  `_expected` below is
    that rule written out; `_PartsList` is the parent's data structure
    (a list of per-sync parts joined at the cut) under the same arena
    code, for the paths whose expectation is a program (pre-reduce,
    checkpoint / restore, the moments and compactor folds).
(b) OWNERSHIP — the triple a part took never aliases the arena's live
    buffer; `snapshot_part()` columns own their memory with the second
    `.copy()` gone.
(c) The ledger — `staged_points`, `staged_cut_copy_bytes`,
    `staged_regrows` on the flush timeline row and in /debug/vars.
"""

import numpy as np
import pytest

from veneur_tpu import config as config_mod
from veneur_tpu import http_api
from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import (ROW_ONLY_SEGMENT_KEYS,
                                        STAGED_LEDGER_KEYS,
                                        MetricAggregator)
from veneur_tpu.core.server import Server
from veneur_tpu.samplers.metric_key import (MetricKey, MetricScope,
                                            UDPMetric)
from veneur_tpu.sinks import simple as simple_sinks

N_ROWS = 48
ARENAS = {"digest": arena_mod.DigestArena,
          "moments": arena_mod.MomentsArena,
          "compactor": arena_mod.CompactorArena}


class _PartsList:
    """The parent commit's accumulator behind _StagedPoints' interface:
    every sync() appends its tick's arrays as one part and nothing joins
    them until somebody asks (np.concatenate, at the cut)."""

    def __init__(self):
        self.parts = []
        self.regrows = self.copied_bytes = 0

    @property
    def n(self):
        return sum(len(p[0]) for p in self.parts)

    def extend(self, k):
        part = (np.empty(k, np.int64), np.empty(k), np.empty(k))
        self.parts.append(part)
        return part

    def views(self):
        if not self.parts:
            z = np.zeros(0)
            return z.astype(np.int64), z, z
        if len(self.parts) > 1:
            self.parts = [tuple(np.concatenate([p[i] for p in self.parts])
                                for i in range(3))]
        return self.parts[0]

    def replace(self, rows, vals, wts):
        self.parts = [(np.asarray(rows, np.int64),
                       np.asarray(vals, np.float64),
                       np.asarray(wts, np.float64))]

    def take(self):
        out = self.views()
        self.parts = []
        return out


def _arena(family, parent=False, capacity=64):
    ar = ARENAS[family](capacity=capacity)
    for i in range(N_ROWS):
        ar.row_for(MetricKey(f"k{i}", "histogram", ""), MetricScope.MIXED,
                   [])
    if parent:
        ar._acc = _PartsList()
    return ar


def _ops(seed, n_syncs, weighted, calls=40, scale=1):
    """A seeded interleaving of the four staging calls with `n_syncs`
    sync() calls spread through it (the last one closes it, as the
    snapshot's own does)."""
    rng = np.random.default_rng(seed)
    ops = []
    for _ in range(calls):
        kind = rng.integers(4)
        if kind == 0:
            ops.append(("sample", int(rng.integers(N_ROWS)),
                        float(rng.gamma(2.0, 10.0)),
                        float(rng.choice([0.5, 0.25])) if weighted
                        else 1.0))
        elif kind == 1:
            n = int(rng.integers(1, 40)) * scale
            ops.append(("sample_batch",
                        rng.integers(N_ROWS, size=n).astype(np.int32),
                        rng.gamma(2.0, 10.0, n).astype(np.float32),
                        (rng.choice([1.0, 2.0, 4.0], n) if weighted
                         else np.ones(n))))
        elif kind == 2:
            n = int(rng.integers(1, 6))
            means = np.sort(rng.gamma(2.0, 10.0, n))
            ops.append(("merge_digest", int(rng.integers(N_ROWS)),
                        means.tolist(),
                        (rng.integers(1, 9, n).astype(float).tolist()
                         if weighted else [1.0] * n),
                        float(means[0]), float(means[-1]),
                        float((1.0 / means).sum())))
        else:
            d = int(rng.integers(1, 12)) * scale
            counts = rng.integers(1, 5, d)
            n = int(counts.sum())
            means = rng.gamma(2.0, 10.0, n)
            ops.append(("merge_digest_batch",
                        rng.integers(N_ROWS, size=d).astype(np.int64),
                        counts, means,
                        (rng.integers(1, 9, n).astype(float) if weighted
                         else np.ones(n)),
                        rng.gamma(2.0, 1.0, d), rng.gamma(2.0, 50.0, d),
                        rng.gamma(2.0, 1.0, d)))
    at = set(np.linspace(0, calls, n_syncs + 1)[1:].astype(int).tolist())
    out = []
    for i, op in enumerate(ops, 1):
        out.append(op)
        if i in at:
            out.append(("sync",))
    return out


def _for(family, ops):
    """The ops a family's arena takes: forwarded centroids reach only
    the digest family (the vector families import wire vectors)."""
    if family == "digest":
        return ops
    return [op for op in ops if not op[0].startswith("merge_digest")]


def _apply(ar, ops):
    for op in ops:
        getattr(ar, op[0])(*op[1:])


def _expected(ops):
    """The parent's concatenation, from the ops alone."""
    acc = ([], [], [])
    lists, chunks = ([], [], []), []

    def sync():
        for col, staged in zip(acc, lists):
            col.extend(staged)
            staged.clear()
        for chunk in chunks:
            for col, arr in zip(acc, chunk):
                col.extend(np.asarray(arr).tolist())
        chunks.clear()

    for op in ops:
        if op[0] == "sample":
            _, row, value, rate = op
            for col, x in zip(lists, (row, value, 1.0 / rate)):
                col.append(x)
        elif op[0] == "merge_digest":
            _, row, means, weights = op[:4]
            lists[0].extend([row] * len(means))
            lists[1].extend(means)
            lists[2].extend(weights)
        elif op[0] == "sample_batch":
            chunks.append(op[1:4])
        elif op[0] == "merge_digest_batch":
            _, rows, counts, means, weights = op[:5]
            chunks.append((np.repeat(rows, counts), means, weights))
        else:
            sync()
    return (np.asarray(acc[0], np.int64), np.asarray(acc[1], np.float64),
            np.asarray(acc[2], np.float64))


def _same(got, want):
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _dense(ar, staged, uniform):
    touched = np.unique(staged[0])
    return ar.build_dense(staged, touched, ar.d_min[touched],
                          ar.d_max[touched], uniform=uniform)[0]


def _same_dense(a, b):
    for x, y in zip(a, b):
        assert (x is None) == (y is None)
        if x is not None:
            assert x.dtype == y.dtype and x.shape == y.shape
            assert x.tobytes() == y.tobytes()


# -- (a) the order is the parent's -------------------------------------------

@pytest.mark.parametrize("weighted", [False, True],
                         ids=["uniform", "weighted"])
@pytest.mark.parametrize("n_syncs", [1, 3, 10])
def test_take_staged_is_the_parents_concatenation(n_syncs, weighted):
    ops = _ops(31 + n_syncs, n_syncs, weighted)
    ar = _arena("digest")
    _apply(ar, ops)
    assert ar.staged_uniform is (not weighted)
    staged = ar.take_staged()
    want = _expected(ops)
    _same(staged, want)
    assert ar.snapshot_staged_points == len(want[0]) > 0
    for a in staged:
        # build_dense's np.ascontiguousarray stays a no-op
        assert a.flags.c_contiguous
        assert np.ascontiguousarray(a) is a
    twin = _arena("digest", parent=True)
    _apply(twin, ops)
    _same(twin.take_staged(), want)
    _same_dense(_dense(ar, staged, not weighted),
                _dense(twin, want, not weighted))


@pytest.mark.parametrize("family", ["moments", "compactor"])
@pytest.mark.parametrize("n_syncs", [1, 3, 10])
def test_vector_families_take_the_parents_concatenation(family, n_syncs):
    ops = _for(family, _ops(47 + n_syncs, n_syncs, True))
    ar = _arena(family)
    _apply(ar, ops)
    _same(ar.take_staged(), _expected(ops))


# -- (b) the part owns what it took ------------------------------------------

@pytest.mark.parametrize("family", sorted(ARENAS))
def test_a_second_interval_leaves_the_first_parts_triple_alone(family):
    ops = _for(family, _ops(5, 3, True))
    ar = _arena(family)
    _apply(ar, ops)
    part = ar.snapshot_part()
    first = tuple(a.copy() for a in part["staged"])
    live = ar._acc
    for a in part["staged"]:
        for col in (live.rows, live.vals, live.wts):
            assert not np.shares_memory(a, col)
    ar.reset_rows(part["rows"])
    ar.end_interval()
    for i in range(N_ROWS):     # end_interval left the rows untouched
        ar.touched[i] = True
    _apply(ar, _for(family, _ops(6, 3, True, scale=8)))
    _same(part["staged"], first)
    second = ar.take_staged()
    assert len(second[0]) > len(first[0])
    _same(part["staged"], first)


@pytest.mark.parametrize("family", sorted(ARENAS) + ["gauge"])
def test_snapshot_columns_own_their_memory(family):
    """`col[rows]` by a row array already copies: with the second
    `.copy()` gone every column of the part still owns its memory, and
    resetting the live rows does not reach it."""
    if family == "gauge":
        ar = arena_mod.GaugeArena(capacity=64)
        for i in range(N_ROWS):
            row = ar.row_for(MetricKey(f"g{i}", "gauge", ""),
                             MetricScope.MIXED, [])
            ar.sample(row, float(i) + 0.5)
    else:
        ar = _arena(family)
        _apply(ar, _for(family, _ops(9, 1, True)))
    part = ar.snapshot_part()
    cols = ["scopes"] + [name for name, _ in ar._COLUMNS]
    if family != "gauge":
        cols.append("name_hashes")
    before = {name: part[name].copy() for name in cols}
    for name in cols:
        live = getattr(ar, {"scopes": "scope_col",
                            "name_hashes": "name_hash_col"}.get(name,
                                                                name))
        assert part[name].base is None, name
        assert not np.shares_memory(part[name], live), name
    assert len(part["rows"]) == N_ROWS
    ar.reset_rows(part["rows"])
    ar.scope_col[:] = 7
    ar.name_hash_col[:] = -1
    for name in cols:
        np.testing.assert_array_equal(part[name], before[name])
    if family != "gauge":
        assert part["l_weight"].sum() > 0       # and it is not all fill


# -- (a) again, through the paths that rewrite the accumulator ---------------

def _regrow_ops(family="digest"):
    # ~10k points in four ticks through a 4,096-point buffer, no row
    # deeper than DENSE_DEPTH_CAP
    return _for(family, _ops(77, 4, True, calls=60,
                             scale=20 if family == "digest" else 28))


@pytest.mark.parametrize("family", sorted(ARENAS))
def test_a_regrow_inside_the_interval_keeps_the_order(family):
    ops = _regrow_ops(family)
    ar, twin = _arena(family), _arena(family, parent=True)
    copied = ar.staged_copied_bytes
    _apply(ar, ops)
    _apply(twin, ops)
    want = _expected(ops)
    assert len(want[0]) > 2 * arena_mod._StagedPoints.FLOOR
    assert int(np.bincount(want[0]).max()) <= arena_mod.DENSE_DEPTH_CAP
    assert ar._acc.regrows >= 2
    # a doubling copies what was there, and says so
    assert ar.staged_copied_bytes - copied >= 24 * 4096
    staged = ar.take_staged()
    assert ar.snapshot_staged_regrows >= 2 and ar._acc.regrows == 0
    _same(staged, want)
    _same(twin.take_staged(), want)
    if family != "compactor":
        _same_dense(_dense(ar, staged, False), _dense(twin, want, False))
    # the next interval's buffer is sized to what this one reached
    assert len(ar._acc.rows) == arena_mod._pow2(len(want[0]))
    _apply(ar, ops)
    assert ar._acc.regrows == 0
    _same(ar.take_staged(), want)


def _hot_ops(family):
    """Row 3 outgrows DENSE_DEPTH_CAP in the second of three ticks."""
    rng = np.random.default_rng(13)
    ops = _for(family, _ops(21, 3, True))
    hot = ("sample_batch", np.full(700, 3, np.int64),
           rng.gamma(2.0, 10.0, 700), np.ones(700))
    mid = [i for i, op in enumerate(ops) if op[0] == "sync"][0] + 1
    return ops[:mid] + [hot] + ops[mid:]


@pytest.mark.parametrize("family", sorted(ARENAS))
def test_a_pre_reduce_keeps_the_parents_points(family):
    ops = _hot_ops(family)
    ar, twin = _arena(family), _arena(family, parent=True)
    copied = ar.staged_copied_bytes
    _apply(ar, ops)
    _apply(twin, ops)
    # the re-staged points went through the accumulator's seam
    assert ar.staged_copied_bytes > copied
    assert int(ar._depth.max()) <= arena_mod.DENSE_DEPTH_CAP
    staged, want = ar.take_staged(), twin.take_staged()
    assert 0 < len(want[0]) < len(_expected(ops)[0])
    _same(staged, want)
    if family == "digest":
        _same_dense(_dense(ar, staged, False), _dense(twin, want, False))
    else:
        # the fold went into the family's own state, the same on both
        for name in ("ivec", "cvals", "ccnt"):
            if hasattr(ar, name):
                assert getattr(ar, name).tobytes() \
                    == getattr(twin, name).tobytes()


@pytest.mark.parametrize("family", sorted(ARENAS))
def test_checkpoint_and_restore_keep_the_order(family):
    ops = _regrow_ops(family)
    cut = [i for i, op in enumerate(ops) if op[0] == "sync"][1] + 1
    ar = _arena(family)
    _apply(ar, ops[:cut])
    meta, arrays = ar.checkpoint_state()
    # the checkpoint owns its copy: the live buffer moves on under it
    frozen = arrays["acc_rows"].copy()
    _apply(ar, ops[cut:])
    np.testing.assert_array_equal(arrays["acc_rows"], frozen)
    revived = ARENAS[family](capacity=64)
    revived.restore_precheck(meta, arrays)
    revived.restore_state(meta, arrays)
    _apply(revived, ops[cut:])
    want = _expected(ops)
    _same(ar.take_staged(), want)
    _same(revived.take_staged(), want)


def test_resident_stream_reads_a_stable_prefix():
    """stream_resident's _res_consumed prefix survives a regrow: the
    chunks streamed before the buffer doubled plus the tail are the
    points, in order."""
    ops = _regrow_ops("moments")
    ar = arena_mod.DigestArena(capacity=64, resident=True,
                               resident_chunk_points=1024,
                               resident_device_assembly=True)
    for op in ops:
        getattr(ar, op[0])(*op[1:])
        if op[0] == "sync":
            ar.stream_resident()
    want = _expected(ops)
    assert ar._res_consumed >= 4096 and ar._acc.regrows >= 1
    assert ar._res_consumed == 1024 * len(ar._res_chunks)
    staged = ar.take_staged()
    _same(staged, want)
    part = ar.take_resident(staged)
    assert not part["dirty"]
    assert part["streamed_points"] + len(part["tail"][0]) == len(want[0])


# -- (c) the counters that say the hand-off engaged --------------------------

def _timer(name, value):
    return UDPMetric(name=name, type="timer", value=value, sample_rate=1.0,
                     tags=[], joined_tags="", scope=MetricScope.MIXED)


def test_cut_copies_nothing_after_drain_ticks_and_says_so_when_it_does():
    agg = MetricAggregator(percentiles=[0.5])
    keys = ("staged_points", "staged_cut_copy_bytes", "staged_regrows")
    assert keys == STAGED_LEDGER_KEYS
    assert set(keys) <= ROW_ONLY_SEGMENT_KEYS
    # an interval whose points were all synced at drain ticks, through a
    # buffer that had to double on the way
    for tick in range(3):
        for i in range(3000):
            agg.process_metric(_timer(f"t{i % 50}", float(i)))
        assert agg.sync_staged(min_samples=1)
    agg.flush(is_local=False)
    seg = agg.last_flush_segments
    assert seg["staged_points"] == 9000
    assert seg["staged_cut_copy_bytes"] == 0
    assert seg["staged_regrows"] == 2           # 4,096 -> 16,384
    # the same interval again: sized from the last, nothing regrows
    for tick in range(3):
        for i in range(3000):
            agg.process_metric(_timer(f"t{i % 50}", float(i)))
        agg.sync_staged(min_samples=1)
    agg.flush(is_local=False)
    seg = agg.last_flush_segments
    assert [seg[k] for k in keys] == [9000, 0, 0]
    # the last partial tick is the snapshot's own sync() to fold, into
    # the room the buffer has: still no accumulated point copied
    for i in range(3000):
        agg.process_metric(_timer(f"t{i % 50}", float(i)))
    agg.sync_staged(min_samples=1)
    for i in range(500):
        agg.process_metric(_timer(f"t{i % 50}", float(i)))
    agg.flush(is_local=False)
    seg = agg.last_flush_segments
    assert [seg[k] for k in keys] == [3500, 0, 0]
    # a hot key whose pre-reduce runs inside the snapshot's sync()
    for i in range(700):
        agg.process_metric(_timer("hot", float(i)))
    agg.flush(is_local=False)
    seg = agg.last_flush_segments
    assert 0 < seg["staged_points"] < 700
    assert seg["staged_cut_copy_bytes"] == 24 * seg["staged_points"]
    # and an interval nobody wrote to
    agg.flush(is_local=False)
    assert [agg.last_flush_segments[k] for k in keys] == [0, 0, 0]


def test_hand_off_counters_on_the_row_and_in_debug_vars():
    from tests.test_self_telemetry import FakeStatsd

    sink = simple_sinks.ChannelMetricSink()
    srv = Server(config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"], interval=3600.0,
        percentiles=[0.5], hostname="h0"), extra_metric_sinks=[sink])
    srv.statsd = FakeStatsd()
    srv.start()
    try:
        assert http_api.debug_vars(srv)["staged_accumulator"] == {
            "staged_points": 0, "staged_cut_copy_bytes": 0,
            "staged_regrows": 0}
        for i in range(600):
            srv.aggregator.process_metric(_timer("row.hot", float(i)))
        srv.flush()
        row = srv.flush_timeline.snapshot()[-1]
        assert 0 < row["staged_points"] < 600
        assert row["staged_cut_copy_bytes"] == 24 * row["staged_points"]
        assert row["staged_regrows"] == 0
        assert http_api.debug_vars(srv)["staged_accumulator"] == {
            k: row[k] for k in STAGED_LEDGER_KEYS}
        # the row is the outlet: no series per flush
        names = {c[1] for c in srv.statsd.calls}
        for key in STAGED_LEDGER_KEYS:
            assert f"flush.{key}" not in names
    finally:
        srv.shutdown()
