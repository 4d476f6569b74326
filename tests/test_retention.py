"""Multi-resolution retention (veneur_tpu/retention/): the tier
ladder and its cascade, the shared bucket codec, the on-disk
TierSegmentStore (spill, budget, crash recovery, ledger closure),
cross-tier fusion accuracy against the numpy oracle for all three
sketch families, checkpoint roundtrip, and the async compaction
worker's drain/discard semantics."""

import math
import threading
import time

import numpy as np
import pytest

from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.query.engine import QueryEngine, weighted_quantiles_np
from veneur_tpu.retention.spill import TierSegmentStore
from veneur_tpu.retention.timeline import (RetentionTimeline,
                                           TierBucket,
                                           decode_bucket_body,
                                           encode_bucket_body,
                                           merge_cloud)
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricScope, UDPMetric
from veneur_tpu.sketches import compactor as cs
from veneur_tpu.sketches import moments as mo

# two-tier ladder used across the file (binary-exact seconds so the
# bucket grid math is bit-exact in the assertions): 0.25s x2
# cascading into 0.5s x1 — the narrow shape evicts fast, the wide
# shape retains everything for the fusion-accuracy oracle tests
TIERS = ({"seconds": 0.25, "buckets": 2}, {"seconds": 0.5, "buckets": 1})
TIERS_WIDE = ({"seconds": 0.25, "buckets": 8},
              {"seconds": 0.5, "buckets": 8})
T0 = 1000.0     # aligned to both bucket grids (1000 / 0.5 = 2000)


def _tl(store=None, tiers=TIERS) -> RetentionTimeline:
    return RetentionTimeline([dict(t) for t in tiers], store=store)


def _td_summary(name: str, vals) -> dict:
    v = np.asarray(vals, np.float64)
    return {(name, "", "histogram"): {
        "v": v.copy(), "w": np.ones(len(v), np.float64),
        "min": float(v.min()), "max": float(v.max()),
        "count": float(len(v)), "sum": float(v.sum()),
        "rsum": float((v * v).sum())}}


def _mo_summary(name: str, vals) -> dict:
    s = mo.MomentsSketch()
    s.add_batch(np.asarray(vals, np.float64))
    return {(name, "", "histogram"): s.vec.copy()}


def _cc_summary(name: str, vals) -> dict:
    k = cs.CompactorSketch()
    k.add_batch(np.asarray(vals, np.float64))
    return {(name, "", "histogram"): k.to_vector()}


def _feed_cuts(tl: RetentionTimeline, chunks, base: float = T0,
               cut_s: float = 0.25, name: str = "h") -> None:
    """One cut per chunk: cut i covers [base + i*cut_s, base +
    (i+1)*cut_s) and lands at its window END (flush semantics)."""
    for i, chunk in enumerate(chunks):
        tl.absorb_summaries(_td_summary(name, chunk), {}, {},
                            base + (i + 1) * cut_s)


# -- tier mechanics: cascade, ring bounds, cut positioning ------------------

def test_cascade_keeps_every_datum_at_every_resolution():
    """A closed finer bucket merges upward, so the coarsest tier
    always holds the full retained mass while finer tiers stay
    bounded rings of recent high-resolution buckets."""
    tl = _tl(tiers=({"seconds": 0.25, "buckets": 2},
                    {"seconds": 0.5, "buckets": 4}))
    _feed_cuts(tl, [[float(i)] * 10 for i in range(6)])
    st = tl.stats()
    fine, coarse = st["tiers"]["t0x0s"], st["tiers"]["t1x0s"]
    assert tl.compactions == 6 and tl.points_in == 60.0
    assert fine["buckets"] <= 2
    # the coarsest never evicted, so coarse mass + the fine OPEN
    # bucket (not yet cascaded) is the WHOLE run, while the bounded
    # fine ring only covers the recent window
    assert coarse["evicted"] == 0
    fine_open = tl.tiers[0].open.points if tl.tiers[0].open else 0.0
    assert coarse["points_held"] + fine_open == 60.0
    assert fine["points_held"] < 60.0
    assert fine["closed_total"] >= 3 and fine["evicted"] >= 1


def test_first_cut_positions_at_cut_ts_then_by_window_start():
    """Cut position is the data window's START (the previous cut), so
    a cut landing exactly on a bucket boundary files under the bucket
    its data came from; the first cut has no prior and files at its
    own timestamp."""
    tl = _tl()
    tl.absorb_summaries(_td_summary("h", [1.0]), {}, {}, T0 + 0.25)
    fine = tl.tiers[0]
    assert fine.open is not None
    assert fine.open.t_start == T0 + 0.25
    # the second cut lands ON the next boundary but its data window
    # STARTED at the previous cut: same bucket [T0+0.25, T0+0.5)
    tl.absorb_summaries(_td_summary("h", [2.0]), {}, {}, T0 + 0.5)
    assert fine.open.t_start == T0 + 0.25
    assert fine.open.points == 2.0 and fine.closed_total == 0
    # the third's window start crosses: closes the bucket, cascades
    tl.absorb_summaries(_td_summary("h", [3.0]), {}, {}, T0 + 0.75)
    assert fine.closed_total == 1
    assert tl.tiers[1].open is not None
    assert tl.tiers[1].open.points == 2.0


def test_tier_geometry_validation():
    with pytest.raises(ValueError, match="at least one tier"):
        RetentionTimeline([])
    with pytest.raises(ValueError, match="strictly increasing"):
        RetentionTimeline([{"seconds": 1.0, "buckets": 2},
                           {"seconds": 1.0, "buckets": 2}])
    with pytest.raises(ValueError, match="capacity"):
        RetentionTimeline([{"seconds": 1.0, "buckets": 0}])


# -- the bucket codec -------------------------------------------------------

def test_bucket_codec_roundtrip_bit_exact():
    b = TierBucket(T0, T0 + 0.4)
    b.absorb(_td_summary("h", [1.0, 2.5, 3.0]),
             _mo_summary("m", [4.0, 5.0]),
             _cc_summary("c", [6.0, 7.0, 8.0]),
             T0 + 0.2, 2048, 100.0)
    b.absorb(_td_summary("h", [9.0]), {}, {}, T0 + 0.4, 2048, 100.0)
    d = decode_bucket_body(encode_bucket_body(b))
    assert (d.t_start, d.t_end, d.filled_to, d.cuts) == \
        (b.t_start, b.t_end, b.filled_to, b.cuts)
    assert set(d.td) == set(b.td) and set(d.mo) == set(b.mo) \
        and set(d.cc) == set(b.cc)
    for key, ent in b.td.items():
        got = d.td[key]
        assert np.array_equal(got["v"], ent["v"])
        assert np.array_equal(got["w"], ent["w"])
        for f in ("min", "max", "count", "sum", "rsum"):
            assert got[f] == ent[f]
    for key, vec in b.mo.items():
        assert np.array_equal(d.mo[key], vec)
    for key, vec in b.cc.items():
        assert np.array_equal(d.cc[key], vec)
    assert d.points == b.points


def test_tier_compaction_bit_parity_with_direct_merge():
    """Under the point cap a bucket built by absorbing cuts one at a
    time is BIT-IDENTICAL to directly merging the constituent
    summaries — tier compaction loses nothing the slot merge keeps."""
    rng = np.random.default_rng(7)
    a_v, b_v = rng.gamma(2.0, 3.0, 40), rng.gamma(2.0, 3.0, 40)
    sa, sb = _td_summary("h", a_v), _td_summary("h", b_v)
    key = ("h", "", "histogram")
    b = TierBucket(T0, T0 + 0.4)
    b.absorb(sa, _mo_summary("m", a_v), _cc_summary("c", a_v),
             T0 + 0.2, 2048, 100.0)
    b.absorb(sb, _mo_summary("m", b_v), _cc_summary("c", b_v),
             T0 + 0.4, 2048, 100.0)
    direct = merge_cloud(sa[key], sb[key], 2048, 100.0)
    assert np.array_equal(b.td[key]["v"], direct["v"])
    assert np.array_equal(b.td[key]["w"], direct["w"])
    assert b.td[key]["count"] == direct["count"]
    assert b.td[key]["sum"] == direct["sum"]
    mkey, ckey = ("m", "", "histogram"), ("c", "", "histogram")
    mo_direct = mo.merge_vectors(
        _mo_summary("m", a_v)[mkey][None, :],
        _mo_summary("m", b_v)[mkey][None, :])[0]
    assert np.array_equal(b.mo[mkey], mo_direct)
    cc_direct = cs.merge_vectors(
        _cc_summary("c", a_v)[ckey][None, :],
        _cc_summary("c", b_v)[ckey][None, :])[0]
    assert np.array_equal(b.cc[ckey], cc_direct)


# -- the spill store --------------------------------------------------------

def test_store_spill_read_and_crash_recovery(tmp_path):
    d = str(tmp_path / "tiers")
    store = TierSegmentStore(d)
    bodies = []
    for i in range(3):
        b = TierBucket(T0 + i * 0.4, T0 + (i + 1) * 0.4)
        b.absorb(_td_summary("h", [float(i)] * 5), {}, {},
                 b.t_end, 2048, 100.0)
        body = encode_bucket_body(b)
        bodies.append(body)
        store.spill("t1x0s", b.t_start, b.t_end, 5, body)
    assert store.stats()["spilled_buckets"] == 3
    assert store.stats()["pending_points"] == 15
    recs = store.records_overlapping(T0 + 0.4, T0 + 0.8)
    assert len(recs) == 1 and store.read_body(recs[0]) == bodies[1]
    # kill -9: NO drain, reopen re-indexes every intact record
    store.close(drain=False)
    back = TierSegmentStore(d)
    st = back.stats()
    assert st["recovered_buckets"] == 3
    assert st["recovered_points"] == 15
    assert st["torn_records"] == 0 and st["crc_rejected"] == 0
    got = [back.read_body(r)
           for r in back.records_overlapping(0.0, 1e18)]
    assert got == bodies
    assert decode_bucket_body(got[0]).points == 5.0


def test_store_byte_budget_and_age_expiry_close_the_ledger(tmp_path):
    body = encode_bucket_body(TierBucket(T0, T0 + 0.4))
    store = TierSegmentStore(str(tmp_path / "t"),
                             max_bytes=6 * len(body),
                             segment_max_bytes=2 * len(body))
    for i in range(10):
        store.spill("t", T0 + i * 0.4, T0 + (i + 1) * 0.4, 1, body)
    st = store.stats()
    assert st["pending_bytes"] <= store.max_bytes
    assert st["expired_buckets"] + st["dropped_buckets"] > 0
    # ledger closure: everything spilled is pending, expired or
    # dropped — no bucket unaccounted for
    assert st["spilled_buckets"] == (st["pending_buckets"]
                                     + st["expired_buckets"]
                                     + st["dropped_buckets"])
    # age expiry on top of the byte budget
    aged = TierSegmentStore(str(tmp_path / "a"), max_age_s=100.0)
    aged.spill("t", T0, T0 + 0.4, 1, body)
    assert aged.expire_now(now=T0 + 0.4 + 99.0) == 0
    assert aged.expire_now(now=T0 + 0.4 + 101.0) == 1
    st = aged.stats()
    assert st["pending_buckets"] == 0 and st["expired_buckets"] == 1


def test_timeline_spills_only_coarsest_evictions(tmp_path):
    tl = _tl(store=TierSegmentStore(str(tmp_path / "t")))
    # 0.2s cuts: the 0.4s x1 coarse ring evicts from the third
    # coarse bucket on — finer-tier evictions must NOT spill (their
    # mass lives on upward)
    _feed_cuts(tl, [[float(i)] * 10 for i in range(12)])
    st = tl.stats()
    assert st["spilled_buckets"] >= 1
    assert st["tiers"]["t0x0s"]["evicted"] >= 1
    # conservation: coarse mass + finer OPEN buckets + disk == fed
    with tl.lock:
        mem = tl.tiers[-1].stats()["points_held"]
        for t in tl.tiers[:-1]:
            if t.open is not None:
                mem += t.open.points
    disk = sum(decode_bucket_body(tl.store.read_body(r)).points
               for r in tl.store.records_overlapping(0.0, 1e18))
    assert mem + disk == tl.points_in == 120.0
    assert st["footprint_bytes"] >= st["on_disk_bytes"] > 0
    tl.close()
    tl.store.close(drain=True)


# -- cross-tier fusion accuracy (the range read vs the numpy oracle) --------

def _range_agg() -> MetricAggregator:
    return MetricAggregator(
        percentiles=[0.5], query_window_slots=2,
        query_slot_seconds=0.05,
        retention_tiers=[dict(t) for t in TIERS_WIDE])


def test_range_fusion_accuracy_all_families_within_envelope():
    """A month of one family's life in miniature: many cuts cascade
    through both resolutions, then the range read fuses buckets back
    and must sit inside each family's committed envelope against the
    exact numpy answer — tdigest EXACT under the point cap, moments
    and compactor within their 5%-of-span envelopes."""
    agg = _range_agg()
    eng = QueryEngine(agg)
    rng = np.random.default_rng(11)
    chunks = [rng.uniform(0.0, 100.0, 50) for _ in range(8)]
    full = np.concatenate(chunks)
    # warm-up cut: establishes last_cut so every data cut files under
    # its window START, aligning the data to the bucket grid
    agg.retention.absorb_summaries({}, {}, {}, T0)
    for i, chunk in enumerate(chunks):
        agg.retention.absorb_summaries(
            _td_summary("rh", chunk), _mo_summary("rm", chunk),
            _cc_summary("rc", chunk),
            T0 + (i + 1) * 0.25)
    until = T0 + 8 * 0.25
    span = full.max() - full.min()
    qs = [0.25, 0.5, 0.9]
    # the tdigest oracle is the serving kernel itself over ALL raw
    # samples (under the cap the tier merges are exact concats, so the
    # range answer must match it bit-for-bit); moments/compactor are
    # judged against np.quantile inside their 5%-of-span envelopes
    exact_td = weighted_quantiles_np(
        full, np.ones(len(full)), float(full.min()),
        float(full.max()), np.asarray(qs))
    exact = np.quantile(full, qs)
    for name, tol in (("rh", None), ("rm", 0.05), ("rc", 0.05)):
        out = eng.query(name, qs=qs, since=T0, until=until,
                        step=until - T0)
        assert out["range"] and out["bins"] == 1
        ent = out["series"][0]
        assert ent["count"] == float(len(full)), name
        assert ent["sum"] == pytest.approx(full.sum(), rel=1e-9)
        got = np.asarray([ent["quantiles"][repr(float(q))]
                          for q in qs])
        if tol is None:
            np.testing.assert_allclose(got, exact_td, rtol=1e-12)
        else:
            err = np.abs(got - exact) / span
            assert err.max() < tol, (name, err)
    agg.retention.close()


def test_range_per_resolution_bins_conserve_counts():
    """Stepping at each tier's native resolution: every bin's count
    equals the mass of exactly the cuts inside it — no bucket counted
    twice across adjacent bins (the float-jitter regression) and none
    dropped at tier handoff."""
    agg = _range_agg()
    eng = QueryEngine(agg)
    sizes = [10, 20, 30, 40, 50, 60]
    agg.retention.absorb_summaries({}, {}, {}, T0)   # grid warm-up
    for i, n in enumerate(sizes):
        agg.retention.absorb_summaries(
            _td_summary("rh", np.arange(n, dtype=np.float64)),
            {}, {}, T0 + (i + 1) * 0.25)
    until = T0 + 6 * 0.25
    # finest resolution: cut i files under its window START, so every
    # bin holds exactly its own cut's mass
    out = eng.query("rh", qs=[0.5], since=T0, until=until,
                    step=0.25)
    counts = [e["count"] for e in out["series"]]
    assert sum(counts) == float(sum(sizes))
    assert counts == [10.0, 20.0, 30.0, 40.0, 50.0, 60.0]
    # coarse resolution: same mass, wider bins
    out = eng.query("rh", qs=[0.5], since=T0, until=until,
                    step=0.5)
    counts = [e["count"] for e in out["series"]]
    assert sum(counts) == float(sum(sizes))
    assert counts == [30.0, 70.0, 110.0]
    for e in out["series"]:
        assert not e["mixed_families"]
    agg.retention.close()


def test_range_reads_spilled_buckets_from_disk(tmp_path):
    """Bins older than every in-memory ring answer from the spill
    store, labelled as the coarsest tier's :disk source."""
    agg = MetricAggregator(
        percentiles=[0.5], query_window_slots=2,
        query_slot_seconds=0.05,
        retention_tiers=[dict(t) for t in TIERS],
        retention_dir=str(tmp_path / "tiers"))
    eng = QueryEngine(agg)
    for i in range(12):
        agg.retention.absorb_summaries(
            _td_summary("rh", [float(i)] * 10), {}, {},
            T0 + (i + 1) * 0.25)
    assert agg.retention.stats()["spilled_buckets"] >= 1
    out = eng.query("rh", qs=[0.5], since=T0,
                    until=T0 + 12 * 0.25, step=0.5)
    assert any(s.endswith(":disk") for s in out["sources"])
    assert sum(e["count"] for e in out["series"]) == 120.0
    agg.retention.close()
    agg.retention.store.close(drain=True)


# -- checkpoint roundtrip ---------------------------------------------------

def test_checkpoint_roundtrip_restores_exact_state():
    tl = _tl()
    _feed_cuts(tl, [[float(i)] * 10 for i in range(5)])
    meta, arrays = tl.checkpoint_capture()
    back = _tl()
    back.checkpoint_restore(meta, arrays)
    assert back.compactions == tl.compactions
    assert back.points_in == tl.points_in
    assert back.last_cut == tl.last_cut
    a, b = tl.stats(), back.stats()
    for tn in a["tiers"]:
        assert a["tiers"][tn] == b["tiers"][tn], tn
    key = ("h", "", "histogram")
    assert np.array_equal(tl.tiers[0].open.td[key]["v"],
                          back.tiers[0].open.td[key]["v"])


def test_checkpoint_geometry_mismatch_cold_starts():
    """A restore into a DIFFERENT tier ladder cold-starts instead of
    mis-filing buckets (the documented contract)."""
    tl = _tl()
    _feed_cuts(tl, [[1.0] * 10 for _ in range(4)])
    meta, arrays = tl.checkpoint_capture()
    other = _tl(tiers=({"seconds": 0.5, "buckets": 4},))
    other.checkpoint_restore(meta, arrays)
    st = other.stats()
    assert st["buckets"] == 0 and other.compactions == 0


# -- the async compaction worker --------------------------------------------

def test_worker_drain_fences_queued_cuts(monkeypatch):
    tl = _tl()
    seen = []
    monkeypatch.setattr(
        tl, "_compact_one",
        lambda cut, ts: (time.sleep(0.02), seen.append(ts)))
    for i in range(4):
        tl.compact_cut(None, T0 + i)
    assert tl.drain(timeout=10.0)
    assert seen == [T0, T0 + 1, T0 + 2, T0 + 3]   # FIFO
    assert tl.stats()["pending_cuts"] == 0
    tl.close()


def test_worker_close_without_drain_discards_queue(monkeypatch):
    """The crash path: close(drain=False) DISCARDS queued cuts —
    exactly what a kill -9 loses — so a dying server cannot keep
    spilling into a directory its revival reopened."""
    tl = _tl()
    gate = threading.Event()
    done = []
    monkeypatch.setattr(
        tl, "_compact_one",
        lambda cut, ts: (gate.wait(5.0), done.append(ts)))
    for i in range(3):
        tl.compact_cut(None, T0 + i)
    tl.close(drain=False)
    gate.set()
    tl._worker.join(timeout=5.0)
    assert len(done) <= 1          # at most the in-flight cut
    assert tl.stats()["pending_cuts"] == 0
    # enqueue after close is a no-op
    tl.compact_cut(None, T0 + 9)
    assert tl.stats()["pending_cuts"] == 0


def test_worker_errors_are_counted_not_fatal(monkeypatch):
    tl = _tl()
    monkeypatch.setattr(
        tl, "_compact_one",
        lambda *a: (_ for _ in ()).throw(RuntimeError("boom")))
    tl.compact_cut(None, T0)
    assert tl.drain(timeout=10.0)
    assert tl.compact_errors == 1
    assert tl.stats()["compact_errors"] == 1
    tl.close()


# -- the flush hook end to end ----------------------------------------------

def test_flush_cut_feeds_timeline_for_all_families():
    agg = MetricAggregator(
        percentiles=[0.5], query_window_slots=2,
        query_slot_seconds=0.05,
        retention_tiers=[dict(t) for t in TIERS],
        sketch_family_rules=[
            {"match": "mh*", "family": "moments"},
            {"match": "ch*", "family": "compactor"}])
    with agg.lock:
        for name, n in (("h", 5), ("mh0", 7), ("ch0", 9)):
            for v in range(n):
                agg._process_locked(UDPMetric(
                    name=name, type=sm.TYPE_HISTOGRAM,
                    value=float(v), scope=MetricScope.MIXED))
    agg.flush(is_local=False)
    assert agg.retention.drain(timeout=10.0)
    st = agg.retention.stats()
    assert st["compactions"] == 1 and st["points_in"] == 21.0
    fine = agg.retention.tiers[0].open
    keys = set(fine.td) | set(fine.mo) | set(fine.cc)
    assert ("h", "", "histogram") in set(fine.td)
    assert ("mh0", "", "histogram") in set(fine.mo)
    assert ("ch0", "", "histogram") in set(fine.cc)
    assert len(keys) >= 3
    agg.retention.close()


def test_stats_promises_the_debug_vars_block_shape():
    tl = _tl()
    _feed_cuts(tl, [[1.0]])
    st = tl.stats()
    for k in ("tiers", "compactions", "points_in", "last_cut_unix",
              "pending_cuts", "compact_errors", "buckets",
              "on_disk_bytes", "footprint_bytes"):
        assert k in st, k
    for tn, ts in st["tiers"].items():
        for k in ("bucket_seconds", "capacity", "buckets", "open",
                  "closed_total", "evicted", "points_held",
                  "bytes_held"):
            assert k in ts, (tn, k)
    tl.close()


# -- the chaos cell ---------------------------------------------------------

def test_timeline_crash_revive_arm_conserves_exactly():
    """The acceptance cell: kill -9 with a spilled bucket on disk —
    the re-indexed store recovers every spilled point, retained mass
    equals the oracle exactly before AND after, and the revived node
    answers the whole run's range query from tiers + disk."""
    from veneur_tpu.testbed.chaos import arm_by_name, run_chaos_arm

    row = run_chaos_arm(arm_by_name("timeline-crash-revive"), seed=0)
    assert row["ok"], row
    assert row["spilled_buckets"] >= 1
    assert row["recovered_points_exact"] and row["store_closure"]
    pre, post, want = row["timeline_points"]
    assert pre == post == want
    assert row["range_counts_exact"] and row["range_disk_served"]
