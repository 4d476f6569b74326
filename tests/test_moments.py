"""Moments sketch family: sketch math, merge exactness, kernel parity,
arena contract, checkpoint bit-parity, wire interop, family dispatch,
and the tier-1 mixed-family testbed cell (ISSUE 13)."""

import numpy as np
import pytest

from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.core.arena import CheckpointIncompatible, MomentsArena
from veneur_tpu.forward import convert
from veneur_tpu.ops import moments_eval as me
from veneur_tpu.samplers.metric_key import (MetricKey, MetricScope,
                                            UDPMetric)
from veneur_tpu.sketches import moments as mo


def _udp(name, value, scope=MetricScope.LOCAL_ONLY, tags=(),
         mtype="histogram", rate=1.0):
    return UDPMetric(name=name, type=mtype, value=float(value),
                     sample_rate=rate, tags=list(tags),
                     joined_tags=",".join(sorted(tags)), scope=scope)


# ---------------------------------------------------------------------------
# sketch math
# ---------------------------------------------------------------------------

def test_sketch_accuracy_across_distributions():
    rng = np.random.default_rng(0)
    cases = {
        "uniform": rng.uniform(0, 100, 20_000),
        "gamma": rng.gamma(2.0, 10.0, 20_000),
        "lognormal": rng.lognormal(3.0, 1.0, 20_000),
        "heavy_tail": rng.pareto(1.5, 20_000) + 1.0,
        # values far from zero relative to spread: the raw-power-sum
        # formulation would cancel to garbage here; the range-scaled
        # sums must not care
        "narrow_shift": rng.uniform(1000, 1001, 20_000),
        "adversarial_sorted": np.sort(rng.gamma(2.0, 10.0, 20_000)),
    }
    qs = [0.5, 0.9, 0.99]
    for name, data in cases.items():
        s = mo.MomentsSketch()
        s.add_batch(data)
        got = s.quantiles(qs)
        exact = np.quantile(data, qs)
        span = data.max() - data.min()
        err = np.abs(got - exact) / span
        assert err.max() < 0.02, (name, err)


def test_merge_is_exact_on_scalars_and_tight_on_quantiles():
    rng = np.random.default_rng(1)
    data = rng.gamma(2.0, 10.0, 30_000)
    whole = mo.MomentsSketch()
    whole.add_batch(data)
    a, b = mo.MomentsSketch(), mo.MomentsSketch()
    a.add_batch(data[:10_000])
    b.add_batch(data[10_000:])
    a.merge(b)
    # exact scalar merges
    assert a.vec[mo.IDX_COUNT] == 30_000.0
    assert a.vec[mo.IDX_MIN] == data.min()
    assert a.vec[mo.IDX_MAX] == data.max()
    assert np.isclose(a.vec[mo.IDX_SUM], data.sum(), rtol=1e-12)
    # merged quantiles track the whole-data sketch closely (the rebase
    # is exact in exact arithmetic; fp drift stays at the ulp level)
    qa = a.quantiles([0.5, 0.99])
    qw = whole.quantiles([0.5, 0.99])
    span = data.max() - data.min()
    assert np.abs(qa - qw).max() / span < 1e-3


def test_merge_with_empty_is_identity():
    rng = np.random.default_rng(2)
    data = rng.gamma(2.0, 10.0, 1000)
    s = mo.MomentsSketch()
    s.add_batch(data)
    before = s.vec.copy()
    s.merge(mo.MomentsSketch())           # empty right operand
    assert np.array_equal(s.vec, before)
    e = mo.MomentsSketch()
    e.merge(s)                             # empty left operand
    assert np.allclose(e.vec, before, rtol=1e-12)
    assert np.all(np.isfinite(e.vec))


def test_mixed_k_vectors_refuse_to_merge():
    a = MomentsArena(k=8)
    row = a.row_for(MetricKey("x", "histogram", ""),
                    MetricScope.MIXED, [])
    with pytest.raises(ValueError, match="mixed-k"):
        a.merge_moments(row, mo.empty_vector(6))


def test_rebase_sums_is_stable_far_from_zero():
    # scaled sums rebased across nested domains keep full precision
    # even when |values| >> span
    rng = np.random.default_rng(3)
    vals = rng.uniform(1e6, 1e6 + 1, 5000)
    s1 = mo.MomentsSketch()
    s1.add_batch(vals)
    s2 = mo.MomentsSketch()
    s2.add_batch(vals + 0.5)              # shifted domain
    s1.merge(s2)
    q = s1.quantile(0.5)
    both = np.concatenate([vals, vals + 0.5])
    exact = np.quantile(both, 0.5)
    span = both.max() - both.min()
    assert abs(q - exact) / span < 0.02


# ---------------------------------------------------------------------------
# kernel parity (XLA twin vs Pallas interpret mode)
# ---------------------------------------------------------------------------

def _rand_dense(rng, u, d):
    dv = rng.gamma(2.0, 10.0, (u, d)).astype(np.float32)
    dw = (rng.uniform(0, 1, (u, d)) > 0.3).astype(np.float32)
    occ = dw > 0
    a = np.where(occ.any(1), np.where(occ, dv, np.inf).min(1), 0.0)
    b = np.where(occ.any(1), np.where(occ, dv, -np.inf).max(1), 0.0)
    la, lb = mo.log_domain(a, b)
    return (dv, dw, np.stack([a, b]).astype(np.float32),
            np.stack([la, lb]).astype(np.float32))


def test_kernel_interpret_parity_classic():
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    for u, d in ((256, 8), (512, 64)):
        dv, dw, ab, lab = _rand_dense(rng, u, d)
        twin = np.asarray(me._moments_sums_twin(
            jnp.asarray(dv), jnp.asarray(dw), jnp.asarray(ab),
            jnp.asarray(lab), 8, False))
        pal = np.asarray(me._moments_sums_pallas(
            jnp.asarray(dv), jnp.asarray(dw), jnp.asarray(ab),
            jnp.asarray(lab), 8, False, interpret=True))
        np.testing.assert_allclose(pal, twin, rtol=2e-5, atol=1e-4)


@pytest.mark.slow
def test_kernel_interpret_parity_dma():
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    u, d = 8192, 128
    assert me._auto_nbuf(u, me._lane_tile(u), d) > 1   # DMA path engaged
    dv, dw, ab, lab = _rand_dense(rng, u, d)
    twin = np.asarray(me._moments_sums_twin(
        jnp.asarray(dv), jnp.asarray(dw), jnp.asarray(ab),
        jnp.asarray(lab), 8, False))
    pal = np.asarray(me._moments_sums_pallas(
        jnp.asarray(dv), jnp.asarray(dw), jnp.asarray(ab),
        jnp.asarray(lab), 8, False, interpret=True))
    np.testing.assert_allclose(pal, twin, rtol=2e-5, atol=1e-4)
    # uniform (depth-vector) variant
    dep = dw.astype(np.int32).sum(1)
    dvp = np.zeros_like(dv)
    for r in range(u):
        n = int(dep[r])
        dvp[r, :n] = dv[r, :n]
    twin_u = np.asarray(me._moments_sums_twin(
        jnp.asarray(dvp), jnp.asarray(dep), jnp.asarray(ab),
        jnp.asarray(lab), 8, True))
    pal_u = np.asarray(me._moments_sums_pallas(
        jnp.asarray(dvp), jnp.asarray(dep.astype(np.int16)),
        jnp.asarray(ab), jnp.asarray(lab), 8, True, interpret=True))
    np.testing.assert_allclose(pal_u, twin_u, rtol=2e-5, atol=1e-4)


def test_flush_program_depth_variant_matches_general():
    import jax.numpy as jnp
    rng = np.random.default_rng(6)
    fn = me.make_moments_flush(8)
    u, d = 8, 128
    dv = np.zeros((u, d), np.float32)
    dep = np.zeros(u, np.int16)
    a = np.zeros(u)
    b = np.zeros(u)
    for r in range(u):
        n = int(rng.integers(10, d))
        vals = rng.gamma(2.0, 10.0, n)
        dv[r, :n] = vals
        dep[r] = n
        a[r], b[r] = vals.min(), vals.max()
    la, lb = mo.log_domain(a, b)
    ab = np.stack([a, b]).astype(np.float32)
    lab = np.stack([la, lb]).astype(np.float32)
    imp = np.zeros((u, 18), np.float32)
    pct = jnp.asarray([0.5, 0.9, 0.99], jnp.float32)
    dw = (np.arange(d)[None, :] < dep[:, None]).astype(np.float32)
    general = np.asarray(fn(jnp.asarray(dv), jnp.asarray(dw),
                            jnp.asarray(ab), jnp.asarray(lab),
                            jnp.asarray(imp), pct))
    depth = np.asarray(fn.depth_variant(
        jnp.asarray(dv), jnp.asarray(dep), jnp.asarray(ab),
        jnp.asarray(lab), jnp.asarray(imp), pct))
    np.testing.assert_array_equal(general, depth)


# ---------------------------------------------------------------------------
# arena contract
# ---------------------------------------------------------------------------

def _mom_agg(**kw):
    kw.setdefault("percentiles", [0.5, 0.99])
    kw.setdefault("sketch_family_rules",
                  [{"match": "mom.*", "family": "moments"}])
    return MetricAggregator(**kw)


def test_arena_flush_quantiles_match_numpy():
    agg = _mom_agg()
    rng = np.random.default_rng(7)
    vals = rng.gamma(2.0, 10.0, 2000)
    for v in vals:
        agg.process_metric(_udp("mom.h", v))
    res = agg.flush(is_local=True)
    ms = {m.name: m.value for m in res.metrics}
    exact = np.quantile(vals, [0.5, 0.99])
    span = vals.max() - vals.min()
    assert ms["mom.h.count"] == 2000.0
    assert ms["mom.h.min"] == vals.min()
    assert ms["mom.h.max"] == vals.max()
    got = np.asarray([ms["mom.h.50percentile"],
                      ms["mom.h.99percentile"]])
    assert (np.abs(got - exact) / span).max() < 0.02


def test_arena_hot_row_pre_reduce_folds_into_ivec():
    agg = _mom_agg()
    rng = np.random.default_rng(8)
    n = arena_mod.DENSE_DEPTH_CAP * 4 + 37
    vals = rng.gamma(2.0, 10.0, n)
    agg.moments.sample_batch(
        np.full(n, agg.moments.row_for(
            MetricKey("mom.hot", "histogram", ""),
            MetricScope.LOCAL_ONLY, []), np.int64),
        vals, np.ones(n))
    with agg.lock:
        agg.moments.sync()
    # the deep row collapsed out of staging into the ivec accumulator
    assert int(agg.moments._depth.max()) <= arena_mod.DENSE_DEPTH_CAP
    row = agg.moments.kdict[(MetricKey("mom.hot", "histogram", ""),
                             MetricScope.LOCAL_ONLY)]
    assert agg.moments.ivec[row, 0] > 0          # folded mass
    res = agg.flush(is_local=True)
    ms = {m.name: m.value for m in res.metrics}
    assert ms["mom.hot.count"] == float(n)
    exact = np.quantile(vals, [0.5, 0.99])
    span = vals.max() - vals.min()
    got = np.asarray([ms["mom.hot.50percentile"],
                      ms["mom.hot.99percentile"]])
    assert (np.abs(got - exact) / span).max() < 0.02


def test_arena_release_keys_zeroes_moments_state():
    a = MomentsArena()
    dk = (MetricKey("x", "histogram", ""), MetricScope.MIXED)
    row = a.row_for(*dk, [])
    a.merge_moments(row, mo.MomentsSketch().vec * 0 + _vec_of([1.0, 2.0]))
    assert a.ivec[row, 0] > 0
    assert a.release_keys([dk]) == 1
    assert a.ivec[row, 0] == 0
    assert a.iv_a[row] == np.inf and a.iv_b[row] == -np.inf
    assert a.d_logn[row] == 0


def _vec_of(values):
    s = mo.MomentsSketch()
    s.add_batch(np.asarray(values, np.float64))
    return s.vec


def test_dense_block_per_shard_unmeshed():
    a = MomentsArena()
    assert a.n_shards == 1 and a.n_replicas == 1
    assert a.dense_block_per_shard(5) == 8      # pow2 ceiling
    assert a.dense_block_per_shard(0) == 1


def test_moments_arena_rejects_mesh():
    class FakeMesh:
        pass
    with pytest.raises(ValueError, match="unmeshed"):
        MomentsArena(mesh=FakeMesh())


# ---------------------------------------------------------------------------
# checkpoint/restore bit-parity
# ---------------------------------------------------------------------------

def test_checkpoint_restore_bit_parity_mid_interval():
    """Checkpoint with staged samples + imported vectors mid-interval,
    restore into a fresh aggregator, flush both: emissions must be
    BIT-IDENTICAL (the crash chaos arms' exactness contract)."""
    rng = np.random.default_rng(9)
    kw = dict(percentiles=[0.5, 0.99],
              sketch_family_rules=[{"match": "mom.*",
                                    "family": "moments"}])
    agg = MetricAggregator(**kw)
    for v in rng.gamma(2.0, 10.0, 500):
        agg.process_metric(_udp("mom.a", v, scope=MetricScope.MIXED))
    # an imported vector too (ivec + iv domain state must restore)
    key = MetricKey("mom.b", "histogram", "")
    with agg.lock:
        row = agg.moments.row_for(key, MetricScope.MIXED, [])
        agg.moments.merge_moments(
            row, _vec_of(rng.lognormal(3.0, 1.0, 400)))
    meta, arrays = agg.checkpoint_state()

    fresh = MetricAggregator(**kw)
    fresh.restore_state(meta, arrays)
    r1 = agg.flush(is_local=True)
    r2 = fresh.flush(is_local=True)
    m1 = sorted((m.name, m.value) for m in r1.metrics)
    m2 = sorted((m.name, m.value) for m in r2.metrics)
    assert m1 == m2                        # bit-identical emissions
    f1 = sorted((f.name, tuple(f.moments or [])) for f in r1.forward)
    f2 = sorted((f.name, tuple(f.moments or [])) for f in r2.forward)
    assert f1 == f2                        # bit-identical wire vectors


def test_checkpoint_incompatible_on_k_mismatch():
    agg = _mom_agg(sketch_moments_k=8)
    for v in (1.0, 2.0, 3.0):
        agg.process_metric(_udp("mom.k", v))
    meta, arrays = agg.checkpoint_state()
    other = _mom_agg(sketch_moments_k=6)
    with pytest.raises(CheckpointIncompatible, match="moments"):
        other.restore_state(meta, arrays)
    # the precheck fired BEFORE any arena mutated: clean cold start
    assert not other.moments.kdict and not other.digests.kdict


def test_checkpoint_incompatible_on_solver_mismatch():
    a = MomentsArena()
    a.row_for(MetricKey("x", "histogram", ""), MetricScope.MIXED, [])
    meta, arrays = a.checkpoint_state()
    meta["solver"] = [32, 10]              # foreign solver config
    fresh = MomentsArena()
    with pytest.raises(CheckpointIncompatible, match="solver"):
        fresh.restore_precheck(meta, arrays)


def test_pre_family_checkpoint_cold_starts_moments():
    """A checkpoint written before the moments family existed restores
    every other family and cold-starts moments."""
    agg = MetricAggregator(percentiles=[0.5])
    agg.process_metric(_udp("c", 3, mtype="counter"))
    meta, arrays = agg.checkpoint_state()
    del meta["families"]["moments"]
    arrays = {k: v for k, v in arrays.items()
              if not k.startswith("moments/")}
    fresh = MetricAggregator(percentiles=[0.5])
    fresh.restore_state(meta, arrays)
    assert len(fresh.counters.kdict) == 1
    assert not fresh.moments.kdict


# ---------------------------------------------------------------------------
# wire interop
# ---------------------------------------------------------------------------

def test_wire_roundtrip_is_bit_exact():
    vec = _vec_of(np.random.default_rng(10).gamma(2.0, 10.0, 1000))
    from veneur_tpu.samplers import samplers as sm
    fm = sm.ForwardMetric(name="x", tags=["a:b"], kind="histogram",
                          scope=int(MetricScope.MIXED),
                          moments=vec.tolist())
    pb = convert.to_pb(fm)
    assert pb.histogram.t_digest.compression == -8.0   # family marker
    back = convert.from_pb(pb)
    assert back.moments is not None
    assert np.array_equal(np.asarray(back.moments), vec)
    # digest payloads stay untouched by the marker logic
    fm2 = sm.ForwardMetric(name="y", tags=[], kind="histogram",
                           scope=int(MetricScope.MIXED),
                           digest_means=[1.0], digest_weights=[2.0],
                           digest_min=1.0, digest_max=1.0,
                           digest_compression=100.0)
    back2 = convert.from_pb(convert.to_pb(fm2))
    assert back2.moments is None and back2.digest_means == [1.0]


def test_local_proxy_global_merge_conserves_exactly():
    """Two locals -> (wire roundtrip) -> one global: counts/min/max
    conserve exactly, quantiles inside the committed envelope."""
    rng = np.random.default_rng(11)
    vals = rng.gamma(2.0, 10.0, 600)
    rules = [{"match": "mom.*", "family": "moments"}]
    locals_ = [MetricAggregator(percentiles=[0.5, 0.99],
                                sketch_family_rules=rules)
               for _ in range(2)]
    glob = MetricAggregator(percentiles=[0.5, 0.99], is_local=False)
    for i, v in enumerate(vals):
        locals_[i % 2].process_metric(
            _udp("mom.f", v, scope=MetricScope.MIXED))
    local_count = 0.0
    for lagg in locals_:
        res = lagg.flush(is_local=True)
        lm = {m.name: m.value for m in res.metrics}
        local_count += lm["mom.f.count"]
        for fm in res.forward:
            # through the REAL wire bytes, like the proxy path
            data = convert.to_pb(fm).SerializeToString()
            from veneur_tpu.protocol import metric_pb2
            glob.import_metric(convert.from_pb(
                metric_pb2.Metric.FromString(data)))
    assert local_count == 600.0
    gres = glob.flush(is_local=False)
    gm = {m.name: m.value for m in gres.metrics}
    exact = np.quantile(vals, [0.5, 0.99])
    span = vals.max() - vals.min()
    got = np.asarray([gm["mom.f.50percentile"],
                      gm["mom.f.99percentile"]])
    assert (np.abs(got - exact) / span).max() < 0.05
    # rows persist across intervals but the flush reset zeroed the
    # row's accumulated state (arena lifecycle contract)
    row = glob.moments.kdict[
        (MetricKey("mom.f", "histogram", ""), MetricScope.MIXED)]
    assert glob.moments.d_weight[row] == 0.0
    assert glob.moments.ivec[row, 0] == 0.0


# ---------------------------------------------------------------------------
# family dispatch
# ---------------------------------------------------------------------------

def test_dispatch_rules_name_glob_tenant_and_default():
    agg = MetricAggregator(
        percentiles=[0.5],
        sketch_family_default="moments",
        sketch_family_rules=[
            {"match": "dig.*", "family": "tdigest"},
            {"tenant": "hog", "family": "moments"},
        ])
    # name-glob rule beats default
    agg.process_metric(_udp("dig.x", 1.0))
    # tenant rule
    agg.process_metric(_udp("t.x", 1.0, tags=["tenant:hog"]))
    # default = moments
    agg.process_metric(_udp("other.x", 1.0))
    assert len(agg.digests.kdict) == 1
    assert len(agg.moments.kdict) == 2


def test_dispatch_off_is_zero_overhead_path():
    agg = MetricAggregator(percentiles=[0.5])
    assert not agg.family_dispatch
    agg.process_metric(_udp("h", 1.0))
    assert len(agg.digests.kdict) == 1 and not agg.moments.kdict


def test_cardinality_rollup_family_moments():
    """The guard's over-budget histogram tail folds into ONE moments
    vector (the first production consumer of the family dispatch) and
    conserves the tail's mass exactly."""
    agg = MetricAggregator(percentiles=[0.5],
                           cardinality_key_budget=2,
                           cardinality_rollup_family="moments")
    assert agg.family_dispatch
    rng = np.random.default_rng(12)
    for i in range(2):
        for _ in range(30):
            agg.process_metric(_udp(f"pin{i}", 1.0,
                                    tags=["tenant:hog"]))
    tail_vals = rng.gamma(2.0, 10.0, 25)
    for i, v in enumerate(tail_vals):
        agg.process_metric(_udp(f"tail{i}", v, tags=["tenant:hog"]))
    res = agg.flush(is_local=True)
    ms = {m.name: m.value for m in res.metrics}
    assert ms["veneur.rollup.histogram.count"] == 25.0
    assert ms["veneur.rollup.histogram.max"] == tail_vals.max()
    assert len(agg.moments.kdict) == 1    # one rollup row, not 25
    # the rollup row releases through the MOMENTS arena on eviction
    # (the family-aware _arena_for_type path)
    arena = agg._arena_for_type(
        "histogram",
        MetricKey("veneur.rollup.histogram", "histogram",
                  "tenant:hog,veneur_rollup:true"))
    assert arena is agg.moments


def test_eviction_releases_from_the_arena_that_holds_the_key():
    """Payload-routed imports can land a histogram key in the moments
    arena on a tier whose RULES say tdigest (the supported cross-tier
    rules mismatch); the cardinality release path must free the row
    from the arena that actually holds it, not the rules-derived
    one."""
    agg = MetricAggregator(percentiles=[0.5],
                           cardinality_key_budget=2)
    key = MetricKey("imported.h", "histogram", "tenant:hog")
    dk = (key, MetricScope.MIXED)
    with agg.lock:
        row = agg.moments.row_for(key, MetricScope.MIXED,
                                  ["tenant:hog"])
        agg.moments.merge_moments(row, _vec_of([1.0, 2.0, 3.0]))
    assert dk in agg.moments.kdict

    class StubGuard:
        def end_interval(self, cb):
            cb([dk])
            return 1

    agg.cardinality = StubGuard()
    agg._cardinality_end_interval()
    assert dk not in agg.moments.kdict     # released, not skipped
    assert agg.moments.ivec[row, 0] == 0.0


def test_config_mesh_policy_is_per_family():
    from veneur_tpu import config as config_mod
    # moments + mesh is allowed: the maxent solve shards over the key
    # axis (single-process; multi-process is rejected at runtime by
    # the aggregator where process_count is known)
    config_mod.Config(
        mesh_devices=2,
        sketch_family_rules=[{"match": "a*",
                              "family": "moments"}]).apply_defaults()
    # compactor + mesh stays rejected at boot
    with pytest.raises(ValueError, match="mesh"):
        config_mod.Config(
            mesh_devices=2,
            sketch_family_rules=[{"match": "a*",
                                  "family": "compactor"}]).apply_defaults()
    with pytest.raises(ValueError, match="unknown sketch family"):
        config_mod.Config(
            sketch_family_default="req").apply_defaults()


# ---------------------------------------------------------------------------
# tier-1 mixed-family testbed cell
# ---------------------------------------------------------------------------

def test_mixed_family_testbed_cell_conserves_exactly():
    """Both families live in one 3-tier cluster: exact count
    conservation for every histogram key, per-family percentile
    envelopes, counters/sets exact — the ISSUE-13 acceptance cell."""
    from veneur_tpu.testbed.dryrun import run_dryrun
    report = run_dryrun(n_locals=2, n_globals=1, intervals=2, seed=13,
                        counter_keys=4, histo_keys=2, set_keys=1,
                        histo_samples=120, moments_histo_keys=2)
    assert report["ok"], report
    sf = report["sketch_families"]
    assert sf["histo_counts_exact"]
    assert sf["histo_keys_by_family"] == {"tdigest": 2, "moments": 2}
    assert sf["quantiles_checked_by_family"]["moments"] == \
        2 * 2 * 3                           # keys x intervals x pctiles
    assert report["conservation"]["counters_exact"]
    assert report["conservation"]["sets_exact"]


# ---------------------------------------------------------------------------
# meshed maxent solver: key-axis sharding bit-parity (ISSUE 19)
# ---------------------------------------------------------------------------

def _mesh_flush_inputs(rng, u=24, d=64, k=8):
    dv = rng.lognormal(0.5, 1.0, (u, d)).astype(np.float32)
    dw = np.ones((u, d), np.float32)
    dep = np.full(u, d, np.int16)
    a, b = dv.min(axis=1), dv.max(axis=1)
    ab = np.stack([a, b]).astype(np.float32)
    lab = np.stack([np.log(a), np.log(b)]).astype(np.float32)
    imp = np.zeros((u, 2 * (k + 1)), np.float32)
    return dv, dw, dep, ab, lab, imp


@pytest.mark.parametrize("ndev", [2, 8])
def test_meshed_moments_flush_bit_parity(ndev):
    """The key-axis-sharded solver must return the SAME BITS as the
    unmeshed program — both the general and uniform-depth variants.
    The solver is row-local, so the only parity hazards are batch-
    shape-dependent lowerings (the reason _chol_solve replaced
    jnp.linalg.solve); any regression there lands here first."""
    import jax
    from veneur_tpu.parallel import mesh as mesh_mod
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    rng = np.random.default_rng(19)
    dv, dw, dep, ab, lab, imp = _mesh_flush_inputs(rng)
    pct = np.asarray([0.5, 0.9, 0.99], np.float32)

    base = me.make_moments_flush(8)
    fn = me.make_moments_flush(8, mesh=mesh_mod.make_mesh(ndev))
    out0 = np.asarray(base(dv, dw, ab, lab, imp, pct))
    out1 = np.asarray(fn(dv, dw, ab, lab, imp, pct))
    assert (out0 == out1).all(), np.abs(out0 - out1).max()
    u0 = np.asarray(base.depth_variant(dv, dep, ab, lab, imp, pct))
    u1 = np.asarray(fn.depth_variant(dv, dep, ab, lab, imp, pct))
    assert (u0 == u1).all(), np.abs(u0 - u1).max()


def test_meshed_moments_flush_pads_ragged_rows():
    """Row counts that don't divide the device count zero-pad
    in-program and slice back; the visible rows still match the
    unmeshed program bit-for-bit."""
    import jax
    from veneur_tpu.parallel import mesh as mesh_mod
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    rng = np.random.default_rng(7)
    dv, dw, _, ab, lab, imp = _mesh_flush_inputs(rng, u=13)
    pct = np.asarray([0.5, 0.99], np.float32)
    base = me.make_moments_flush(8)
    fn = me.make_moments_flush(8, mesh=mesh_mod.make_mesh(8))
    out0 = np.asarray(base(dv, dw, ab, lab, imp, pct))
    out1 = np.asarray(fn(dv, dw, ab, lab, imp, pct))
    assert out1.shape == out0.shape
    assert (out0 == out1).all(), np.abs(out0 - out1).max()


def test_chol_solve_is_batch_shape_stable():
    """The unrolled Cholesky must give identical bits for a row whether
    it's solved in a batch of 3 or sliced from a batch of 24 — the
    property LAPACK batched LU lacks and mesh parity stands on."""
    import jax
    rng = np.random.default_rng(0)
    n = 9
    h = rng.normal(0, 1, (24, n, n)).astype(np.float32)
    h = h @ h.transpose(0, 2, 1) + 3 * np.eye(n, dtype=np.float32)
    g = rng.normal(0, 1, (24, n)).astype(np.float32)
    f = jax.jit(me._chol_solve)
    full = np.asarray(f(h, g))
    part = np.asarray(f(h[:3], g[:3]))
    assert (full[:3] == part).all()
    # and it actually solves: residual at f32 scale
    r = np.einsum("uij,uj->ui", h, full) - g
    assert np.abs(r).max() < 1e-3, np.abs(r).max()
