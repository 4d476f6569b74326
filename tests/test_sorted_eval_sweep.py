"""v3 fused-flush-kernel sweeps: parity across depths/tilings/dtypes,
and the tiling-invariance regression.

Exactness contract (what each assertion pins):

  * **Pallas vs Pallas is BITWISE.**  Every (tile, nbuf) launch shape,
    the classic and DMA pipelines, and the bf16-native vs
    widened-f32 key networks must produce byte-identical outputs for
    the same input — a tiling change can never ship a silent numeric
    drift.  (The DMA pipeline's sub-tile loop is a fori_loop
    specifically so all sub-tiles run one compiled body; unrolled
    instances were observed to pick per-instance FMA contraction.)
  * **Kernel vs XLA twin is BIT-IDENTICAL on exactness-preserving
    data.**  Integer-valued inputs make every sum/cumsum exact in any
    association, and the two per-program FMA/FMS contraction sites in
    the quantile tail are pinned (sorted_eval._pin, applied identically
    in the twin), so every remaining op is a single IEEE operation —
    the kernel must reproduce the twin's bytes exactly.  Float-valued
    production data additionally differs only by summation-order ulps
    (covered by the existing rtol parity tests in test_ops.py).
  * **The compact (packed-key) network is STABLE**, matching
    `lax.sort`'s tie order exactly — unlike the f32 paired bitonic
    network, whose equal-valued points may order arbitrarily (pair-
    consistent either way).  Compact parity is therefore asserted on
    tied data too; paired-network parity uses tie-free rows.

The fast subset runs in tier-1; the full depth x tile sweep is
slow-marked (ROADMAP tier-1 runs `-m 'not slow'`).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from veneur_tpu.ops import sorted_eval as se
from veneur_tpu.sketches import tdigest as td

PCT = (0.1, 0.5, 0.9, 0.99)


def _edge_case_inputs(u, d, rng, tie_free=False, max_w=4, vmax=200):
    """Integer-valued rows with the adversarial edge rows of the
    existing parity tests: an all-tied row, an empty row, a single-point
    row, plus zero-weight holes.  Integer values and weights keep every
    sum/cumsum exact in any association, so only FMA ulps can separate
    the kernel from the twin.  `vmax <= 256` makes every value
    bf16-representable (the compact network's legality gate)."""
    if tie_free:
        # distinct values per row: choice without replacement
        m = np.stack([rng.choice(1 << 16, d, replace=False)
                      for _ in range(u)]).astype(np.float32)
    else:
        m = rng.integers(0, vmax, (u, d)).astype(np.float32)
    w = ((rng.random((u, d)) < 0.7)
         * rng.integers(1, max_w, (u, d))).astype(np.float32)
    if not tie_free:
        m[1, :] = 5.0                # whole-row tie
    w[2, :] = 0.0                    # empty row
    w[3, :] = 0.0
    w[3, 0] = 2.0                    # single-point row
    dmin = np.where(w.sum(1) > 0, np.where(w > 0, m, np.inf).min(1), 0.0)
    dmax = np.where(w.sum(1) > 0, np.where(w > 0, m, -np.inf).max(1),
                    0.0)
    return (jnp.asarray(m), jnp.asarray(w),
            jnp.asarray(dmin.astype(np.float32)),
            jnp.asarray(dmax.astype(np.float32)),
            jnp.asarray(PCT, jnp.float32))


def _assert_twin_parity(got, ref, label):
    # bit-identical: integer data + the pinned contraction sites leave
    # no op whose result is program-dependent
    np.testing.assert_array_equal(got, ref, err_msg=label)


def _sweep_point(u, d, seed):
    rng = np.random.default_rng(seed)
    args = _edge_case_inputs(u, d, rng, tie_free=True)
    ref = np.asarray(td.weighted_eval(*args))
    general = np.asarray(se.weighted_eval(*args, interpret=True))
    _assert_twin_parity(general, ref, f"general {u}x{d}")
    if d <= se.MAX_COMPACT_DEPTH:
        # same canonical edge-row set (ties, empty row, single-point
        # row, zero-weight holes) with bf16-exact values — the compact
        # network's legality gate
        rng2 = np.random.default_rng(seed + 1)
        cargs = _edge_case_inputs(u, d, rng2, vmax=250)
        cref = np.asarray(td.weighted_eval(*cargs))
        compact = np.asarray(se.weighted_eval(*cargs, interpret=True,
                                              compact=True))
        _assert_twin_parity(compact, cref, f"compact {u}x{d}")


def test_parity_sweep_fast():
    """Tier-1 sweep: the shallow/production depths with edge rows."""
    for i, (u, d) in enumerate(((256, 4), (128, 8), (64, 64))):
        _sweep_point(u, d, 100 + i)


@pytest.mark.slow
def test_parity_sweep_full():
    """Full depth x tile-width sweep (satellite: depths {4, 8, 64, 256,
    1024}, tiles {128, 512, 1024})."""
    for i, d in enumerate((4, 8, 64, 256)):
        rng = np.random.default_rng(200 + i)
        u = 2048
        args = _edge_case_inputs(u, d, rng, tie_free=True)
        ref = np.asarray(td.weighted_eval(*args))
        base = None
        for tile in (128, 512, 1024):
            got = np.asarray(se.weighted_eval(*args, interpret=True,
                                              tile=tile, nbuf=1))
            _assert_twin_parity(got, ref, f"{u}x{d} tile={tile}")
            if base is None:
                base = got
            else:
                np.testing.assert_array_equal(
                    got, base, err_msg=f"{u}x{d} tile={tile} drifted")
        _sweep_point(256, d, 300 + i)
    # max depth: smaller u bounds the interpret-mode runtime
    rng = np.random.default_rng(299)
    args = _edge_case_inputs(256, 1024, rng, tie_free=True)
    ref = np.asarray(td.weighted_eval(*args))
    for tile in (128, 256):
        got = np.asarray(se.weighted_eval(*args, interpret=True,
                                          tile=tile, nbuf=1))
        _assert_twin_parity(got, ref, f"256x1024 tile={tile}")


def test_tiling_and_grid_invariance():
    """Satellite regression: kernel output is invariant to lane-tile
    width AND grid coarseness (classic vs DMA pipeline, any nbuf) —
    identical BYTES, so tiling changes can never ship numeric drift."""
    rng = np.random.default_rng(11)
    u, d = 1024, 16
    args = _edge_case_inputs(u, d, rng)
    base = np.asarray(se.weighted_eval(*args, interpret=True,
                                       tile=128, nbuf=1))
    for tile, nbuf in ((128, 2), (128, 4), (256, 1), (256, 4),
                       (512, 1), (512, 2), (1024, 1)):
        got = np.asarray(se.weighted_eval(*args, interpret=True,
                                          tile=tile, nbuf=nbuf))
        np.testing.assert_array_equal(
            got, base, err_msg=f"general tile={tile} nbuf={nbuf}")
    # default (auto) tiling is one of the swept configurations
    auto = np.asarray(se.weighted_eval(*args, interpret=True))
    np.testing.assert_array_equal(auto, base, err_msg="auto tiling")

    # depth-vector kernel: same invariance
    depths = rng.integers(0, d + 1, u).astype(np.int32)
    depths[2] = 0
    m = np.asarray(args[0])
    m = np.where(np.arange(d)[None, :] < depths[:, None], m,
                 0.0).astype(np.float32)
    pct = jnp.asarray(PCT, jnp.float32)
    ubase = np.asarray(se.uniform_eval(jnp.asarray(m),
                                       jnp.asarray(depths), pct,
                                       interpret=True, tile=128, nbuf=1))
    for tile, nbuf in ((128, 4), (256, 2), (512, 2), (1024, 1)):
        got = np.asarray(se.uniform_eval(jnp.asarray(m),
                                         jnp.asarray(depths), pct,
                                         interpret=True, tile=tile,
                                         nbuf=nbuf))
        np.testing.assert_array_equal(
            got, ubase, err_msg=f"uniform tile={tile} nbuf={nbuf}")


def test_compact_network_is_stable_on_ties():
    """The packed compact network's index payload makes it STABLE: on
    adversarial tie runs with differing weights — where the f32 paired
    bitonic network may legitimately order equal values arbitrarily —
    compact must still match the (stable lax.sort) twin."""
    rng = np.random.default_rng(3)
    u, d = 64, 8
    m = rng.integers(0, 4, (u, d)).astype(np.float32) * 2.0
    w = rng.integers(1, 5, (u, d)).astype(np.float32)
    dmin = np.where(w > 0, m, np.inf).min(1)
    dmax = np.where(w > 0, m, -np.inf).max(1)
    args = (jnp.asarray(m), jnp.asarray(w),
            jnp.asarray(dmin.astype(np.float32)),
            jnp.asarray(dmax.astype(np.float32)),
            jnp.asarray(PCT, jnp.float32))
    ref = np.asarray(td.weighted_eval(*args))
    compact = np.asarray(se.weighted_eval(*args, interpret=True,
                                          compact=True))
    _assert_twin_parity(compact, ref, "compact ties")


def test_bf16_native_sort_is_exact():
    """The compact-key legality argument, asserted directly: sorting
    bf16-staged values at 16-bit width and widening AFTER the network is
    byte-identical to widening first and sorting at f32 — bf16 -> f32 is
    monotone and injective, so the sort order commutes with widening.
    Also checks the depth-vector kernel against the XLA twin fed the
    widened values."""
    import ml_dtypes
    rng = np.random.default_rng(17)
    for (u, d) in ((128, 32), (256, 4)):
        m = rng.normal(50, 20, (u, d)).astype(np.float32)
        depths = rng.integers(0, d + 1, u).astype(np.int32)
        depths[2] = 0                    # empty row
        depths[3] = 1                    # single-point row
        occ = np.arange(d)[None, :] < depths[:, None]
        m = np.where(occ, m, 0.0).astype(np.float32)
        mb = m.astype(ml_dtypes.bfloat16)
        mw = mb.astype(np.float32)       # the widened-first values
        pct = jnp.asarray(PCT, jnp.float32)

        narrow = np.asarray(se.uniform_eval(
            jnp.asarray(mb), jnp.asarray(depths), pct, interpret=True))
        wide = np.asarray(se.uniform_eval(
            jnp.asarray(mw), jnp.asarray(depths), pct, interpret=True))
        np.testing.assert_array_equal(narrow, wide,
                                      err_msg=f"bf16 vs widened {u}x{d}")

        w = occ.astype(np.float32)
        dmin = np.where(depths > 0,
                        np.where(occ, mw, np.inf).min(1), 0.0)
        dmax = np.where(depths > 0,
                        np.where(occ, mw, -np.inf).max(1), 0.0)
        ref = np.asarray(td.weighted_eval(
            jnp.asarray(mw), jnp.asarray(w),
            jnp.asarray(dmin.astype(np.float32)),
            jnp.asarray(dmax.astype(np.float32)), pct))[:, :len(PCT)]
        np.testing.assert_array_equal(narrow, ref,
                                      err_msg=f"bf16 vs twin {u}x{d}")

        # the uniform (key-only) network inside weighted_eval takes the
        # same bf16-native path (digest_eval routes uniform bf16
        # intervals here, NOT to the compact network)
        uargs = (jnp.asarray(w), jnp.asarray(dmin.astype(np.float32)),
                 jnp.asarray(dmax.astype(np.float32)), pct)
        u_narrow = np.asarray(se.weighted_eval(
            jnp.asarray(mw).astype(jnp.bfloat16), *uargs,
            interpret=True, uniform=True))
        u_wide = np.asarray(se.weighted_eval(
            jnp.asarray(mw), *uargs, interpret=True, uniform=True))
        np.testing.assert_array_equal(
            u_narrow, u_wide, err_msg=f"uniform bf16 vs f32 {u}x{d}")


def test_compact_general_accepts_bf16_blocks():
    """digest_eval's compact route hands the kernel bf16 VALUE blocks
    with f32 weights (arena compact_general staging): same bytes as the
    f32-block compact path."""
    import ml_dtypes
    rng = np.random.default_rng(23)
    u, d = 128, 16
    m = rng.integers(0, 250, (u, d)).astype(np.float32)
    w = rng.integers(0, 3, (u, d)).astype(np.float32)
    dmin = np.where(w.sum(1) > 0, np.where(w > 0, m, np.inf).min(1), 0.0)
    dmax = np.where(w.sum(1) > 0, np.where(w > 0, m, -np.inf).max(1),
                    0.0)
    pct = jnp.asarray(PCT, jnp.float32)
    common = (jnp.asarray(w), jnp.asarray(dmin.astype(np.float32)),
              jnp.asarray(dmax.astype(np.float32)), pct)
    f32_blocks = np.asarray(se.weighted_eval(
        jnp.asarray(m), *common, interpret=True, compact=True))
    bf16_blocks = np.asarray(se.weighted_eval(
        jnp.asarray(m.astype(ml_dtypes.bfloat16)), *common,
        interpret=True, compact=True))
    np.testing.assert_array_equal(bf16_blocks, f32_blocks)


def test_lane_tile_v3_and_compact_predicates():
    """v3 sizing: the paired network now gets 1024-wide tiles at
    d <= 128 (the VMEM budget of the doubled live set); the key-only
    cutoffs are unchanged; usable_compact bounds the packed network's
    permutation-apply depth."""
    # paired wide engages at shallow depth, big 1024-divisible counts
    assert se._lane_tile(131072, 128) == 1024
    assert se._lane_tile(65536, 32) == 1024
    assert se._lane_tile(66048, 128) == 512     # not /1024: fallback
    assert se._lane_tile(32768, 128) == 512     # below cutoff
    assert se._lane_tile(131072, 256) == 512    # paired d=256: unchanged
    # DMA coarsening: engages at >= 16 steps, divides evenly, else off
    assert se._auto_nbuf(131072, 512, 256) == 4
    assert se._auto_nbuf(4096, 512, 256) == 1
    assert se._auto_nbuf(16384, 1024, 128) == 4
    # ... and only where the [tile, d] row slices fill whole 128-lane
    # rows: Mosaic refuses the DMA of a narrower minor dimension, so
    # shallow depths keep the classic path at every key count
    assert se._auto_nbuf(131072, 1024, 32) == 1
    assert se._auto_nbuf(1048576, 1024, 64) == 1
    assert se.usable_compact(131072, 32, "tpu")
    assert se.usable_compact(131072, 64, "tpu")
    assert not se.usable_compact(131072, 128, "tpu")   # too deep
    assert not se.usable_compact(131072, 32, "cpu")
    # pack/unpack round-trips the full bf16 range including +-inf
    import ml_dtypes
    vals = np.asarray([-np.inf, -3e38, -1.5, -1e-30, 0.0, 1e-30, 2.5,
                       3e38, np.inf], np.float32).astype(ml_dtypes.bfloat16)
    order = np.argsort(vals.astype(np.float32), kind="stable")
    import jax
    idx = jnp.zeros(vals.shape, jnp.int32)
    word = np.asarray(se._pack_compact(jnp.asarray(vals), idx))
    assert (np.argsort(word, kind="stable") == order).all()
    back, _ = se._unpack_compact(jnp.asarray(word))
    np.testing.assert_array_equal(np.asarray(back).astype(np.float32),
                                  vals.astype(np.float32))
