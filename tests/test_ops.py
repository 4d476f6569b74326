"""Pallas ops parity tests: the hand-tiled kernels must match their XLA
twins exactly (same outputs)."""

import numpy as np
import jax.numpy as jnp


def test_sorted_eval_pallas_parity_interpret():
    """The fused Pallas flush kernel (ops/sorted_eval.py) must match the
    XLA weighted_eval on dense/sparse/tied/empty/single-point rows."""
    import numpy as np

    from veneur_tpu.ops import sorted_eval as se
    from veneur_tpu.sketches import tdigest as td

    rng = np.random.default_rng(3)
    for (u, d) in ((64, 32), (16, 256), (8, 2), (32, 512), (256, 4),
                   (8, 1024)):
        m = rng.gamma(2.0, 10.0, (u, d)).astype(np.float32)
        w = ((rng.random((u, d)) < 0.7)
             * rng.integers(1, 4, (u, d))).astype(np.float32)
        m[1, :] = 5.0                    # ties: pairs must not split
        w[2, :] = 0.0                    # empty row
        w[3, :] = 0.0
        w[3, 0] = 2.0                    # single-point row
        dmin = np.where(w.sum(1) > 0,
                        np.where(w > 0, m, np.inf).min(1), 0.0)
        dmax = np.where(w.sum(1) > 0,
                        np.where(w > 0, m, -np.inf).max(1), 0.0)
        pct = jnp.asarray([0.5, 0.9, 0.99], jnp.float32)
        ref = np.asarray(td.weighted_eval(
            jnp.asarray(m), jnp.asarray(w),
            jnp.asarray(dmin.astype(np.float32)),
            jnp.asarray(dmax.astype(np.float32)), pct))
        got = np.asarray(se.weighted_eval(
            jnp.asarray(m), jnp.asarray(w),
            jnp.asarray(dmin.astype(np.float32)),
            jnp.asarray(dmax.astype(np.float32)), pct, interpret=True))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4,
                                   err_msg=f"{u}x{d}")


def test_sorted_eval_usable_predicate():
    from veneur_tpu.ops import sorted_eval as se
    assert se.usable(256, 256, "tpu")
    assert se.usable(512, 256, "tpu")
    assert se.usable(128, 256, "tpu")        # one full lane tile
    assert se.usable(131072, 4, "tpu")       # shallow prod depth
    assert se.usable(16384, 1024, "tpu")     # max depth
    assert not se.usable(256, 256, "cpu")
    assert not se.usable(256, 3, "tpu")      # non-pow2 depth
    assert not se.usable(256, 2048, "tpu")   # past MAX_DEPTH
    assert not se.usable(24, 256, "tpu")     # sub-lane-tile key count
    assert not se.usable(4, 256, "tpu")
    assert se.usable(384, 256, "tpu")        # single 384-lane tile
    # not a whole number of lane tiles: trailing keys would be
    # unwritten garbage
    assert not se.usable(131072 + 128, 256, "tpu")


def test_sorted_eval_extreme_float32_values():
    """Values near float32 max must sort before the +inf padding key —
    a finite sentinel would order them after padding and corrupt the
    quantiles (review finding)."""
    import numpy as np

    from veneur_tpu.ops import sorted_eval as se
    from veneur_tpu.sketches import tdigest as td

    m = np.zeros((8, 8), np.float32)
    w = np.zeros((8, 8), np.float32)
    m[0, :3] = [1.0, 3.3e38, 2.0]
    w[0, :3] = 1.0
    dmin = np.array([1.0] + [0] * 7, np.float32)
    dmax = np.array([3.3e38] + [0] * 7, np.float32)
    pct = jnp.asarray([0.5, 0.99], jnp.float32)
    ref = np.asarray(td.weighted_eval(
        jnp.asarray(m), jnp.asarray(w), jnp.asarray(dmin),
        jnp.asarray(dmax), pct))
    got = np.asarray(se.weighted_eval(
        jnp.asarray(m), jnp.asarray(w), jnp.asarray(dmin),
        jnp.asarray(dmax), pct, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    assert got[0, 0] == 2.0  # median of {1, 2, 3.3e38}


def test_sorted_eval_uniform_kernel_parity_interpret():
    """The uniform-weight specialization (key-only sort network) must be
    numerically identical to the general kernel AND the XLA twin on
    w in {0, 1} inputs — including empty rows, single-point rows, ties,
    and padding columns."""
    import numpy as np

    from veneur_tpu.ops import sorted_eval as se
    from veneur_tpu.sketches import tdigest as td

    rng = np.random.default_rng(11)
    for (u, d) in ((64, 32), (16, 256), (8, 2), (256, 4)):
        m = rng.gamma(2.0, 10.0, (u, d)).astype(np.float32)
        w = (rng.random((u, d)) < 0.7).astype(np.float32)  # 0/1 only
        m[1, :] = 5.0                    # ties
        w[2, :] = 0.0                    # empty row
        w[3, :] = 0.0
        w[3, 0] = 1.0                    # single-point row
        dmin = np.where(w.sum(1) > 0,
                        np.where(w > 0, m, np.inf).min(1), 0.0)
        dmax = np.where(w.sum(1) > 0,
                        np.where(w > 0, m, -np.inf).max(1), 0.0)
        pct = jnp.asarray([0.5, 0.9, 0.99], jnp.float32)
        args = (jnp.asarray(m), jnp.asarray(w),
                jnp.asarray(dmin.astype(np.float32)),
                jnp.asarray(dmax.astype(np.float32)), pct)
        ref = np.asarray(td.weighted_eval(*args))
        general = np.asarray(se.weighted_eval(*args, interpret=True))
        fast = np.asarray(se.weighted_eval(*args, interpret=True,
                                           uniform=True))
        np.testing.assert_allclose(general, ref, rtol=1e-5, atol=1e-4,
                                   err_msg=f"general {u}x{d}")
        # identical arithmetic on w in {0,1}: positions are exact f32
        # integers, so the two networks agree exactly
        np.testing.assert_array_equal(fast, general,
                                      err_msg=f"uniform {u}x{d}")


def test_uniform_depth_vector_eval_parity_interpret():
    """The depth-vector kernel (no weight matrix crosses HBM) must equal
    the general kernel and XLA twin for contiguously-packed weight-1
    points."""
    import numpy as np

    from veneur_tpu.ops import sorted_eval as se
    from veneur_tpu.sketches import tdigest as td

    rng = np.random.default_rng(13)
    for (u, d) in ((64, 32), (16, 256), (256, 4)):
        m = rng.gamma(2.0, 10.0, (u, d)).astype(np.float32)
        depths = rng.integers(0, d + 1, u).astype(np.int32)
        depths[2] = 0                    # empty row
        depths[3] = 1                    # single-point row
        w = (np.arange(d)[None, :] < depths[:, None]).astype(np.float32)
        m[w == 0] = 0.0                  # padding cells are zeros (builder)
        dmin = np.where(depths > 0,
                        np.where(w > 0, m, np.inf).min(1), 0.0)
        dmax = np.where(depths > 0,
                        np.where(w > 0, m, -np.inf).max(1), 0.0)
        pct = jnp.asarray([0.5, 0.9, 0.99], jnp.float32)
        ref = np.asarray(td.weighted_eval(
            jnp.asarray(m), jnp.asarray(w),
            jnp.asarray(dmin.astype(np.float32)),
            jnp.asarray(dmax.astype(np.float32)), pct))
        got = np.asarray(se.uniform_eval(
            jnp.asarray(m), jnp.asarray(depths), pct, interpret=True))
        # the depth kernel returns the quantile columns only (totals
        # come from host accumulators)
        np.testing.assert_allclose(got, ref[:, :3], rtol=1e-5,
                                   atol=1e-4, err_msg=f"{u}x{d}")


def test_lane_tile_wide_boundary():
    """The wide (1024-lane) tile applies only to the key-only kernel at
    large 1024-divisible key counts; every previously-usable shape keeps
    the Pallas path and the general kernels keep 512-lane tiles."""
    from veneur_tpu.ops import sorted_eval as se

    # general kernels: unchanged sizing
    assert se._lane_tile(131072, 256) == 512
    assert se._lane_tile(131072, 512) == 256
    # wide: engages only at >=65536 AND 1024-divisible
    assert se._lane_tile(131072, 256, wide=True) == 1024
    assert se._lane_tile(65536, 256, wide=True) == 1024
    assert se._lane_tile(66048, 256, wide=True) == 512   # not /1024
    assert se._lane_tile(32768, 256, wide=True) == 512   # below cutoff
    assert se._lane_tile(131072, 512, wide=True) == 256  # deep: VMEM
    # usable() keeps accepting every 512-multiple shape it accepted
    assert se.usable(66048, 256, "tpu")
    assert se.usable(65536, 256, "tpu")
    assert se.usable(131072, 256, "tpu")
