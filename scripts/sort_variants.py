"""Microbench: bitonic compare-exchange stage formulations on the chip.

Variants of the [D, T]-tile sort network, timed with the in-launch scan
harness (launch cost amortized out).  All variants must produce the same
sorted keys + paired weights; v0 is the production kernel's current
formulation.  Input values are quantized to bf16-exact so the compact
(16-bit key) formulations are output-identical to the f32 ones — the
quantization changes no variant's instruction mix.

Compact-key formulations (v3 kernel evidence; ops/sorted_eval.py):
  c0  packed (bf16-key | depth-index) int32 single-array network +
      permutation-apply weight reconstruct — the production
      `compact=True` general kernel's formulation.  Stage cost ~6
      passes vs the paired form's ~11, paid back by O(D) selects in the
      reconstruct: the crossover depth measured here is what
      MAX_COMPACT_DEPTH pins.
  c1  bf16 key-only network, widen after the last stage — the
      uniform/depth-vector kernel's 16-bit path (no payload at all;
      legal on this harness because the weights are all 1).

Usage: python scripts/sort_variants.py [K] [D] [inner] [pipeline] [modes]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, "/root/repo")

from veneur_tpu.ops import sorted_eval as se
from veneur_tpu.util import compile_cache

_PAD = se._PAD_KEY


def _stage_v0(key, w, j, k, idx):
    return se._cmp_exchange(key, w, j, k, idx)


def _stage_v1(key, w, j, k, idx):
    """min/max + moved-mask: 2 fewer compares, 2 fewer logic ops."""
    d = key.shape[0]
    lower = (idx & j) == 0
    pk = jnp.where(lower, pltpu.roll(key, d - j, axis=0),
                   pltpu.roll(key, j, axis=0))
    pw = jnp.where(lower, pltpu.roll(w, d - j, axis=0),
                   pltpu.roll(w, j, axis=0))
    up = (idx & k) == 0
    want_small = lower == up
    newkey = jnp.where(want_small, jnp.minimum(key, pk),
                       jnp.maximum(key, pk))
    moved = newkey != key
    return newkey, jnp.where(moved, pw, w)


def _stage_v2(key, w, j, k, idx1):
    """v1 with [D, 1] row masks broadcast instead of full [D, T] iota."""
    d = key.shape[0]
    lower = (idx1 & j) == 0
    up = (idx1 & k) == 0
    want_small = lower == up
    pk = jnp.where(lower, pltpu.roll(key, d - j, axis=0),
                   pltpu.roll(key, j, axis=0))
    pw = jnp.where(lower, pltpu.roll(w, d - j, axis=0),
                   pltpu.roll(w, j, axis=0))
    newkey = jnp.where(want_small, jnp.minimum(key, pk),
                       jnp.maximum(key, pk))
    moved = newkey != key
    return newkey, jnp.where(moved, pw, w)


def _xorshuf(x, j):
    """Partner gather idx ^ j via reshape + flip of the 2-block axis."""
    d, t = x.shape
    return jnp.flip(x.reshape(d // (2 * j), 2, j, t), axis=1).reshape(d, t)


def _stage_v3(key, w, j, k, idx1):
    """xor-shuffle partner (single flip) + min/max + moved-mask."""
    lower = (idx1 & j) == 0
    up = (idx1 & k) == 0
    want_small = lower == up
    pk = _xorshuf(key, j)
    pw = _xorshuf(w, j)
    newkey = jnp.where(want_small, jnp.minimum(key, pk),
                       jnp.maximum(key, pk))
    moved = newkey != key
    return newkey, jnp.where(moved, pw, w)


def _xorshuf_concat(x, j):
    """Partner idx ^ j via static slices: swap halves of each 2j block."""
    d = x.shape[0]
    parts = []
    for base in range(0, d, 2 * j):
        parts.append(x[base + j:base + 2 * j])
        parts.append(x[base:base + j])
    return jnp.concatenate(parts, axis=0)


def _stage_v5(key, w, j, k, idx1):
    """concat-slice partner for j>=8, roll-based for smaller strides."""
    d = key.shape[0]
    lower = (idx1 & j) == 0
    up = (idx1 & k) == 0
    want_small = lower == up
    if j >= 8:
        pk = _xorshuf_concat(key, j)
        pw = _xorshuf_concat(w, j)
    else:
        pk = jnp.where(lower, pltpu.roll(key, d - j, axis=0),
                       pltpu.roll(key, j, axis=0))
        pw = jnp.where(lower, pltpu.roll(w, d - j, axis=0),
                       pltpu.roll(w, j, axis=0))
    newkey = jnp.where(want_small, jnp.minimum(key, pk),
                       jnp.maximum(key, pk))
    moved = newkey != key
    return newkey, jnp.where(moved, pw, w)


def _stage_v6(key, w, j, k, idx1):
    """concat-slice partner at every stride."""
    lower = (idx1 & j) == 0
    up = (idx1 & k) == 0
    want_small = lower == up
    pk = _xorshuf_concat(key, j)
    pw = _xorshuf_concat(w, j)
    newkey = jnp.where(want_small, jnp.minimum(key, pk),
                       jnp.maximum(key, pk))
    moved = newkey != key
    return newkey, jnp.where(moved, pw, w)




STAGES = {"v0": (_stage_v0, 2), "v1": (_stage_v1, 2),
          "v2": (_stage_v2, 1), "v3": (_stage_v3, 1)}
STAGES["v5"] = (_stage_v5, 1)
STAGES["v6"] = (_stage_v6, 1)

COMPACT_MODES = ("c0", "c1")


def _emit(key, w, out_ref):
    d = key.shape[0]
    out_ref[...] = jnp.concatenate(
        [key[0:1], key[d // 2:d // 2 + 1],
         jnp.sum(key * jnp.where(key != _PAD, w, 0.0),
                 axis=0, keepdims=True)], axis=0)


def _kernel_c0(mean_ref, weight_ref, out_ref):
    """Packed compact general network (production compact=True)."""
    m = mean_ref[...]
    w = weight_ref[...]
    d, t = m.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (d, t), 0)
    key, w_s = se._compact_sort_tile(m, w, idx)
    # padding reconstructs as +inf like the f32 network's pad key
    key = jnp.where(w_s > 0, key, _PAD)
    _emit(key, w_s, out_ref)


def _kernel_c1(mean_ref, weight_ref, out_ref):
    """bf16 key-only network (the uniform/depth kernels' 16-bit path);
    weights are all 1 on this harness, so sorted keys + the pre-sort
    weight array emit the same outputs as the paired variants."""
    m = mean_ref[...]
    w = weight_ref[...]
    d, t = m.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (d, t), 0)
    key = jnp.where(w > 0, m.astype(jnp.bfloat16),
                    jnp.asarray(_PAD, jnp.bfloat16))
    key = se._sort_keys(key, idx).astype(jnp.float32)
    _emit(key, w, out_ref)


def make_kernel(mode: str):
    if mode == "c0":
        return _kernel_c0
    if mode == "c1":
        return _kernel_c1
    stage, iota_kind = STAGES[mode]

    def kernel(mean_ref, weight_ref, out_ref):
        m = mean_ref[...]
        w = weight_ref[...]
        d, t = m.shape
        if iota_kind == 2:
            idx = jax.lax.broadcasted_iota(jnp.int32, (d, t), 0)
        else:
            idx = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
        key = jnp.where(w > 0, m, _PAD)
        k = 2
        while k <= d:
            j = k // 2
            while j >= 1:
                key, w = stage(key, w, j, k, idx)
                j //= 2
            k *= 2
        _emit(key, w, out_ref)
    return kernel


def run(mode, mt, wt, tile):
    d, u = mt.shape
    return pl.pallas_call(
        make_kernel(mode),
        grid=(u // tile,),
        in_specs=[pl.BlockSpec((d, tile), lambda i: (0, i)),
                  pl.BlockSpec((d, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((3, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((3, u), jnp.float32),
    )(mt, wt)


def main():
    k = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    inner = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    pipeline = int(sys.argv[4]) if len(sys.argv) > 4 else 8
    modes = (sys.argv[5].split(",") if len(sys.argv) > 5
             else list(STAGES) + list(COMPACT_MODES))

    compile_cache.enable(min_compile_secs=0.0)
    print(f"device: {jax.devices()[0]} K={k} D={d} inner={inner} "
          f"pipeline={pipeline}", flush=True)
    rng = np.random.default_rng(0)
    import ml_dtypes
    vals = (rng.gamma(2.0, 10.0, (d, k)).astype(np.float32)
            .astype(ml_dtypes.bfloat16).astype(np.float32))
    mt = jax.device_put(vals)   # bf16-exact: compact modes match v0
    wt = jax.device_put(np.ones((d, k), np.float32))
    tile = se._lane_tile(k, d)
    if "c0" in modes and d > se.MAX_COMPACT_DEPTH:
        print(f"c0 skipped: d={d} > MAX_COMPACT_DEPTH="
              f"{se.MAX_COMPACT_DEPTH} (the permutation-apply "
              f"reconstruct is O(D) selects)", flush=True)
        modes = [m for m in modes if m != "c0"]

    # correctness vs v0 first (on a small slice, via CPU comparison)
    small_m, small_w = np.asarray(mt[:, :tile]), np.asarray(wt[:, :tile])
    ref = None
    for mode in modes:
        out = np.asarray(run(mode, jnp.asarray(small_m),
                             jnp.asarray(small_w), tile))
        if ref is None:
            ref = out
        else:
            if not np.allclose(out, ref, rtol=1e-6, atol=1e-6):
                print(f"{mode}: OUTPUT MISMATCH vs v0 "
                      f"(max diff {np.abs(out - ref).max()})", flush=True)
                continue
        for r in range(3):
            pass
    for mode in modes:
        def body(carry, _, _mode=mode):
            out = run(_mode, mt + carry * 1e-12, wt, tile)
            return carry + out[2, 0] * 1e-20 + 1.0, ()

        def looped(c0, _mode=mode):
            c, _ = jax.lax.scan(body, c0, None, length=inner)
            return c

        jfn = jax.jit(looped)
        t0 = time.perf_counter()
        float(np.asarray(jfn(jnp.float32(0.0))))
        compile_s = time.perf_counter() - t0
        float(np.asarray(jfn(jnp.float32(1.0))))
        per = []
        for r in range(3):
            t0 = time.perf_counter()
            y = jnp.float32(float(r))
            for _ in range(pipeline):
                y = jfn(y)
            float(np.asarray(y))
            per.append((time.perf_counter() - t0) / (pipeline * inner)
                       * 1e3)
        p50 = float(np.percentile(per, 50))
        print(f"{mode:4s} p50={p50:8.4f} ms/sort  (compile {compile_s:.1f}s)",
              flush=True)


if __name__ == "__main__":
    main()
