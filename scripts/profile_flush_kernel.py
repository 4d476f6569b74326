"""Decompose the fused flush-eval kernel's device time on the real chip.

Times progressively larger slices of ops/sorted_eval.py under the
pipelined protocol (N launches, one value fetch), so the per-call
dispatch cost amortizes out:

  dma        read both [K, D] inputs, write a row-reduce -> HBM/launch floor
  sort       + full bitonic network                      -> sort cost
  cumsum     + MXU triangular prefix sum                 -> rank-base cost
  full       the production kernel (auto tile/nbuf)      -> + quantile passes
  full_nodma the production kernel, classic grid forced  -> DMA-pipeline A/B
  full_dma   the production kernel, nbuf=4 forced        -> DMA-pipeline A/B
  compact    the packed compact-key general network      -> v3 evidence
  depth      the depth-vector (uniform) kernel, f32      -> key-only network
  depth_bf16 the depth-vector kernel on bf16 staging     -> 16-bit keys
  xla        the lax.sort twin (td.weighted_eval)        -> XLA comparison
  moments    the moments-family flush (segmented-sum     -> the OTHER
             merge kernel + maxent solver,                  compute class
             ops/moments_eval.py depth variant)             (ROADMAP #3)
  moments_sums the merge kernel alone (no solver)        -> merge roofline
  delta      the host->HBM delta-chunk stream            -> chunk-size x
             (serving.resident_scatter assembly,            nbuf sweep with
             flush_resident_arenas' amortized upload)       overlap efficiency

Usage: python scripts/profile_flush_kernel.py [K] [D] [pipeline] [rounds]
       [modes]

`delta` is not a kernel slice: it sweeps the OTHER pipeline level — the
chunked host->device upload the resident delta flush amortizes across
the interval — and reports per-configuration wall time plus
sorted_eval.overlap_efficiency over the recorded per-chunk segments
(the same upload_s/dispatch_s/wait_s stats the aggregator's
`flush.seg.device` chunk spans carry).  K and D set the interval shape
(K keys x D points/key).
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, "/root/repo")

from veneur_tpu.ops import sorted_eval as se
from veneur_tpu.util import compile_cache
from veneur_tpu.sketches import tdigest as td


def run_variant(mode: str, mean, weight, minmax, qs, tile: int):
    u, d = mean.shape
    n_pct = qs.shape[1]
    if mode == "full":
        return se.weighted_eval(mean, weight, minmax[:, 0], minmax[:, 1],
                                qs[0])
    if mode == "full_nodma":
        return se.weighted_eval(mean, weight, minmax[:, 0], minmax[:, 1],
                                qs[0], nbuf=1)
    if mode == "full_dma":
        return se.weighted_eval(mean, weight, minmax[:, 0], minmax[:, 1],
                                qs[0], nbuf=4)
    if mode == "compact":
        if d > se.MAX_COMPACT_DEPTH:
            raise ValueError(f"compact needs D <= "
                             f"{se.MAX_COMPACT_DEPTH} (got {d})")
        return se.weighted_eval(mean, weight, minmax[:, 0], minmax[:, 1],
                                qs[0], compact=True)
    if mode in ("depth", "depth_bf16"):
        depths = jnp.full((u,), d, jnp.int32)
        mv = mean.astype(jnp.bfloat16) if mode == "depth_bf16" else mean
        return se.uniform_eval(mv, depths, qs[0])
    if mode == "xla":
        return td.weighted_eval(mean, weight, minmax[:, 0], minmax[:, 1],
                                qs[0])
    if mode in ("moments", "moments_sums"):
        from veneur_tpu.ops import moments_eval as me
        from veneur_tpu.sketches import moments as mo
        depths = jnp.full((u,), d, jnp.int16)
        a = minmax[:, 0]
        b = minmax[:, 1]
        # traced log_domain twin (the host helper is numpy)
        ok = a > 0
        la = jnp.where(ok, jnp.log(jnp.where(ok, a, 1.0)), 0.0)
        lb = jnp.where(ok, jnp.log(jnp.where(ok, jnp.maximum(b, a),
                                             1.0)), -1.0)
        ab = jnp.stack([a, b]).astype(jnp.float32)
        lab = jnp.stack([la, lb]).astype(jnp.float32)
        if mode == "moments_sums":
            return me.moments_sums(mean, depths, ab, lab,
                                   mo.DEFAULT_K, True)
        imp = jnp.zeros((u, 2 * (mo.DEFAULT_K + 1)), jnp.float32)
        fn = me.make_moments_flush()
        return fn.depth_variant(mean, depths, ab, lab, imp, qs[0])
    # cumulative stage cuts shared with bench.bench_kernel_stages:
    # built from the production stage functions (sorted_eval
    # stage_slice_kernel), so they cannot drift from the kernel
    kern = se.stage_slice_kernel("read" if mode == "dma" else mode)
    return pl.pallas_call(
        kern,
        grid=(u // tile,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, u), jnp.float32),
    )(mean, weight)


def run_delta_sweep(k: int, d: int, rounds: int) -> None:
    """Chunk-size x nbuf sweep of the resident delta stream: replay one
    interval's staged points (K keys x D points/key) through the
    production scatter-assembly chunks at each configuration, recording
    per-chunk upload/dispatch/wait segments and the pipeline's overlap
    efficiency.  Uses the copying scatter twin so the sweep is identical
    on every backend (donation is a separate axis, gated at runtime by
    serving.resident_donation_ok)."""
    from veneur_tpu.parallel import flush_step, serving

    total = k * d
    chunk_sizes = [c for c in (8192, 32768, 131072) if c <= total] or [total]
    for chunk_points in chunk_sizes:
        chunks, dense_id, expect_v, _ = flush_step.example_delta_chunks(
            n_keys=k, depth=d, chunk_points=chunk_points)
        # rehost: the sweep times the host->device crossing itself
        host = [{kk: np.asarray(v) for kk, v in c.items()} for c in chunks]
        did = jax.device_put(np.asarray(dense_id))
        jax.block_until_ready(did)
        for nbuf in (2, 4):
            walls, effs, last = [], [], None
            for _ in range(rounds):
                dense = serving.resident_dense_zeros(
                    shape=expect_v.shape, dtype=jnp.float32)
                jax.block_until_ready(dense)
                stats: list[dict] = []
                outs = [dense]
                t_wall = time.perf_counter()
                for i, ch in enumerate(host):
                    st: dict = {}
                    t0 = time.perf_counter()
                    dev = tuple(jax.device_put(ch[kk])
                                for kk in ("rows", "pos", "vals"))
                    st["upload_s"] = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    dense = serving.resident_scatter_copy(
                        dense, did, *dev)
                    st["dispatch_s"] = time.perf_counter() - t0
                    outs.append(dense)
                    if i + 1 >= nbuf:
                        # double-buffer backpressure: the chunk nbuf
                        # behind must have retired before we stage more
                        t0 = time.perf_counter()
                        jax.block_until_ready(outs[i + 2 - nbuf])
                        st["wait_s"] = time.perf_counter() - t0
                    stats.append(st)
                t0 = time.perf_counter()
                jax.block_until_ready(dense)
                stats[-1]["wait_s"] = (stats[-1].get("wait_s", 0.0)
                                       + time.perf_counter() - t0)
                walls.append((time.perf_counter() - t_wall) * 1e3)
                effs.append(se.overlap_efficiency(stats))
                last = dense
            if not np.array_equal(np.asarray(last), expect_v):
                raise AssertionError(
                    f"delta sweep parity failure at chunk={chunk_points} "
                    f"nbuf={nbuf}: scatter assembly != host dense build")
            p50 = float(np.percentile(walls, 50))
            mb = total * 12 / 1e6  # int32 rows + int32 pos + f32 vals
            print(f"delta   chunk={chunk_points:7d} nbuf={nbuf}  "
                  f"wall p50={p50:8.2f} ms  "
                  f"stream-BW={mb / p50:6.2f} GB/s  "
                  f"overlap-eff={float(np.median(effs)):.2f}  "
                  f"({len(host)} chunks)", flush=True)


def main():
    k = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    pipeline = int(sys.argv[3]) if len(sys.argv) > 3 else 25
    rounds = int(sys.argv[4]) if len(sys.argv) > 4 else 4

    compile_cache.enable(min_compile_secs=0.0)

    dev = jax.devices()[0]
    print(f"device: {dev} K={k} D={d} pipeline={pipeline}", flush=True)
    rng = np.random.default_rng(0)
    mean = jax.device_put(rng.gamma(2.0, 10.0, (k, d)).astype(np.float32))
    weight = jax.device_put(np.ones((k, d), np.float32))
    mm = np.stack([np.asarray(mean).min(1), np.asarray(mean).max(1)], 1)
    minmax = jax.device_put(mm.astype(np.float32))
    qs = jax.device_put(
        np.asarray([[0.5, 0.9, 0.99]], np.float32))

    def mode_bytes(mode: str) -> int:
        """HBM-facing operand bytes of each mode, per dtype — the
        eff-BW column must not assume two f32 operands (the depth and
        bf16 modes exist precisely because they move fewer bytes)."""
        if mode == "depth":
            return k * d * 4 + k * 4          # f32 values + i32 depths
        if mode == "depth_bf16":
            return k * d * 2 + k * 4          # bf16 values + i32 depths
        if mode in ("moments", "moments_sums"):
            return k * d * 4 + k * 2          # f32 values + i16 depths
        return 2 * k * d * 4                  # both [K, D] f32 operands

    modes = (sys.argv[5].split(",") if len(sys.argv) > 5
             else ["dma", "sort", "cumsum", "full", "full_nodma",
                   "full_dma", "depth", "depth_bf16", "xla"])
    if "delta" in modes:
        modes = [m for m in modes if m != "delta"]
        run_delta_sweep(k, d, rounds)
    for mode in modes:
        def fn(pct_jitter, _mode=mode):
            return run_variant(_mode, mean, weight, minmax,
                               qs + pct_jitter, se._lane_tile(k, d))
        jfn = jax.jit(fn)
        t0 = time.perf_counter()
        float(np.asarray(jfn(0.0)[0, 0]))
        compile_s = time.perf_counter() - t0
        # warmup with varied args
        for i in range(4):
            float(np.asarray(jfn(i * 1e-7)[0, 0]))
        per = []
        for r in range(rounds):
            t0 = time.perf_counter()
            outs = [jfn(i * 1e-7) for i in range(pipeline)]
            float(np.asarray(outs[-1][0, 0]))
            per.append((time.perf_counter() - t0) / pipeline * 1e3)
        p50 = float(np.percentile(per, 50))
        bw = mode_bytes(mode) / (p50 * 1e-3) / 1e9
        print(f"{mode:7s} p50={p50:8.3f} ms/flush  "
              f"eff-BW={bw:7.1f} GB/s  (compile {compile_s:.1f}s)",
              flush=True)


if __name__ == "__main__":
    main()
