"""Device-time decomposition of the flush kernel with ZERO launch noise.

Wraps each variant in an in-launch `lax.scan` of N iterations (percentiles
perturbed per step via the carry so nothing collapses by CSE), so one
launch carries N kernel executions and the per-launch dispatch cost
amortizes to ~zero.  Device time per kernel = launch wall / N, with a
handful of pipelined launches to wash out the fetch latency too.

Usage: python scripts/profile_kernel_inloop.py [K] [D] [inner] [pipeline]
       [modes]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, "/root/repo")

from veneur_tpu.ops import sorted_eval as se
from veneur_tpu.util import compile_cache
from scripts.profile_flush_kernel import _variant_kernel


def variant_fn(mode: str, mean, weight, minmax, qs, tile: int):
    """One kernel invocation, returns a scalar digest of the output."""
    u, d = mean.shape
    n_pct = qs.shape[1]
    if mode == "full":
        out = se.weighted_eval(mean, weight, minmax[:, 0], minmax[:, 1],
                               qs[0])
        return out[0, 0] + out[u // 2, 1]
    kern = _variant_kernel(mode, n_pct)
    out = pl.pallas_call(
        kern,
        grid=(u // tile,),
        in_specs=[
            pl.BlockSpec((d, tile), lambda i: (0, i)),
            pl.BlockSpec((d, tile), lambda i: (0, i)),
            pl.BlockSpec((2, tile), lambda i: (0, i)),
            pl.BlockSpec((1, n_pct), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_pct + 2, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_pct + 2, u), jnp.float32),
    )(mean.T, weight.T, minmax.T, qs)
    return out[0, 0] + out[1, u // 2]


def main():
    k = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    inner = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    pipeline = int(sys.argv[4]) if len(sys.argv) > 4 else 8
    modes = (sys.argv[5].split(",") if len(sys.argv) > 5
             else ["dma", "sort", "full"])

    compile_cache.enable(min_compile_secs=0.0)

    dev = jax.devices()[0]
    print(f"device: {dev} K={k} D={d} inner={inner} pipeline={pipeline}",
          flush=True)
    rng = np.random.default_rng(0)
    mean = jax.device_put(rng.gamma(2.0, 10.0, (k, d)).astype(np.float32))
    weight = jax.device_put(np.ones((k, d), np.float32))
    mm = np.stack([np.asarray(mean).min(1), np.asarray(mean).max(1)], 1)
    minmax = jax.device_put(mm.astype(np.float32))
    qs = jax.device_put(np.asarray([[0.5, 0.9, 0.99]], np.float32))
    bytes_read = 2 * k * d * 4
    tile = se._lane_tile(k, d)

    results = {}
    for mode in modes:
        def body(carry, _, _mode=mode):
            # carry perturbs the percentiles so every iteration is live
            s = variant_fn(_mode, mean, weight, minmax,
                           qs + carry * 1e-9, tile)
            return carry + s * 1e-20 + 1.0, ()

        def looped(c0, _mode=mode):
            c, _ = jax.lax.scan(body, c0, None, length=inner)
            return c

        jfn = jax.jit(looped)
        t0 = time.perf_counter()
        float(np.asarray(jfn(jnp.float32(0.0))))
        compile_s = time.perf_counter() - t0
        float(np.asarray(jfn(jnp.float32(1.0))))   # warm
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
        bw = bytes_read / (p50 * 1e-3) / 1e9
        results[mode] = p50
        print(f"{mode:7s} p50={p50:8.4f} ms/kernel  "
              f"eff-BW={bw:7.1f} GB/s  (compile {compile_s:.1f}s)",
              flush=True)
    if "dma" in results and "sort" in results:
        print(f"sort-only cost: {results['sort'] - results['dma']:.4f} ms",
              flush=True)
    if "full" in results and "sort" in results:
        print(f"eval-tail cost: {results['full'] - results['sort']:.4f} ms",
              flush=True)


if __name__ == "__main__":
    main()
