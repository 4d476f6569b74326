"""Moments-family flush: dense merge kernel + batched maxent solver.

The compute core of the moments sketch family (sketches/moments.py,
core.arena.MomentsArena) — the second compute class next to the bitonic
sort network (ops/sorted_eval.py):

  merge   one Pallas kernel reduces the interval's staged dense
          ``[U, D]`` samples to per-row Chebyshev moment sums — an
          elementwise scale + recurrence + segmented sum along the
          depth axis, NO sort stages.  HBM-streamed like the v3 sort
          kernel: large shapes keep the operands HBM-resident
          (``memory_space=ANY``) and stream them through double-
          buffered VMEM scratch (the shared ``_dma_pipeline``), so HBM
          traffic is exactly one read of the staged matrix and one
          ``[2(k+1), U]`` write.  The XLA twin carries CPU/fallback
          shapes; parity is test-enforced in interpret mode.
  solve   a batched Newton solver on the maximum-entropy dual: find
          theta with density f(t) = exp(sum_j theta_j T_j(t)) on
          [-1, 1] matching the observed Chebyshev moments, via damped
          Newton on the convex potential
          Phi(theta) = integral exp(theta . T) - theta . m
          over fixed Gauss-Legendre quadrature; quantiles read off the
          resulting CDF and map back through the row's domain (raw or
          log — heavy-tailed rows solve in log space).

Both halves are shape-static and batched over the row axis, so one
program evaluates every touched moments key of a flush at once.

The same double-buffered overlap discipline is lifted one level to the
host↔HBM boundary by the delta flush (core/aggregator._dispatch_flush):
the staged ``[U, D]`` matrix this kernel consumes arrives either as
pipelined upload chunks or — under ``flush_resident_arenas`` — is
assembled ON device from interval-streamed COO deltas
(arena.MomentsArena.assemble_resident + serving.resident_scatter*), so
by flush time the merge kernel's input is already in HBM and only the
``[2(k+1), U]`` moment write and the solver's quantile columns cross
the link.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veneur_tpu.ops.sorted_eval import _auto_nbuf, _dma_pipeline
from veneur_tpu.sketches import moments as mo

# quadrature resolution of the maxent density (nodes cluster at the
# domain edges, where the tail quantiles live)
QUAD_POINTS = 48
# fixed damped-Newton iterations (convex objective; converges in ~10
# for well-posed rows, the rest are insurance for near-degenerate ones)
NEWTON_ITERS = 16
# Tikhonov floor on the Newton Hessian (f32 solve)
RIDGE = 1e-6


@functools.lru_cache(maxsize=None)
def _quad(n: int = QUAD_POINTS):
    """(nodes [n], weights [n]) Gauss-Legendre on [-1, 1], f64 host."""
    x, w = np.polynomial.legendre.leggauss(n)
    return x, w


@functools.lru_cache(maxsize=None)
def _cheb_basis(k: int, n: int = QUAD_POINTS) -> np.ndarray:
    """[n, k+1] Chebyshev T_j at the quadrature nodes, f64 host."""
    x, _ = _quad(n)
    b = np.zeros((n, k + 1))
    b[:, 0] = 1.0
    if k >= 1:
        b[:, 1] = x
    for j in range(2, k + 1):
        b[:, j] = 2.0 * x * b[:, j - 1] - b[:, j - 2]
    return b


def _lane_tile(u: int) -> int:
    """Lane-axis tile width for the merge kernel: the reduction's VMEM
    working set is ~2 live [T, D] blocks, so wide 1024-lane tiles fit
    at any supported depth; fall back so no 128-multiple shape loses
    the Pallas path."""
    if u >= 65536 and u % 1024 == 0:
        return 1024
    return min(512, u)


def usable(u: int, d: int, backend: str) -> bool:
    """Static predicate: can the Pallas merge kernel reduce this dense
    shape?  No sort network, so no pow2-depth constraint — only whole
    128-lane tiles; smaller flushes take the XLA twin, where the
    reduction is sub-millisecond anyway."""
    t = _lane_tile(u)
    return (backend == "tpu" and d >= 1
            and u >= 128 and u % t == 0 and t % 128 == 0)


# ---------------------------------------------------------------------------
# Merge: dense [U, D] staged samples -> [U, 2(k+1)] Chebyshev sums
# ---------------------------------------------------------------------------

def _tile_moments(v_block, occ_w, ab, lab, k: int):
    """Chebyshev moment sums of one ``[T, D]`` tile: scale each staged
    value into the row's [-1, 1] domain (raw and log), run the T_j
    recurrence, and reduce along depth.  -> ``[2(k+1), T]``: rows
    0..k raw-domain sums (row 0 = staged count), rows k+1..2k+1
    log-domain sums (row k+1 = staged positive mass)."""
    v = v_block.astype(jnp.float32)                       # [T, D]
    w = occ_w.astype(jnp.float32)
    a = ab[0:1, :].T                                      # [T, 1]
    b = ab[1:2, :].T
    span = jnp.maximum(b - a, 0.0)
    inv = jnp.where(span > 0, 1.0 / jnp.maximum(span, 1e-30), 0.0)
    t = jnp.clip((2.0 * v - (a + b)) * inv, -1.0, 1.0)
    # log domain: u over [la, lb]; occupied positive samples only
    la = lab[0:1, :].T
    lb = lab[1:2, :].T
    lspan = lb - la
    linv = jnp.where(lspan > 0, 1.0 / jnp.maximum(lspan, 1e-30), 0.0)
    pos = (v > 0) & (w > 0)
    lw = jnp.where(pos, w, 0.0)
    lv = jnp.log(jnp.where(pos, v, 1.0))
    u_ = jnp.clip((2.0 * lv - (la + lb)) * linv, -1.0, 1.0)

    rows = []
    tj_prev, tj = jnp.ones_like(t), t
    uj_prev, uj = jnp.ones_like(u_), u_
    rows.append(jnp.sum(w, axis=1, keepdims=True).T)      # count
    raw_rows, log_rows = [], []
    for j in range(1, k + 1):
        raw_rows.append(jnp.sum(w * tj, axis=1, keepdims=True).T)
        log_rows.append(jnp.sum(lw * uj, axis=1, keepdims=True).T)
        tj_prev, tj = tj, 2.0 * t * tj - tj_prev
        uj_prev, uj = uj, 2.0 * u_ * uj - uj_prev
    rows.extend(raw_rows)
    rows.append(jnp.sum(lw, axis=1, keepdims=True).T)     # logn
    rows.extend(log_rows)
    return jnp.concatenate(rows, axis=0)                  # [2(k+1), T]


def _kernel_moments(v_ref, w_ref, ab_ref, lab_ref, out_ref, *, k: int):
    out_ref[...] = _tile_moments(v_ref[...], w_ref[...], ab_ref[...],
                                 lab_ref[...], k)


def _kernel_moments_depth(v_ref, dep_ref, ab_ref, lab_ref, out_ref, *,
                          k: int):
    occ = (jax.lax.broadcasted_iota(jnp.int32, v_ref.shape, 1)
           < dep_ref[...].T)
    out_ref[...] = _tile_moments(v_ref[...], occ.astype(jnp.float32),
                                 ab_ref[...], lab_ref[...], k)


def _kernel_moments_dma(v_ref, w_ref, ab_ref, lab_ref, out_ref,
                        *scratch, tile: int, nbuf: int, k: int,
                        uniform: bool):
    sems = scratch[-1]
    scr = scratch[:-1]

    def compute(blocks, j):
        sl = pl.ds(j * tile, tile)
        if uniform:
            occ = (jax.lax.broadcasted_iota(
                jnp.int32, blocks[0].shape, 1)
                < w_ref[:, sl].T)
            out_ref[:, sl] = _tile_moments(
                blocks[0], occ.astype(jnp.float32), ab_ref[:, sl],
                lab_ref[:, sl], k)
        else:
            out_ref[:, sl] = _tile_moments(
                blocks[0], blocks[1], ab_ref[:, sl], lab_ref[:, sl], k)

    big = (v_ref,) if uniform else (v_ref, w_ref)
    _dma_pipeline(big, scr, sems, tile, nbuf, compute)


def _moments_sums_pallas(dv, dw, ab, lab, k: int, uniform: bool,
                         interpret: bool = False):
    u, d = dv.shape
    tile = _lane_tile(u)
    nbuf = _auto_nbuf(u, tile, d)
    out_rows = 2 * (k + 1)
    dv = dv.astype(jnp.float32)
    if uniform:
        dw = dw.reshape(1, u).astype(jnp.int32)
    else:
        dw = dw.astype(jnp.float32)
    if nbuf > 1:
        scratch = [pltpu.VMEM((2, tile, d), jnp.float32)]
        if not uniform:
            scratch.append(pltpu.VMEM((2, tile, d), jnp.float32))
        scratch.append(pltpu.SemaphoreType.DMA((len(scratch), 2)))
        out = pl.pallas_call(
            functools.partial(_kernel_moments_dma, tile=tile,
                              nbuf=nbuf, k=k, uniform=uniform),
            grid=(u // (tile * nbuf),),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                (pl.BlockSpec((1, tile * nbuf), lambda i: (0, i))
                 if uniform else pl.BlockSpec(memory_space=pl.ANY)),
                pl.BlockSpec((2, tile * nbuf), lambda i: (0, i)),
                pl.BlockSpec((2, tile * nbuf), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((out_rows, tile * nbuf),
                                   lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((out_rows, u), jnp.float32),
            scratch_shapes=scratch,
            interpret=interpret,
        )(dv, dw, ab, lab)
    else:
        kern = functools.partial(
            _kernel_moments_depth if uniform else _kernel_moments, k=k)
        out = pl.pallas_call(
            kern,
            grid=(u // tile,),
            in_specs=[
                pl.BlockSpec((tile, d), lambda i: (i, 0)),
                (pl.BlockSpec((1, tile), lambda i: (0, i)) if uniform
                 else pl.BlockSpec((tile, d), lambda i: (i, 0))),
                pl.BlockSpec((2, tile), lambda i: (0, i)),
                pl.BlockSpec((2, tile), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((out_rows, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((out_rows, u), jnp.float32),
            interpret=interpret,
        )(dv, dw, ab, lab)
    return out.T                                          # [U, 2(k+1)]


def _moments_sums_twin(dv, dw, ab, lab, k: int, uniform: bool):
    """XLA twin of the merge kernel (CPU tier-1 + unusable shapes):
    the same scale/recurrence/reduce math on the full [U, D] arrays."""
    v = dv.astype(jnp.float32)
    u, d = v.shape
    if uniform:
        occ = (jnp.arange(d, dtype=jnp.int32)[None, :]
               < dw.reshape(u)[:, None].astype(jnp.int32))
        w = occ.astype(jnp.float32)
    else:
        w = dw.astype(jnp.float32)
    return _tile_moments(v, w, ab, lab, k).T


def moments_sums(dv, dw, ab, lab, k: int, uniform: bool):
    """Dense staged samples -> per-row Chebyshev sums ``[U, 2(k+1)]``
    (raw block then log block; order-0 columns are count / positive
    mass).  Routes to the Pallas kernel when the backend and shape
    allow, else the XLA twin — parity is test-enforced."""
    import os
    u, d = dv.shape
    if (not os.environ.get("VENEUR_TPU_DISABLE_PALLAS_EVAL")
            and usable(u, d, jax.default_backend())):
        return _moments_sums_pallas(dv, dw, ab, lab, k, uniform)
    return _moments_sums_twin(dv, dw, ab, lab, k, uniform)


# ---------------------------------------------------------------------------
# Solve: Chebyshev moments -> quantiles (batched maxent Newton)
# ---------------------------------------------------------------------------

def _chol_solve(H, g):
    """Batched SPD solve ``H x = g`` (``H`` [U, n, n], ``g`` [U, n])
    via an unrolled Cholesky built from elementwise ops only.

    ``jnp.linalg.solve`` lowers to LAPACK batched LU on CPU, whose
    blocking — and therefore float accumulation order — depends on the
    BATCH size; rows 0:3 of a batch-24 solve and a batch-3 solve of the
    same systems differ in the last ulp.  That breaks meshed-vs-
    unmeshed bit-parity (each shard solves its own slice).  Elementwise
    chains are evaluated per-row regardless of batch, so this unrolled
    form (n is small and static: k+1 = 9) is bit-stable under any row
    partition.  H is SPD by construction (B' diag(p) B + ridge, p > 0),
    so Cholesky is exact here, not a compromise."""
    n = H.shape[-1]
    L = [[None] * n for _ in range(n)]
    inv = [None] * n
    for j in range(n):
        s = H[:, j, j]
        for t in range(j):
            s = s - L[j][t] * L[j][t]
        d = jnp.sqrt(jnp.maximum(s, 1e-30))
        L[j][j] = d
        inv[j] = 1.0 / d
        for i in range(j + 1, n):
            s = H[:, i, j]
            for t in range(j):
                s = s - L[i][t] * L[j][t]
            L[i][j] = s * inv[j]
    y = [None] * n
    for i in range(n):
        s = g[:, i]
        for t in range(i):
            s = s - L[i][t] * y[t]
        y[i] = s * inv[i]
    x = [None] * n
    for i in reversed(range(n)):
        s = y[i]
        for t in range(i + 1, n):
            s = s - L[t][i] * x[t]
        x[i] = s * inv[i]
    return jnp.stack(x, axis=1)


def _solve_domain(cheb, B, wq, xq, pct):
    """Batched maxent solve in ONE scaled domain.  ``cheb`` [U, k+1]
    are moment SUMS (cheb[:, 0] = mass); returns (t-quantiles [U, P],
    residual [U])."""
    count = cheb[:, 0]
    safe = jnp.maximum(count, 1e-30)
    m = cheb / safe[:, None]
    m = m.at[:, 0].set(1.0)
    m = jnp.clip(jnp.nan_to_num(m), -1.0, 1.0)
    kp1 = m.shape[1]
    u_rows = m.shape[0]

    theta0 = jnp.zeros((u_rows, kp1), jnp.float32)
    # B_j(x_n) B_l(x_n) flattened so the per-iteration Hessian is ONE
    # [U, N] x [N, (k+1)^2] matmul (MXU-shaped) instead of a
    # three-operand einsum XLA lowers poorly on every backend
    BB = (B[:, :, None] * B[:, None, :]).reshape(B.shape[0],
                                                 kp1 * kp1)

    def newton(i, theta):
        logits = jnp.clip(theta @ B.T, -30.0, 30.0)       # [U, N]
        p = jnp.exp(logits) * wq[None, :]
        mhat = p @ B                                      # [U, k+1]
        g = mhat - m
        # H = B' diag(p) B, PSD; ridge keeps near-degenerate rows
        # (tiny n, collinear moments) solvable
        H = (p @ BB).reshape(-1, kp1, kp1)
        H = H + (RIDGE * (1.0 + mhat[:, 0]))[:, None, None] \
            * jnp.eye(kp1, dtype=jnp.float32)[None]
        delta = _chol_solve(H, g)
        nrm = jnp.max(jnp.abs(delta), axis=1, keepdims=True)
        step = jnp.minimum(1.0, 2.0 / jnp.maximum(nrm, 1e-12))
        return theta - delta * step

    theta = jax.lax.fori_loop(0, NEWTON_ITERS, newton, theta0)
    logits = jnp.clip(theta @ B.T, -30.0, 30.0)
    p = jnp.exp(logits) * wq[None, :]
    resid = jnp.max(jnp.abs(p @ B - m), axis=1)
    # midpoint-corrected CDF at the nodes (cum - p/2, the digest
    # kernel's cmid convention): the plain cumsum lands between nodes
    # and biases every quantile by half a node's mass
    cum = jnp.cumsum(p, axis=1)
    total = jnp.maximum(cum[:, -1:], 1e-30)
    cdf = (cum - 0.5 * p) / total

    # quantile read-off: rank search + linear interp between nodes
    targets = pct[None, :, None]                          # [1, P, 1]
    below = (cdf[:, None, :] < targets).sum(axis=2)       # [U, P]
    hi = jnp.clip(below, 1, cdf.shape[1] - 1)
    lo = hi - 1
    c_lo = jnp.take_along_axis(cdf, lo, axis=1)
    c_hi = jnp.take_along_axis(cdf, hi, axis=1)
    x_lo = xq[lo]
    x_hi = xq[hi]
    frac = jnp.clip((pct[None, :] - c_lo)
                    / jnp.maximum(c_hi - c_lo, 1e-30), 0.0, 1.0)
    tq = x_lo + (x_hi - x_lo) * frac
    return tq, resid


def _maxent_quantiles(cheb_raw, cheb_log, ab, lab, pct, k: int):
    """Quantiles of every row from its Chebyshev moment sums: solve in
    the raw domain and (where valid) the log domain, pick per row, map
    back to data space, clamp to the authoritative [min, max]."""
    x, w = _quad()
    B = jnp.asarray(_cheb_basis(k), jnp.float32)
    wq = jnp.asarray(w, jnp.float32)
    xq = jnp.asarray(x, jnp.float32)
    pct = pct.astype(jnp.float32)

    a, b = ab[0], ab[1]
    la, lb = lab[0], lab[1]
    count = cheb_raw[:, 0]
    logn = cheb_log[:, 0]
    # heavy-tailed rows solve in log space: domain strictly positive
    # (the arena's lab sentinel lb < la encodes "invalid"), log mass
    # covering the full count, dynamic range past the ratio gate, AND
    # the mass actually crammed against the domain's left edge (scaled
    # mean near -1).  The ratio alone over-triggers: a moderate-spread
    # row whose min happens to be small solves better in the raw
    # domain (measured: gamma n=147, ratio 216 — log p99 error 11x
    # raw), while genuinely heavy tails (pareto, lognormal) sit at
    # scaled mean < -0.9 and gain 3-30x from the log solve.
    mean_t = cheb_raw[:, 1] / jnp.maximum(count, 1e-30)
    use_log = ((lb > la)
               & (logn >= count * (1.0 - 1e-6))
               & (b > a * mo.LOG_DOMAIN_RATIO)
               & (mean_t < -0.75))

    cheb = jnp.where(use_log[:, None], cheb_log, cheb_raw)
    tq, resid = _solve_domain(cheb, B, wq, xq, pct)

    lo = jnp.where(use_log, la, a)[:, None]
    hi = jnp.where(use_log, lb, b)[:, None]
    xq_dom = (tq + 1.0) * 0.5 * (hi - lo) + lo
    q = jnp.where(use_log[:, None], jnp.exp(xq_dom), xq_dom)
    # degenerate rows: no mass -> 0; single point / zero span -> min
    span = (b - a)[:, None]
    q = jnp.where(span > 0, q, a[:, None])
    q = jnp.clip(q, a[:, None], b[:, None])
    q = jnp.where(count[:, None] > 0, q, 0.0)
    q = jnp.nan_to_num(q)
    return q, jnp.where(count > 0, resid, 0.0)


# ---------------------------------------------------------------------------
# Flush program (the serving entry; mirrors serving.make_serving_flush's
# unmeshed shape so prewarm covers both variants)
# ---------------------------------------------------------------------------

def make_moments_flush(k: int = mo.DEFAULT_K, mesh=None):
    """Build the per-flush moments program:

    ``fn(dv [U,D] f32, dw [U,D] f32, ab [2,U] f32, lab [2,U] f32,
    imp [U, 2(k+1)] f32, pct [P] f32) -> [U, P+1]`` (quantile columns
    then the solver residual).  ``imp`` carries the host-converted
    Chebyshev contributions of imported/pre-reduced vectors (raw block
    then log block), added to the kernel's staged sums before the
    solve.  ``fn.depth_variant`` is the uniform (depth-vector) twin:
    ``(dv, depths [U] i16, ab, lab, imp, pct)`` — the weight matrix
    never crosses the link on raw-sample intervals.

    With a ``mesh``, the program shard_maps over the KEY axis across
    every mesh device (shard x replica — the merge and the damped-
    Newton solve are row-local, so there is not one collective in the
    body and the per-row arithmetic is the exact unmeshed sequence:
    meshed-vs-unmeshed bit-parity is test-pinned).  Rows pad up to a
    device multiple in-program and slice back off."""

    def _run(dv, dw, ab, lab, imp, pct, uniform):
        sums = moments_sums(dv, dw, ab, lab, k, uniform)
        sums = sums + imp.astype(jnp.float32)
        qs, resid = _maxent_quantiles(
            sums[:, :k + 1], sums[:, k + 1:], ab, lab, pct, k)
        return jnp.concatenate([qs, resid[:, None]], axis=1)

    if mesh is None:
        body = _run
    else:
        from veneur_tpu.parallel import mesh as mesh_mod
        from jax.sharding import PartitionSpec as P
        rows = (mesh_mod.SHARD_AXIS, mesh_mod.REPLICA_AXIS)
        ndev = (mesh.shape[mesh_mod.SHARD_AXIS]
                * mesh.shape[mesh_mod.REPLICA_AXIS])

        def body(dv, dw, ab, lab, imp, pct, uniform):
            u = dv.shape[0]
            up = mesh_mod.pad_to_multiple(max(u, ndev), ndev)
            if up != u:
                # all-zero padding rows solve to q 0 / resid 0 and are
                # sliced back off — same convention as the vector path
                dv = jnp.pad(dv, ((0, up - u), (0, 0)))
                dw = jnp.pad(
                    dw, ((0, up - u),) + ((0, 0),) * (dw.ndim - 1))
                ab = jnp.pad(ab, ((0, 0), (0, up - u)))
                lab = jnp.pad(lab, ((0, 0), (0, up - u)))
                imp = jnp.pad(imp, ((0, up - u), (0, 0)))
            f = mesh_mod.shard_map(
                functools.partial(_run, uniform=uniform),
                mesh=mesh,
                in_specs=(P(rows, None),
                          P(rows) if uniform else P(rows, None),
                          P(None, rows), P(None, rows),
                          P(rows, None), P(None)),
                out_specs=P(rows, None))
            return f(dv, dw, ab, lab, imp, pct)[:u]

    general = jax.jit(functools.partial(body, uniform=False))
    depth_variant = jax.jit(functools.partial(body, uniform=True))

    def moments_flush(dv, dw, ab, lab, imp, pct):
        return general(dv, dw, ab, lab, imp, pct)

    moments_flush.lower = general.lower
    moments_flush.depth_variant = depth_variant
    moments_flush.k = k
    moments_flush.mesh = mesh
    return moments_flush


# ---------------------------------------------------------------------------
# Vector-only convenience (analysis harness, MomentsSketch.quantile)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _vector_solver(k: int):
    """Jitted batched maxent solve for the vector-only path.  Eager
    dispatch of the damped-Newton loop costs hundreds of ms per call
    regardless of batch size — far too slow for the query plane, which
    solves per-group batches on every group-by request — so the solver
    compiles once per (rows, quantiles) shape and row counts are padded
    to powers of two by the caller to bound recompiles."""
    @jax.jit
    def run(cheb_raw, cheb_log, ab, lab, pct):
        return _maxent_quantiles(cheb_raw, cheb_log, ab, lab, pct, k)
    return run


def quantiles_from_vectors(vecs: np.ndarray, qs) -> np.ndarray:
    """Quantiles straight from batched moments VECTORS ``[n, M]`` (no
    dense staging): host f64 conversion to Chebyshev sums in each
    row's own domain, then the batched solver.  The path a vector-only
    row (pure-import global rows, group-by cube queries, the analysis
    twin) takes."""
    vecs = np.asarray(vecs, np.float64)
    n, m = vecs.shape
    k = mo.k_from_len(m)
    a = np.where(np.isfinite(vecs[:, mo.IDX_MIN]),
                 vecs[:, mo.IDX_MIN], 0.0)
    b = np.where(np.isfinite(vecs[:, mo.IDX_MAX]),
                 vecs[:, mo.IDX_MAX], 0.0)
    la, lb = mo.log_domain(a, b)
    cheb_raw, cheb_log = cheb_contrib(vecs, (a, b), (la, lb))
    # pad the row axis to the next power of two: the jitted solver
    # compiles per shape, and group-by queries arrive with arbitrary
    # group counts (padding rows are all-zero -> count 0 -> q 0,
    # sliced off below)
    npad = 1 << max(0, (n - 1).bit_length())
    if npad != n:
        pad = ((0, npad - n), (0, 0))
        cheb_raw = np.pad(cheb_raw, pad)
        cheb_log = np.pad(cheb_log, pad)
        a = np.pad(a, (0, npad - n))
        b = np.pad(b, (0, npad - n))
        la = np.pad(la, (0, npad - n))
        lb = np.pad(lb, (0, npad - n))
    pct = jnp.asarray(np.asarray(qs, np.float64), jnp.float32)
    qs_out, _ = _vector_solver(k)(
        jnp.asarray(cheb_raw, jnp.float32),
        jnp.asarray(cheb_log, jnp.float32),
        jnp.asarray(np.stack([a, b]), jnp.float32),
        jnp.asarray(np.stack([la, lb]), jnp.float32),
        pct)
    return np.asarray(qs_out, np.float64)[:n]


@functools.lru_cache(maxsize=None)
def _mono_to_cheb(k: int) -> np.ndarray:
    """[k+1, k+1] matrix C with T_j(t) = sum_m C[j, m] t^m (f64)."""
    c = np.zeros((k + 1, k + 1))
    c[0, 0] = 1.0
    if k >= 1:
        c[1, 1] = 1.0
    for j in range(2, k + 1):
        c[j, 1:] += 2.0 * c[j - 1, :-1]
        c[j] -= c[j - 2]
    return c


def cheb_contrib(vecs: np.ndarray, ab, lab):
    """Host f64 conversion of moments VECTORS to Chebyshev moment sums
    in a TARGET domain: rebase each row's scaled monomial sums from its
    own [min, max] (and log twin) to ``ab``/``lab``, then apply the
    monomial->Chebyshev matrix.  Returns (cheb_raw [n, k+1],
    cheb_log [n, k+1]) — the ``imp`` operand of the flush program."""
    vecs = np.asarray(vecs, np.float64)
    n, m = vecs.shape
    k = mo.k_from_len(m)
    own_a = vecs[:, mo.IDX_MIN]
    own_b = vecs[:, mo.IDX_MAX]
    raw = np.zeros((n, k + 1))
    raw[:, 0] = vecs[:, mo.IDX_COUNT]
    raw[:, 1:] = vecs[:, mo.SUMS_OFF:mo.SUMS_OFF + k]
    raw = mo.rebase_sums(raw, (own_a, own_b), ab)
    own_la, own_lb = mo.log_domain(
        np.where(np.isfinite(own_a), own_a, 0.0),
        np.where(np.isfinite(own_b), own_b, 0.0))
    log = np.zeros((n, k + 1))
    log[:, 0] = vecs[:, mo.IDX_LOGN]
    log[:, 1:] = vecs[:, mo.SUMS_OFF + k:mo.SUMS_OFF + 2 * k]
    log = mo.rebase_sums(log, (own_la, own_lb), lab)
    c = _mono_to_cheb(k).T
    return raw @ c, log @ c
