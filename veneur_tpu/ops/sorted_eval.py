"""Pallas TPU kernel: the fused flush evaluation (bitonic sort + quantiles).

Drop-in for `veneur_tpu.sketches.tdigest.weighted_eval` — THE serving
flush's compute core.  One kernel invocation per tile does everything the
flush needs while the tile stays VMEM-resident:

  * in-register bitonic sort of the (value, weight) pairs along the depth
    axis (compare-exchange stages built from `pltpu.roll` + selects;
    pair-consistent strict comparisons keep tied values' weights with
    their owners);
  * cumulative weights as a triangular ones matmul on the MXU for MXU-
    sized depths, or a log-step shift-add (Hillis-Steele) for shallow
    ones;
  * per-quantile rank search as compare+reduce, and the neighbor value
    gathers as one-hot reductions (Mosaic has no cheap dynamic lane
    gather);
  * midpoint interpolation, single-point/empty-row handling, min/max
    clamping — numerically matching the XLA twin (parity-tested in
    interpret mode and natively).

Layout (v2): tiles are TRANSPOSED — depth D on the sublane axis, keys on
the 128-wide lane axis.  The v1 layout put D on lanes, so the network's
rolls and selects ran at D/128 lane occupancy for shallow depths (a
production flush with D=4 staged points used 3% of the VPU); transposed,
every stage runs on full 128-lane vectors regardless of depth, and the
sort's rolls become sublane rotations (static vreg permutes for the
stride >= 8 stages).  The transpose happens IN VMEM per tile (the kernel
reads the natural [K, D] blocks and transposes in registers), so the
operands cross HBM exactly once.

v3 — the HBM-roofline rework (ROADMAP #2: 0.444 -> >=0.6 at the 100k
shape).  Three coordinated changes, all output-preserving:

  * **compact sort keys.**  bf16-staged tiles cross HBM at 16-bit
    width (half the HBM-facing read).  The key-only network widens
    them to f32 in VMEM before its first stage — Mosaic has no
    sublane rotate for 16-bit data on the v5e — which is exact:
    bf16 -> f32 widening is monotone and injective, so sorting before
    or after widening commutes (this is the narrow-key/value-
    reconstruct legality argument: the quantile tail is
    reconstruction-exact as long as the sort ORDER is preserved).
    The general weighted network
    additionally gets a packed formulation (`compact=True`): one int32
    word per point carrying the monotone-mapped 16-bit key in the high
    half and the depth index in the low half, sorted as a SINGLE array
    (6 passes/stage instead of the paired form's 11), with the f32
    weights reconstructed afterwards by permutation-apply from the
    index payload.  Ties order by original index — i.e. the packed
    network is STABLE, matching `lax.sort` exactly — and the value
    reconstruct is exact precisely when the staged values are
    bf16-representable, which is what the dispatch gate
    (`usable_compact` + the arena's bf16 staging) guarantees.  The
    permutation-apply costs O(D) selects per tile, so the packed form
    pays off only at shallow depths (`MAX_COMPACT_DEPTH`).
  * **generalized depth-vector scheduling.**  The 1024-wide lane tiles
    (previously only on the key-only depth-vector kernel) now apply to
    the paired (value, weight) network too, VMEM budget permitting
    (d <= 128), and every kernel shares one stage scheduler
    (`_bitonic_stages`) instead of three hand-unrolled loops.
  * **coarser grid + double-buffered block DMA.**  Large shapes take
    `nbuf` sub-tiles per grid step: the `[K, D]` operands stay in HBM
    (`memory_space=ANY`) and the kernel streams them through 2-slot
    VMEM scratch with `pltpu.make_async_copy`, overlapping the next
    sub-tile's HBM read against the current sub-tile's sort.  This
    amortizes the per-grid-step launch overhead the 1M shape measured
    at 2x (256 steps of 512 lanes ran ~2.5 ms where 128 steps of 1024
    ran ~1.25 ms) without growing the compute working set.  Output
    bytes are identical for every (tile, nbuf) choice — enforced by the
    tiling-invariance regression test.

HBM traffic is exactly one read of the `[K, D]` inputs (at their staged
dtype) and one `[K, P+2]` write; everything else lives in VMEM.  XLA's
stock `lax.sort` lowers to a far slower generic network with full HBM
round-trips per stage — this kernel is why the flush beats the 32-core
native baseline by a wide margin instead of a narrow one (cited path:
`worker.go:402-459` + `flusher.go:26-122`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veneur_tpu.ops import mxu

# padding sort key: +inf never collides with real values (the parser
# rejects non-finite samples; m_clean masks padding before any product,
# so no inf*0 NaN can arise).  A plain python float — jnp scalars would
# be captured constants, which pallas_call rejects.
_PAD_KEY = float("inf")

MAX_DEPTH = 1024

# compact (packed-word) general network: the permutation-apply that
# reconstructs the weights costs O(D) selects per tile, so the packed
# form only wins at shallow depths (the dispatch gate keeps deeper
# shapes on the f32 paired network)
MAX_COMPACT_DEPTH = 64

# double-buffered DMA pipeline: sub-tiles per coarse grid step, engaged
# once the classic grid would have at least _DMA_MIN_STEPS steps (the
# regime where per-grid-step overhead dominates; see _lane_tile)
_DMA_NBUF = 4
_DMA_MIN_STEPS = 16


def _lane_tile(u: int, d: int, wide: bool = False) -> int:
    """Lane-axis tile width: full-VPU 128 multiples, sized so the VMEM
    working set (~8 live [D, T] f32 arrays) stays well under the 16 MiB
    budget at every depth.

    1024-wide tiles engage at large key counts, where per-grid-step
    overhead dominates past ~128 steps (measured 2x on the 1M-digest
    shape: 256 steps of 512 lanes ran ~2.5 ms where 128 steps of 1024
    run ~1.25 ms): for the key-only depth-vector kernel (wide=True,
    roughly half the paired working set) at d <= 256, and — new in v3 —
    for the paired (value, weight) network too at d <= 128, where the
    doubled live set still fits.  Falls back to 512 when u is not a
    1024-multiple so no previously-usable shape loses the Pallas
    path."""
    if d <= 256:
        cap = 512
        if (wide or d <= 128) and u >= 65536 and u % 1024 == 0:
            cap = 1024
    else:
        cap = 256
    return min(cap, u)


def _auto_nbuf(u: int, tile: int, d: int) -> int:
    """Sub-tiles per coarse grid step for the DMA pipeline: the largest
    of (4, 2) that divides the classic step count once that count is
    >= _DMA_MIN_STEPS, else 1 (classic auto-pipelined path).

    Depths that do not fill whole 128-lane rows always take the classic
    path: the pipeline copies `[tile, d]` row slices of the HBM-resident
    operand, and Mosaic refuses a DMA whose minor dimension is not
    aligned to the (8, 128) tiling ("Slice shape along dimension 1 must
    be aligned to tiling (128)").  The classic BlockSpec path pads such
    blocks itself and compiles at every depth."""
    steps = u // tile
    if d % 128 == 0 and steps >= _DMA_MIN_STEPS:
        for nbuf in (_DMA_NBUF, 2):
            if steps % nbuf == 0:
                return nbuf
    return 1


# ---------------------------------------------------------------------------
# Stage scheduling (shared by every network formulation)
# ---------------------------------------------------------------------------

def _bitonic_stages(d: int) -> list[tuple[int, int]]:
    """The (j, k) compare-exchange schedule of the d-deep bitonic
    network, in execution order.  One place instead of three unrolled
    while-loops so every kernel (paired / key-only / packed-compact)
    provably runs the same stages."""
    out = []
    k = 2
    while k <= d:
        j = k // 2
        while j >= 1:
            out.append((j, k))
            j //= 2
        k *= 2
    return out


def _partner(x, j, lower):
    """The stage-j exchange partner (row ^ j) of every row: rolls by
    +-j selected by the side mask.  pltpu.roll requires non-negative
    shifts, so roll by d-j stands in for roll by -j."""
    d = x.shape[0]
    return jnp.where(lower, pltpu.roll(x, d - j, axis=0),
                     pltpu.roll(x, j, axis=0))


def _cmp_exchange(key, w, j, k, idx):
    """One bitonic compare-exchange stage over the sublane (depth) axis:
    partner = row ^ j, direction by bit k.

    min/max formulation (r5): the kept key is directly
    `min(key, partner)` on the keep-small side and `max` on the other —
    two fewer compares and two fewer logical ops per stage than the
    take-mask form, worth ~30% of the whole sort on chip.  The weight
    follows whenever the kept key CHANGED (`moved`); for tied keys
    min == max == key on both sides, so moved is false for both and each
    partner keeps its own weight — (key, weight) pairs never split."""
    lower = (idx & j) == 0
    pk = _partner(key, j, lower)
    pw = _partner(w, j, lower)
    up = (idx & k) == 0
    want_small = lower == up
    newkey = jnp.where(want_small, jnp.minimum(key, pk),
                       jnp.maximum(key, pk))
    moved = newkey != key
    return newkey, jnp.where(moved, pw, w)


def _cmp_exchange_keys(key, j, k, idx):
    """Key-only compare-exchange for the uniform-weight network: no
    weight array rides along (positions ARE the cumulative weights), so
    a stage is 2 rolls + min/max + 2 selects instead of the paired
    form's 11 passes.  Dtype-generic: runs on f32, native bf16 (half
    the vreg traffic per stage), and the packed int32 compact words."""
    lower = (idx & j) == 0
    pk = _partner(key, j, lower)
    up = (idx & k) == 0
    want_small = lower == up
    return jnp.where(want_small, jnp.minimum(key, pk),
                     jnp.maximum(key, pk))


def _sort_pairs(key, w, idx):
    """Full paired network: sort keys along the sublane axis, weights
    riding with their owners."""
    for j, k in _bitonic_stages(key.shape[0]):
        key, w = _cmp_exchange(key, w, j, k, idx)
    return key, w


def _sort_keys(key, idx):
    """Full key-only network (dtype-generic; see _cmp_exchange_keys)."""
    for j, k in _bitonic_stages(key.shape[0]):
        key = _cmp_exchange_keys(key, j, k, idx)
    return key


# ---------------------------------------------------------------------------
# Compact (packed-word) formulation
# ---------------------------------------------------------------------------

def _pack_compact(key_bf16, idx):
    """(bf16 key, depth index) -> ONE int32 word whose SIGNED order is
    the (value asc, index asc) lexicographic order.

    The bf16 bits map to an unsigned-monotone 16-bit integer with the
    classic IEEE trick (negatives flip all bits, positives set the sign
    bit); flipping the top bit before the shift re-centers the unsigned
    range so plain signed int32 min/max compares give the unsigned
    order.  The index payload in the low half makes every word unique,
    so the network is STABLE — tied values keep their original depth
    order, exactly like `lax.sort`."""
    b = jax.lax.bitcast_convert_type(key_bf16, jnp.uint16).astype(
        jnp.int32)
    neg = (b & 0x8000) != 0
    m16 = jnp.where(neg, 0xFFFF - b, b | 0x8000)
    return ((m16 ^ 0x8000) << 16) | idx


def _unpack_compact(word):
    """Inverse of _pack_compact: -> (bf16 key, int32 depth index)."""
    idx = word & 0xFFFF
    m16 = ((word >> 16) & 0xFFFF) ^ 0x8000
    pos = (m16 & 0x8000) != 0
    b = jnp.where(pos, m16 & 0x7FFF, 0xFFFF - m16)
    key = jax.lax.bitcast_convert_type(b.astype(jnp.uint16),
                                       jnp.bfloat16)
    return key, idx


def _apply_perm(x, perm):
    """Permutation-apply along the sublane axis: out[i] = x[perm[i]],
    per lane.  Mosaic has no dynamic sublane gather, so this is D
    broadcast-selects — the reconstruct cost that bounds
    MAX_COMPACT_DEPTH."""
    d = x.shape[0]
    out = jnp.zeros_like(x)
    for r in range(d):
        out = out + jnp.where(perm == r, x[r:r + 1, :], 0.0)
    return out


def _compact_sort_tile(m, w, idx):
    """Sort a [D, T] tile by (value, depth index) on packed int32 words
    and reconstruct the sorted f32 (value, weight) pairs.  Exact when
    the values are bf16-representable (the usable_compact dispatch
    gate); stable on ties, matching the XLA twin."""
    key_b = jnp.where(w > 0, m.astype(jnp.bfloat16),
                      jnp.asarray(_PAD_KEY, jnp.bfloat16))
    word = _sort_keys(_pack_compact(key_b, idx), idx)
    key_s, perm = _unpack_compact(word)
    return key_s.astype(jnp.float32), _apply_perm(w, perm)


# finite padding sentinel for cmid lanes (inf would turn the one-hot
# gathers' 0 * inf products into NaN)
_PAD_CMID = 3.0e38

# contraction pin (mxu.pin): applied to the two FMA/FMS-vulnerable
# products of the quantile tail, and IDENTICALLY by the XLA twin
# (td.weighted_eval) — which is what makes kernel-vs-twin parity
# bit-exact on inputs whose sums are exact (collision cost of the
# sentinel: one lane's quantile snapping to m_lo — still inside the
# data range)
_pin = mxu.pin


def _eval_tail(idx, m_clean, cmid, total, sums, n_real, mm, qs):
    """Shared quantile-extraction tail: per-percentile rank search on
    cmid + one-hot neighbor gathers + midpoint interpolation, matching
    `td.weighted_eval` (Hazen convention) bit-for-bit.  Returns the
    output rows (callers write them to their out block/slice).

    mm=None skips the min/max clamp (a provable no-op on uniform
    intervals, where interpolation stays between data values);
    sums=None emits the quantile rows alone (totals come from host
    accumulators on that path)."""
    n_pct = qs.shape[1]
    hi_bound = jnp.maximum(n_real - 1, 1)
    first_mean = m_clean[0:1, :]            # sorted: row 0 is the min
    if mm is not None:
        dmin, dmax = mm[0:1, :], mm[1:2, :]

    rows = []
    for p in range(n_pct):        # static: unrolled per quantile
        # pinned: `tq - c_lo` below would otherwise contract with this
        # product into an FMS that keeps q*total UNROUNDED (observed:
        # 0.1 * 5 - 0.5 = 7.45e-9 instead of 0), a per-program choice
        # that breaks tiling invariance and twin bit-parity
        tq = _pin(qs[0, p] * total)                             # [1, T]
        rank = jnp.sum((cmid < tq).astype(jnp.int32), axis=0,
                       keepdims=True)
        ii = jnp.clip(rank, 1, hi_bound)
        oh_hi = (idx == ii).astype(jnp.float32)
        oh_lo = (idx == ii - 1).astype(jnp.float32)
        m_hi = jnp.sum(oh_hi * m_clean, axis=0, keepdims=True)
        m_lo = jnp.sum(oh_lo * m_clean, axis=0, keepdims=True)
        c_hi = jnp.sum(oh_hi * cmid, axis=0, keepdims=True)
        c_lo = jnp.sum(oh_lo * cmid, axis=0, keepdims=True)
        tt = jnp.where(c_hi > c_lo,
                       (tq - c_lo) / jnp.maximum(c_hi - c_lo, 1e-30),
                       0.0)
        q = m_lo + _pin((m_hi - m_lo) * jnp.clip(tt, 0.0, 1.0))
        q = jnp.where(n_real <= 1, first_mean, q)
        if mm is not None:
            q = jnp.clip(q, dmin, dmax)
        q = jnp.where(total > 0, q, 0.0)
        rows.append(q)
    if sums is not None:
        rows = rows + [total, sums]
    return jnp.concatenate(rows, axis=0)


def _cumsum_depth(w):
    """Inclusive prefix sum along the sublane (depth) axis.  MXU-sized
    depths use the shared triangular ones matmul (mxu.tri_cumsum:
    HIGHEST precision keeps integer weights exact below 2^24, preserving
    the monotonicity rank searches depend on); shallow and extreme
    depths use log-step shift-adds, which are exact for the same
    reason."""
    d = w.shape[0]
    if 128 <= d <= 512:
        return mxu.tri_cumsum(w, axis=0)
    idx = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    cum = w
    s = 1
    while s < d:
        shifted = pltpu.roll(cum, s, axis=0)
        cum = cum + jnp.where(idx >= s, shifted, 0.0)
        s *= 2
    return cum


# ---------------------------------------------------------------------------
# Tile evaluators: [T, D] VMEM-resident blocks -> [rows, T] outputs.
# Shared verbatim by the classic (auto-pipelined) and DMA kernels, so
# the two launch shapes are tiling-invariant by construction.
# ---------------------------------------------------------------------------

def _tile_general(m_block, w_block, mm, qs, compact: bool):
    """The general weighted evaluation of one [T, D] tile: in-register
    transpose, paired sort (or the packed compact network), prefix sums,
    quantile tail.  -> [P+2, T].  compact=True accepts bf16 value blocks
    natively (the packing narrows f32 blocks in-register anyway, so both
    staging dtypes meet the same network)."""
    m = m_block.T                             # [D, T]
    w = w_block.T.astype(jnp.float32)
    d, t = m.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (d, t), 0)
    if compact:
        key, w = _compact_sort_tile(m, w, idx)
    else:
        m = m.astype(jnp.float32)
        key = jnp.where(w > 0, m, _PAD_KEY)
        key, w = _sort_pairs(key, w, idx)
    occ = w > 0
    m_clean = jnp.where(occ, key, 0.0)

    cum = _cumsum_depth(w)                                      # [D, T]
    total = cum[d - 1:d, :]                                     # [1, T]
    sums = jnp.sum(m_clean * w, axis=0, keepdims=True)          # [1, T]
    n_real = jnp.sum(occ.astype(jnp.int32), axis=0,
                     keepdims=True)                             # [1, T]
    cmid = cum - 0.5 * w
    return _eval_tail(idx, m_clean, cmid, total, sums, n_real, mm, qs)


def _tile_uniform(m_block, w_block, mm, qs):
    """Uniform-weight specialization of one [T, D] tile: every staged
    point weighs exactly 1 (raw-sample staging — the local tier always,
    and any global merge of under-compressed incoming digests, e.g. the
    32-samples-at-compression-100 digests of the reference's own
    benchmark, whose centroids are all singletons).  The weight array
    then never enters the sort network — sorted positions ARE the
    cumulative weights (cum_i = i+1, cmid_i = i+0.5, total = n_real) —
    so a stage is 6 passes instead of 11 and the prefix-sum disappears.
    bf16-staged tiles cross HBM at 16-bit width and widen to f32 in
    VMEM BEFORE the key network: Mosaic has no sublane rotate for
    16-bit data on the v5e ("Rotate with non-32-bit data"), and
    bf16 -> f32 is monotone and injective, so the sorted order and
    every output bit are those of a 16-bit sort.
    Numerically identical outputs to the general network on w in {0, 1}
    inputs (enforced in interpret mode by tests/test_ops.py; the
    compiled Mosaic path is exercised natively by the bench and the
    verify flow — CI runs on CPU and cannot lower Mosaic)."""
    m = m_block.T.astype(jnp.float32)                           # [D, T]
    w = w_block.T
    d, t = m.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (d, t), 0)
    occ0 = w > 0
    key = jnp.where(occ0, m, _PAD_KEY)
    n_real = jnp.sum(occ0.astype(jnp.int32), axis=0,
                     keepdims=True)                             # [1, T]
    key = _sort_keys(key, idx)
    occ_sorted = idx < n_real     # real points sort before +inf padding
    m_clean = jnp.where(occ_sorted, key, 0.0)
    # summed AFTER the sort, like the general kernel, so the two
    # networks agree bit-for-bit (f32 summation order matters)
    sums = jnp.sum(m_clean, axis=0, keepdims=True)
    total = n_real.astype(jnp.float32)
    cmid = jnp.where(occ_sorted, idx.astype(jnp.float32) + 0.5,
                     _PAD_CMID)
    return _eval_tail(idx, m_clean, cmid, total, sums, n_real, mm, qs)


def _tile_uniform_depth(m_block, dep, qs):
    """_tile_uniform fed by a PER-ROW DEPTH VECTOR instead of the
    [K, D] weight matrix: staged points pack contiguously from column 0
    (arena build_dense), so `col < depth[row]` IS the occupancy — the
    weight matrix never crosses HBM at all.

    Also drops the minmax operand and the total/sums output rows: on a
    uniform interval every staged point is a true sample, so the
    quantile interpolation between data points cannot leave the data
    range (the clip is a provable no-op), and the exact f64 totals
    live in host accumulators (`DigestArena.d_weight`/`d_sum`).  The
    flush's readback is therefore the quantile columns alone.  Like
    _tile_uniform, bf16 tiles widen to f32 in VMEM before the sort."""
    m = m_block.T.astype(jnp.float32)                           # [D, T]
    d, t = m.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (d, t), 0)
    occ0 = idx < dep
    key = jnp.where(occ0, m, _PAD_KEY)
    n_real = dep
    key = _sort_keys(key, idx)
    occ_sorted = idx < n_real     # real points sort before +inf padding
    m_clean = jnp.where(occ_sorted, key, 0.0)
    total = n_real.astype(jnp.float32)
    cmid = jnp.where(occ_sorted, idx.astype(jnp.float32) + 0.5,
                     _PAD_CMID)
    return _eval_tail(idx, m_clean, cmid, total, None, n_real, None, qs)


# ---------------------------------------------------------------------------
# Kernel wrappers: classic (auto-pipelined blocks) and DMA (coarse grid,
# HBM-resident operands streamed through double-buffered VMEM scratch)
# ---------------------------------------------------------------------------

def _kernel(mean_ref, weight_ref, minmax_ref, qs_ref, out_ref, *,
            compact: bool = False):
    out_ref[...] = _tile_general(mean_ref[...], weight_ref[...],
                                 minmax_ref[...], qs_ref[...], compact)


def _kernel_uniform(mean_ref, weight_ref, minmax_ref, qs_ref, out_ref):
    out_ref[...] = _tile_uniform(mean_ref[...], weight_ref[...],
                                 minmax_ref[...], qs_ref[...])


def _kernel_uniform_depth(mean_ref, depth_ref, qs_ref, out_ref):
    out_ref[...] = _tile_uniform_depth(mean_ref[...], depth_ref[...],
                                       qs_ref[...])


def _dma_pipeline(big_refs, scratch, sems, tile: int, nbuf: int,
                  compute):
    """The double-buffered block pipeline: sub-tile j+1's HBM->VMEM
    copies start before sub-tile j's sort runs, so the next block's read
    overlaps the current block's compute and each coarse grid step
    amortizes the per-step launch overhead over `nbuf` tiles.

    The sub-tile loop is a fori_loop, not a python unroll: the body
    traces ONCE, so every sub-tile runs the exact same compiled code and
    the outputs are bitwise independent of the (tile, nbuf) choice —
    unrolled instances were observed to pick per-instance fusion
    (last-ulp interpolation drift between sub-tiles of one launch),
    which the tiling-invariance regression forbids."""
    i = pl.program_id(0)
    n_big = len(big_refs)

    def dma(b, j, slot):
        return pltpu.make_async_copy(
            big_refs[b].at[pl.ds((i * nbuf + j) * tile, tile), :],
            scratch[b].at[slot], sems.at[b, slot])

    for b in range(n_big):
        dma(b, 0, 0).start()

    def body(j, _):
        slot = j % 2

        @pl.when(j + 1 < nbuf)
        def _():
            for b in range(n_big):
                dma(b, j + 1, (j + 1) % 2).start()

        for b in range(n_big):
            dma(b, j, slot).wait()
        compute([scratch[b][slot] for b in range(n_big)], j)
        return 0

    jax.lax.fori_loop(0, nbuf, body, 0)


def _kernel_dma(mean_ref, weight_ref, minmax_ref, qs_ref, out_ref,
                m_scr, w_scr, sems, *, tile: int, nbuf: int,
                uniform: bool, compact: bool):
    qs = qs_ref[...]

    def compute(blocks, j):
        sl = pl.ds(j * tile, tile)
        mm = minmax_ref[:, sl]
        if uniform:
            out_ref[:, sl] = _tile_uniform(blocks[0], blocks[1], mm, qs)
        else:
            out_ref[:, sl] = _tile_general(blocks[0], blocks[1], mm, qs,
                                           compact)

    _dma_pipeline((mean_ref, weight_ref), (m_scr, w_scr), sems,
                  tile, nbuf, compute)


def _kernel_uniform_depth_dma(mean_ref, depth_ref, qs_ref, out_ref,
                              m_scr, sems, *, tile: int, nbuf: int):
    qs = qs_ref[...]

    def compute(blocks, j):
        sl = pl.ds(j * tile, tile)
        out_ref[:, sl] = _tile_uniform_depth(blocks[0],
                                             depth_ref[:, sl], qs)

    _dma_pipeline((mean_ref,), (m_scr,), sems, tile, nbuf, compute)


@functools.partial(jax.jit, static_argnames=("interpret", "tile",
                                             "nbuf"))
def uniform_eval(mean: jax.Array, depths: jax.Array,
                 percentiles: jax.Array,
                 interpret: bool = False,
                 tile: int | None = None,
                 nbuf: int | None = None) -> jax.Array:
    """Depth-vector flush evaluation: `[K, D]` values whose first
    depths[k] columns are real weight-1 points -> `[K, P]` quantiles.
    Matches weighted_eval(mean, w, ..., uniform=True)'s quantile
    columns for w = (col < depths[row]), at half the HBM traffic and a
    P-column readback (totals/sums come from the host accumulators).

    bf16 inputs cross HBM as bf16 and widen to f32 in VMEM before the
    sort — bit-identical to a 16-bit sort, since bf16 -> f32 is
    monotone.  `tile`/`nbuf`
    override the lane-tile width and DMA sub-tile count (tests sweep
    them; production uses the defaults)."""
    u, d = mean.shape
    n_pct = percentiles.shape[0]
    if tile is None:
        tile = _lane_tile(u, d, wide=True)
    if nbuf is None:
        nbuf = _auto_nbuf(u, tile, d)
    if u % (tile * nbuf):
        raise ValueError(
            f"uniform_eval: key count {u} is not a whole number of "
            f"tile*nbuf={tile}*{nbuf} blocks — the floored grid would "
            f"silently leave trailing rows unwritten")
    qs = percentiles.reshape(1, n_pct).astype(jnp.float32)
    if mean.dtype not in (jnp.bfloat16,):
        mean = mean.astype(jnp.float32)
    depths = depths.reshape(1, u).astype(jnp.int32)
    if nbuf > 1:
        out = pl.pallas_call(
            functools.partial(_kernel_uniform_depth_dma, tile=tile,
                              nbuf=nbuf),
            grid=(u // (tile * nbuf),),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, tile * nbuf), lambda i: (0, i)),
                pl.BlockSpec((1, n_pct), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((n_pct, tile * nbuf),
                                   lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((n_pct, u), jnp.float32),
            scratch_shapes=[pltpu.VMEM((2, tile, d), mean.dtype),
                            pltpu.SemaphoreType.DMA((1, 2))],
            interpret=interpret,
        )(mean, depths, qs)
    else:
        out = pl.pallas_call(
            _kernel_uniform_depth,
            grid=(u // tile,),
            in_specs=[
                pl.BlockSpec((tile, d), lambda i: (i, 0)),
                pl.BlockSpec((1, tile), lambda i: (0, i)),
                pl.BlockSpec((1, n_pct), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((n_pct, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((n_pct, u), jnp.float32),
            interpret=interpret,
        )(mean, depths, qs)
    return out.T                                                # [U, P]


@functools.partial(jax.jit, static_argnames=("interpret", "uniform",
                                             "compact", "tile", "nbuf"))
def weighted_eval(mean: jax.Array, weight: jax.Array,
                  d_min: jax.Array, d_max: jax.Array,
                  percentiles: jax.Array,
                  interpret: bool = False,
                  uniform: bool = False,
                  compact: bool = False,
                  tile: int | None = None,
                  nbuf: int | None = None) -> jax.Array:
    """Pallas twin of `td.weighted_eval`: `[K, D]` weighted points ->
    `[K, P+2]` (quantiles, total weight, weighted sum).  Shapes must
    satisfy `usable()`; the dense builder's pow2 padding guarantees it
    for every at-scale flush.

    `uniform=True` selects the key-only network (~1.8x faster) and is
    only legal when every nonzero weight equals 1.0 — the dense builder
    tracks that per interval (`DigestArena.staged_uniform`) and the
    serving path threads it through as a static program choice.
    `compact=True` selects the packed-word general network (stable
    16-bit keys + index payload, weights reconstructed by
    permutation-apply) and is only legal when every staged value is
    bf16-representable (`usable_compact` + the arena's bf16 staging
    gate).  `tile`/`nbuf` override the lane-tile width and the DMA
    sub-tile count (tests sweep them for the tiling-invariance
    regression; production uses the defaults)."""
    u, d = mean.shape
    n_pct = percentiles.shape[0]
    if tile is None:
        tile = _lane_tile(u, d)
    if nbuf is None:
        nbuf = _auto_nbuf(u, tile, d)
    if u % (tile * nbuf):
        raise ValueError(
            f"weighted_eval: key count {u} is not a whole number of "
            f"tile*nbuf={tile}*{nbuf} blocks — the floored grid would "
            f"silently leave trailing rows unwritten")
    minmax = jnp.stack([d_min, d_max], axis=0).astype(jnp.float32)
    qs = percentiles.reshape(1, n_pct).astype(jnp.float32)
    # bf16-staged values cross HBM at their wire width for EVERY
    # network: the compact tile packs 16-bit keys and the others
    # widen in VMEM (_tile_general, _tile_uniform) — an XLA-side
    # astype would materialize an f32
    # copy in HBM, tripling the value-matrix traffic
    if mean.dtype != jnp.bfloat16:
        mean = mean.astype(jnp.float32)
    weight = weight.astype(jnp.float32)
    if nbuf > 1:
        out = pl.pallas_call(
            functools.partial(_kernel_dma, tile=tile, nbuf=nbuf,
                              uniform=uniform, compact=compact),
            grid=(u // (tile * nbuf),),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((2, tile * nbuf), lambda i: (0, i)),
                pl.BlockSpec((1, n_pct), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((n_pct + 2, tile * nbuf),
                                   lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((n_pct + 2, u), jnp.float32),
            scratch_shapes=[pltpu.VMEM((2, tile, d), mean.dtype),
                            pltpu.VMEM((2, tile, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, 2))],
            interpret=interpret,
        )(mean, weight, minmax, qs)
    else:
        if uniform:
            kern = _kernel_uniform
        else:
            kern = functools.partial(_kernel, compact=compact)
        out = pl.pallas_call(
            kern,
            grid=(u // tile,),
            in_specs=[
                pl.BlockSpec((tile, d), lambda i: (i, 0)),
                pl.BlockSpec((tile, d), lambda i: (i, 0)),
                pl.BlockSpec((2, tile), lambda i: (0, i)),
                pl.BlockSpec((1, n_pct), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((n_pct + 2, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((n_pct + 2, u), jnp.float32),
            interpret=interpret,
        )(mean, weight, minmax, qs)
    return out.T                                                # [U, P+2]


def usable(u: int, d: int, backend: str) -> bool:
    """Static predicate: can the Pallas path evaluate this dense shape?
    Depth must be a power of two (bitonic network) up to MAX_DEPTH; the
    key count must fill whole 128-lane tiles (`_lane_tile`) — smaller
    flushes take the XLA twin, where sub-millisecond either way."""
    t = _lane_tile(u, d)
    return (backend == "tpu" and 2 <= d <= MAX_DEPTH
            and (d & (d - 1)) == 0
            and u >= 128 and u % t == 0 and t % 128 == 0)


def usable_compact(u: int, d: int, backend: str) -> bool:
    """Static predicate for the packed compact-key general network: a
    usable() shape shallow enough that the O(D) permutation-apply
    reconstruct stays cheaper than the paired network's extra passes.
    The VALUE-exactness
    half of the gate — every staged value bf16-representable — is the
    caller's (the arena's bf16 staging guarantees it by
    construction)."""
    return usable(u, d, backend) and d <= MAX_COMPACT_DEPTH
