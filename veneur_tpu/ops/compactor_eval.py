"""Compactor-family flush: batched compaction kernel + state read-off.

The compute core of the relative-error compactor family
(sketches/compactor.py, core.arena.CompactorArena) — the third compute
class next to the bitonic quantile network (ops/sorted_eval.py) and
the moments merge/solve (ops/moments_eval.py):

  compact  ONE Pallas launch runs a full bottom-up compaction pass for
           every staged key at once: operands ``[U, levels, 2*cap]``
           level staging + occupancies + host-planned coin offsets,
           output the compacted ``[U, levels, cap]`` state.  Each
           level's buffer is sorted with the SAME compare-exchange
           network as the flush sort (`sorted_eval._sort_keys`, driven
           by the shared `_bitonic_stages` scheduler — keys on the
           128-wide lane axis, the 4*cap-deep buffer on sublanes), the
           survivor stride-select is a pure mask from occupancy + coin
           offset, and the scattered survivors compress to a sorted
           prefix by a masked re-sort.  Value movement only: the count
           dynamics (which levels compact, every coin) are planned on
           the host by `compactor.plan_pass` — the single integer-math
           source of truth host reference, XLA twin and kernel all
           follow, which is what makes the three bit-identical.
  eval     quantile read-off of compacted states: implied ``2**level``
           item weights built in-program from the occupancies, then
           the flush evaluation core (`tdigest.weighted_eval`) —
           states are `levels*cap` deep (past the sort network's
           MAX_DEPTH at production params), and compactor keys are the
           premium low-cardinality tier, so the batched XLA evaluation
           is the right roofline here; the Pallas win is the
           compaction pass above, where thousands of keys' sort +
           stride-select batch into one launch.

Kernel-vs-twin parity is test-enforced in interpret mode, and the
outputs are bitwise independent of the lane-tile choice by
construction: every op is lane-local (the sort network only crosses
SUBLANES), so re-tiling cannot reassociate anything.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from veneur_tpu.ops.sorted_eval import MAX_DEPTH, _PAD_KEY, _sort_keys
from veneur_tpu.sketches import compactor as cs
from veneur_tpu.sketches import tdigest as td

# re-exported: the host read-off lives with the sketch math (numpy
# only); this module is its serving-side twin surface, mirroring
# moments_eval.quantiles_from_vectors
quantiles_from_vectors = cs.quantiles_from_vectors


def _lane_tile(u: int) -> int:
    """Lane-axis tile width: the staging block ``[levels*2*cap, T]``
    dominates the VMEM working set (~14 KiB per lane at default
    params), so 128-lane tiles keep it under 2 MiB with headroom for
    the per-level sort buffers."""
    return min(128, u)


def usable(u: int, cap: int, levels: int, backend: str) -> bool:
    """Static predicate: can the Pallas pass compact this batch?  The
    per-level working buffer is ``4*cap`` deep — a legal bitonic depth
    whenever cap is a power of two <= 256 — and the key count must
    fill whole 128-lane tiles; smaller batches take the XLA twin."""
    t = _lane_tile(u)
    b = cs.BUF_MUL * cap
    return (backend == "tpu" and cap >= 8 and (cap & (cap - 1)) == 0
            and b <= MAX_DEPTH and levels >= 2
            and u >= 128 and u % t == 0 and t % 128 == 0)


def _pass_tile(stage, cnt, off, cap: int, levels: int, sortfn):
    """One bottom-up compaction pass over a ``[levels*2c, T]`` staging
    tile (+ ``[levels(+pad), T]`` occupancies, ``[levels+2(+pad), T]``
    coin offsets) -> ``[levels*cap, T]`` compacted state.  The mask
    algebra here IS compactor.apply_pass — shared verbatim between the
    Pallas kernel and the XLA twin via ``sortfn`` (the bitonic network
    in-kernel, a values-only jnp.sort in the twin; both sort the same
    value multiset, so the results are bit-identical)."""
    s2 = cs.STAGE_MUL * cap
    b = cs.BUF_MUL * cap
    keep = cs.keep_of(cap)
    t = stage.shape[1]
    idx = jax.lax.broadcasted_iota(jnp.int32, (b, t), 0)
    pad = jnp.asarray(_PAD_KEY, stage.dtype)
    carry = jnp.full((s2, t), pad)
    carry_n = jnp.zeros((1, t), jnp.int32)
    out_rows = []
    for lvl in range(levels):
        stage_l = stage[lvl * s2:(lvl + 1) * s2, :]
        buf = sortfn(jnp.concatenate([stage_l, carry], axis=0))
        occ = cnt[lvl:lvl + 1, :] + carry_n
        if lvl < levels - 1:
            # `do` gates only the [1, T] section size: m == 0 where no
            # compaction runs, which already empties `surv` and widens
            # `retain` to every occupied slot.  Broadcasting the bool
            # row itself over the buffer has no Mosaic lowering (i8 ->
            # i1 truncation).
            do = occ > cap
            sec = occ - keep
            m = jnp.where(do, sec - (sec & 1), 0)
            o = off[lvl:lvl + 1, :]
            surv = (idx < m) & ((idx & 1) == o)
            retain = (idx >= m) & (idx < occ)
            carry = sortfn(jnp.where(surv, buf, pad))[:s2, :]
            carry_n = m // 2
            out_rows.append(sortfn(jnp.where(retain, buf, pad))[:cap, :])
        else:
            top = occ
            for r in range(cs.CLIP_ROUNDS):
                do = top > cap
                m = jnp.where(do, top - (top & 1), 0)
                o = off[levels + r:levels + r + 1, :]
                surv = (idx < m) & ((idx & 1) == o)
                keepm = surv | ((idx >= m) & (idx < top))
                buf = sortfn(jnp.where(keepm, buf, pad))
                top = top - m // 2
            out_rows.append(buf[:cap, :])
    return jnp.concatenate(out_rows, axis=0)


def _kernel_compact(stage_ref, cnt_ref, off_ref, out_ref, *, cap: int,
                    levels: int):
    def sortfn(x):
        return _sort_keys(
            x, jax.lax.broadcasted_iota(jnp.int32, x.shape, 0))

    out_ref[...] = _pass_tile(stage_ref[...], cnt_ref[...], off_ref[...],
                              cap, levels, sortfn)


def _pad8(n: int) -> int:
    return (n + 7) & ~7


@functools.partial(jax.jit,
                   static_argnames=("cap", "levels", "interpret", "tile"))
def _compact_pallas(stage, cnt, off, cap: int, levels: int,
                    interpret: bool = False, tile: int | None = None):
    """stage [levels*2c, U] f32, cnt [pad8(levels), U] i32, off
    [pad8(levels+2), U] i32 -> [levels*cap, U] f32.  ONE launch; every
    op is lane-local, so the output is bitwise identical across tile
    choices (the tiling-invariance regression sweeps them)."""
    u = stage.shape[1]
    if tile is None:
        tile = _lane_tile(u)
    if u % tile:
        raise ValueError(
            f"compact_batch: key count {u} is not a whole number of "
            f"{tile}-lane tiles")
    cr, orr = cnt.shape[0], off.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel_compact, cap=cap, levels=levels),
        grid=(u // tile,),
        in_specs=[
            pl.BlockSpec((levels * cs.STAGE_MUL * cap, tile),
                         lambda i: (0, i)),
            pl.BlockSpec((cr, tile), lambda i: (0, i)),
            pl.BlockSpec((orr, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((levels * cap, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((levels * cap, u), jnp.float32),
        interpret=interpret,
    )(stage, cnt, off)


@functools.partial(jax.jit, static_argnames=("cap", "levels"))
def _compact_twin(stage, cnt, off, cap: int, levels: int):
    """XLA twin (CPU tier-1 + unusable shapes): the shared pass body
    with a values-only sort."""
    return _pass_tile(stage, cnt, off, cap, levels,
                      lambda x: jnp.sort(x, axis=0))


def compact_batch(stage_v, stage_n, off, interpret: bool = False,
                  tile: int | None = None) -> np.ndarray:
    """Batched compaction/merge pass: ``stage_v [U, levels, 2*cap]``
    level staging (+inf padding beyond ``stage_n [U, levels]``), coin
    offsets ``off [U, levels+CLIP_ROUNDS]`` from `compactor.plan_pass`
    -> compacted state ``[U, levels, cap]`` (f32).  Routes to the
    Pallas kernel when the backend and shape allow, else the XLA twin
    — parity is test-enforced.  Post-pass occupancies are the
    planner's ``cnt_out`` (value movement and count dynamics are
    deliberately split; see module docstring)."""
    stage_v = np.asarray(stage_v, np.float32)
    u, levels, s2 = stage_v.shape
    cap = s2 // cs.STAGE_MUL
    loff = levels + cs.CLIP_ROUNDS
    stage = jnp.asarray(stage_v.reshape(u, levels * s2).T)
    cnt = np.zeros((_pad8(levels), u), np.int32)
    cnt[:levels] = np.asarray(stage_n, np.int64).T
    offp = np.zeros((_pad8(loff), u), np.int32)
    offp[:loff] = np.asarray(off, np.int64).T
    if (not os.environ.get("VENEUR_TPU_DISABLE_PALLAS_EVAL")
            and not interpret and tile is None
            and usable(u, cap, levels, jax.default_backend())):
        out = _compact_pallas(stage, jnp.asarray(cnt), jnp.asarray(offp),
                              cap, levels)
    elif interpret or tile is not None:
        out = _compact_pallas(stage, jnp.asarray(cnt), jnp.asarray(offp),
                              cap, levels, interpret=interpret, tile=tile)
    else:
        out = _compact_twin(stage, jnp.asarray(cnt), jnp.asarray(offp),
                            cap, levels)
    return np.asarray(out, np.float32).T.reshape(u, levels, cap)


# ---------------------------------------------------------------------------
# Flush program (the serving entry; state-only evaluation)
# ---------------------------------------------------------------------------

def make_compactor_flush(cap: int = cs.DEFAULT_CAP,
                         levels: int = cs.DEFAULT_LEVELS):
    """Build the per-flush compactor read-off program:

    ``fn(cvals [U, levels*cap] f32, ccnt [U, levels] i32, cscale [U]
    f32, mm [2, U] f32, pct [P] f32) -> [U, P]`` quantile columns.
    Item weights are implied ``2**level * cscale`` built in-program
    from the occupancies (``cscale`` is the arena's exact-count
    renormalization, 1.0 outside the clip regime), and the read-off is
    the flush evaluation core (`tdigest.weighted_eval`) over the
    state's weighted points.  Totals/sums come exact from the host
    accumulators, so only the quantile columns cross back."""
    lw = 2.0 ** np.arange(levels, dtype=np.float32)

    def _run(cvals, ccnt, cscale, mm, pct):
        u = cvals.shape[0]
        live = (jnp.arange(cap, dtype=jnp.int32)[None, None, :]
                < ccnt[:, :, None])
        w = jnp.where(live, jnp.asarray(lw)[None, :, None], 0.0)
        w = (w * cscale[:, None, None]).reshape(u, levels * cap)
        # state padding is +inf; 0 * inf would poison the sums
        vals = jnp.where(w > 0, cvals, 0.0)
        out = td.weighted_eval(vals, w, mm[0], mm[1], pct)
        return out[:, :pct.shape[0]]

    fn = jax.jit(_run)

    def compactor_flush(cvals, ccnt, cscale, mm, pct):
        return fn(cvals, ccnt, cscale, mm, pct)

    compactor_flush.lower = fn.lower
    compactor_flush.cap = cap
    compactor_flush.levels = levels
    return compactor_flush
