"""The serving-path SPMD programs: the per-flush family evaluation.

This wires the sharded flush into the *production* aggregation tier.  The
digest pipeline is **stateless on device**: every interval's samples (and
imported digest centroids, which are just weighted points) stage host-side
in `DigestArena`, and one program per flush evaluates the whole tier —

  - the **shard** mesh axis partitions the touched-key space (the device
    analog of the reference's fnv1a-hash worker sharding,
    `server.go:997-1011` / `worker.go:34-50`, and of the proxy's
    consistent-hash ring);
  - the **replica** axis partitions the sample depth `D`: each replica
    group holds a slice of every key's staged points, and the flush
    all_gathers the slices over ICI before one batched sorted evaluation —
    the collective form of the gRPC ImportMetric merge loop
    (`worker.go:402-459`).

Per-flush device traffic is minimal by design: upload = the interval's
staged points (`[K, D]`, proportional to samples), download = one
`[K, P+2]` evaluation matrix.  No persistent centroid state is rewritten
per flush — t-digest *compression* runs only where the sketch must stay
bounded: forwarding export (`digest_export`) and hot-key
pre-reduction (`partial_digests`), both of which return slim arrays.

Sets (HLL registers) and counters keep device-resident lane state only
when a mesh is configured (the registers then pmax over 'replica' and the
counter hi/lo planes psum); without a mesh both families keep their state on
host (see core/arena.py), the program evaluates digests only, and a flush
uploads a copy of the touched set rows' registers for `sketches/hll.estimate`
(core/aggregator.py `_dispatch_sets`).

Counters ride as two float32 planes (hi, lo) with value = hi * 2^24 + lo:
each plane is integer-exact below 2^24, so the psum'd total is exact below
2^48 without relying on x64 mode — int64 counter semantics
(`samplers/samplers.go:97-150`) on an f32-native device.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS
from veneur_tpu.sketches import hll as hll_mod
from veneur_tpu.sketches import tdigest as td

# counters travel as (hi, lo) f32 planes: value = hi * COUNTER_SPLIT + lo,
# each plane integer-exact below 2^24 => totals exact below 2^48
COUNTER_SPLIT = float(1 << 24)


class FlushInputs(NamedTuple):
    """Device inputs to one full flush (shapes: K touched digest keys
    padded pow2, D staged depth padded pow2, R replica lanes, S set rows,
    m HLL registers, K2 counter rows)."""
    dense_v: jax.Array        # [K, D] f32 staged values / centroid means
    dense_w: jax.Array        # [K, D] f32 weights (0 = empty cell)
    minmax: jax.Array         # [2, K] f32 authoritative min;max
    hll_regs: jax.Array       # [R, S, m] u8 set register lanes
    counter_planes: jax.Array  # [R, K2, 2] f32 (hi, lo)
    uts_regs: jax.Array       # [R, m_u] u8 unique-timeseries registers


class FlushOutputs(NamedTuple):
    digest_eval: jax.Array    # [K, P+2]: P quantiles, total weight, sum
    counter_hi: jax.Array     # [K2]
    counter_lo: jax.Array     # [K2]
    set_regs: jax.Array       # [S, m] u8 merged registers (forwarding)
    set_estimates: jax.Array  # [S] f32
    unique_ts: jax.Array      # [] f32


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------

def mesh_device_count(mesh: Optional[Mesh]) -> int:
    """Devices a flush program runs over: 1 unmeshed, else the full
    (shard x replica) grid.  The flush-timeline records carry this so a
    live server's segment decomposition is comparable across mesh
    reconfigurations (the bench's mesh-scaling curve, observable in
    production)."""
    return 1 if mesh is None else int(mesh.size)


def lane_sharding(mesh: Optional[Mesh]):
    """[R, K, ...] lane-striped state: lanes over 'replica', keys over
    'shard'."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P(REPLICA_AXIS, SHARD_AXIS, None))


def dense_sharding(mesh: Optional[Mesh]):
    """[K, D] staged sample matrices: keys over 'shard', depth over
    'replica' (the replica groups each evaluate a sample slice)."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P(SHARD_AXIS, REPLICA_AXIS))


def minmax_sharding(mesh: Optional[Mesh]):
    if mesh is None:
        return None
    return NamedSharding(mesh, P(None, SHARD_AXIS))


def put(x, sharding):
    """Host array -> (sharded) device array.

    Multi-controller runs (jax.distributed, multihost.py) construct the
    global array from each process's view via make_array_from_callback:
    every process supplies the slices its devices own, so per-process
    staging lands on the shards that process is responsible for — the
    key-ownership model of the proxy ring (`destinations.go:129-142`)
    carried onto the device mesh."""
    if sharding is None:
        return jnp.asarray(x)
    if jax.process_count() > 1:
        import numpy as _np
        arr = _np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])
    return jax.device_put(x, sharding)


def place_dense_blocks(mesh: Mesh, dv, dw, minmax,
                       dense_shd: NamedSharding,
                       mm_shd: NamedSharding):
    """Pre-sharded staging of the dense build: every device's blocks —
    its row block of each shard (the dense builder's row order IS shard
    order) and its depth slice — are placed DIRECTLY on their owning
    device with one batched jax.device_put, then assembled with
    make_array_from_single_device_arrays.  The mesh program consumes
    already-resident shards instead of re-laying-out one process-wide
    host matrix on entry, and the per-device transfers overlap on real
    hardware.  minmax is key-sharded, replica-replicated: every replica
    gets its shard's columns."""
    from jax.sharding import SingleDeviceSharding
    S = int(mesh.shape[SHARD_AXIS])
    R = int(mesh.shape[REPLICA_AXIS])
    ps, dr = dv.shape[0] // S, dv.shape[1] // R
    devs = mesh.devices  # [S, R] device grid
    blocks: list = []
    tgts: list = []
    for s in range(S):
        for r in range(R):
            dev = SingleDeviceSharding(devs[s][r])
            blocks.append(dv[s * ps:(s + 1) * ps, r * dr:(r + 1) * dr])
            tgts.append(dev)
            blocks.append(dw[s * ps:(s + 1) * ps, r * dr:(r + 1) * dr])
            tgts.append(dev)
            blocks.append(minmax[:, s * ps:(s + 1) * ps])
            tgts.append(dev)
    arrs = jax.device_put(blocks, tgts)
    asm = jax.make_array_from_single_device_arrays
    return (asm(dv.shape, dense_shd, arrs[0::3]),
            asm(dw.shape, dense_shd, arrs[1::3]),
            asm(minmax.shape, mm_shd, arrs[2::3]))


def fetch(x):
    """Device array (or pytree of arrays) -> host numpy.  Multi-controller:
    ONE process_allgather over DCN for the whole tree (callers batch every
    readback of a flush into a single fetch so each flush pays one
    cross-process barrier, not one per family)."""
    import numpy as _np
    if jax.process_count() > 1:
        leaves = jax.tree_util.tree_leaves(x)
        if leaves and not all(l.is_fully_addressable for l in leaves):
            from jax.experimental import multihost_utils
            return multihost_utils.process_allgather(x, tiled=True)
    return jax.tree_util.tree_map(_np.asarray, x)


# ---------------------------------------------------------------------------
# Flush body (shared by the serving path and parallel/flush_step.py)
# ---------------------------------------------------------------------------

def pallas_eval_applies(u: int, d: int, dtype=jnp.float32) -> bool:
    """True when digest_eval will route this shape to the fused Pallas
    kernel (where the uniform/general network choice is a DISTINCT
    program).  Callers normalize their `uniform` flag with this so the
    XLA-twin fallback never compiles two identical programs under two
    static keys.  bf16 staging takes the Pallas path too: the compact
    packed-key network at shallow depths, the f32 paired network on
    in-kernel-widened values otherwise."""
    import os

    from veneur_tpu.ops import sorted_eval as se
    return (not os.environ.get("VENEUR_TPU_DISABLE_PALLAS_EVAL")
            and dtype in (jnp.float32, jnp.bfloat16)
            and se.usable(u, d, jax.default_backend()))


def digest_eval(dv: jax.Array, dw: jax.Array, d_min: jax.Array,
                d_max: jax.Array, percentiles: jax.Array,
                uniform: bool = False) -> jax.Array:
    """The flush's evaluation core, routed to the fused Pallas kernel
    (ops/sorted_eval.py: in-VMEM bitonic sort + MXU prefix sums) when the
    backend and static shapes allow, else the XLA formulation — bitwise
    parity between the two is test-enforced.  `uniform` (static) selects
    the key-only sort network, legal when every nonzero staged weight is
    exactly 1 (tracked per interval by the dense builder).

    bf16-staged dense values (the arena's compact_general staging) keep
    their wire width into the kernel where the compact packed-key
    network applies (usable_compact: the value-exactness half of the
    gate is the bf16 dtype itself — every staged value IS
    bf16-representable by construction); deeper bf16 shapes widen
    in-kernel and run the f32 paired network.

    VENEUR_TPU_DISABLE_PALLAS_EVAL is read at TRACE time (the choice is
    baked into each compiled program): set it before process start."""
    import os

    from veneur_tpu.ops import sorted_eval as se
    u, d = dv.shape
    backend = jax.default_backend()
    if (not os.environ.get("VENEUR_TPU_DISABLE_PALLAS_EVAL")
            and dv.dtype in (jnp.float32, jnp.bfloat16)  # f64 -> twin
            and se.usable(u, d, backend)):
        if uniform:
            # the key-only network beats the compact one (~1.8x: no
            # payload, no prefix-sum) and reads bf16 values at wire width —
            # checked FIRST so bf16 uniform intervals never pay the
            # packed network's permutation-apply
            return se.weighted_eval(dv, dw, d_min, d_max, percentiles,
                                    uniform=True)
        if (dv.dtype == jnp.bfloat16
                and se.usable_compact(u, d, backend)):
            return se.weighted_eval(dv, dw, d_min, d_max, percentiles,
                                    compact=True)
        # bf16 stays bf16 into the kernel here too: the paired network
        # widens in-register, so no f32 copy ever lands in HBM
        return se.weighted_eval(dv, dw, d_min, d_max, percentiles)
    return td.weighted_eval(dv, dw, d_min, d_max, percentiles)


def digest_eval_uniform(dv: jax.Array, depths: jax.Array,
                        percentiles: jax.Array) -> jax.Array:
    """Depth-vector evaluation for uniform (all-weight-1) intervals ->
    `[U, P]` quantiles only: the weight matrix never uploads (occupancy
    is `col < depths[row]`), no minmax operand (each staged point is a
    true sample, so interpolation cannot leave the data range), and the
    totals come from host accumulators instead of the readback.  Routes
    to the fused Pallas depth kernel when shapes allow, else
    reconstructs the 0/1 weights and the row ranges ON DEVICE (free
    next to uploading them) and runs the XLA twin."""
    import os

    from veneur_tpu.ops import sorted_eval as se
    u, d = dv.shape
    n_pct = percentiles.shape[0]
    if (not os.environ.get("VENEUR_TPU_DISABLE_PALLAS_EVAL")
            and dv.dtype in (jnp.float32, jnp.bfloat16)
            and se.usable(u, d, jax.default_backend())):
        return se.uniform_eval(dv, depths, percentiles)
    # XLA-twin fallback: widen narrow staging, keep f64 as f64
    dt = jnp.float64 if dv.dtype == jnp.float64 else jnp.float32
    dv = dv.astype(dt)
    dw = (jnp.arange(d, dtype=jnp.int32)[None, :]
          < depths[:, None].astype(jnp.int32)).astype(dt)
    occ = dw > 0
    d_min = jnp.where(depths > 0,
                      jnp.where(occ, dv, jnp.inf).min(axis=1), 0.0)
    d_max = jnp.where(depths > 0,
                      jnp.where(occ, dv, -jnp.inf).max(axis=1), 0.0)
    return td.weighted_eval(dv, dw, d_min.astype(dt),
                            d_max.astype(dt),
                            percentiles)[:, :n_pct]


def flush_body(inputs: FlushInputs, percentiles: jax.Array,
               axis: Optional[str],
               uniform: bool = False,
               shard_axis: Optional[str] = None) -> FlushOutputs:
    """Evaluate every family for one flush.

    `axis` names the replica mesh axis for cross-replica collectives;
    None means the replica axis has size 1 (or no mesh at all) and the
    math is identical with every collective elided at TRACE time — the
    axis-size-1 specialization that keeps the mesh=1 wrapper overhead at
    dispatch cost only.  `shard_axis` names the shard axis when meshed
    (the unique-timeseries union must span it even when R == 1).

    The digest repartition is an **all_to_all**, not an all_gather: each
    replica group re-splits its key rows over the replicas while
    concatenating the depth slices, so every device evaluates
    K_s/R keys at FULL depth.  The old all_gather form materialized all
    K_s keys at full depth on EVERY replica — R× the eval work and R×
    the collective bytes for identical output (t-digest mergeability,
    arxiv 1902.04023, is what makes any per-shard split legal; the
    quantile evaluation itself is row-local either way)."""
    dv, dw = inputs.dense_v, inputs.dense_w
    if axis is not None and dv.dtype != dw.dtype:
        # the stacked all_to_all needs one dtype; bf16 staging is an
        # unmeshed option (arena.compact_general), so this only guards
        # hand-built inputs
        dv = dv.astype(dw.dtype)
    if axis is not None:
        # repartition [K_s, D/R] -> [K_s/R, D]: split keys, concat depth.
        # BOTH matrices ride ONE all_to_all (stacked on a leading axis):
        # every collective is a cross-device rendezvous, and the flush's
        # wall-clock overhead scales with rendezvous count, not bytes —
        # the stack copy is plain HBM traffic the combiner pays anyway.
        # (the scopes name the three collectives in the device trace:
        # flush.a2a, flush.psum, flush.pmax)
        with jax.named_scope("flush.a2a"):
            both = jax.lax.all_to_all(jnp.stack([dv, dw]), axis,
                                      split_axis=1, concat_axis=2,
                                      tiled=True)
        dv, dw = both[0], both[1]
        # this replica's key sub-block of the (replica-replicated) minmax
        j = jax.lax.axis_index(axis)
        mm = jax.lax.dynamic_slice_in_dim(
            inputs.minmax, j * dv.shape[0], dv.shape[0], axis=1)
    else:
        mm = inputs.minmax
    ev = digest_eval(dv, dw, mm[0], mm[1], percentiles, uniform=uniform)

    set_regs = jnp.max(inputs.hll_regs, axis=0)
    planes = jnp.sum(inputs.counter_planes, axis=0)   # [K2_s, 2]
    uts = jnp.max(inputs.uts_regs, axis=0)
    if axis is not None:
        # one psum for both counter planes, one u8 pmax for both
        # register families (same rendezvous-count argument as above)
        with jax.named_scope("flush.psum"):
            planes = jax.lax.psum(planes, axis)
        n_set = set_regs.size
        with jax.named_scope("flush.pmax"):
            regs = jax.lax.pmax(
                jnp.concatenate([set_regs.ravel(), uts]), axis)
        set_regs = regs[:n_set].reshape(set_regs.shape)
        uts = regs[n_set:]
    chi, clo = planes[..., 0], planes[..., 1]
    if shard_axis is not None:
        with jax.named_scope("flush.pmax"):
            uts = jax.lax.pmax(uts, shard_axis)
    return FlushOutputs(
        digest_eval=ev, counter_hi=chi, counter_lo=clo,
        set_regs=set_regs, set_estimates=hll_mod.estimate(set_regs),
        unique_ts=hll_mod.estimate(uts[None, :])[0])


def collective_bytes(mesh: Mesh, shapes) -> int:
    """Bytes ONE device sends over the mesh per flush, reckoned from the
    global shapes of the FlushInputs dispatched (`shapes`: one shape per
    field, in field order) — arithmetic, not a measurement; the flush
    timeline row carries it as `collective_bytes`.  flush_body's
    collectives, S = shards, R = replicas:

      all_to_all over 'replica': the stacked [2, K/S, D/R] f32 block,
        of which a device keeps 1/R and sends (R-1)/R;
      psum over 'replica': the [K2/S, 2] f32 counter planes, as a ring
        all-reduce sends them, 2(R-1)/R times;
      pmax over 'replica': the [S_rows/S, m] u8 set registers and the
        [m_u] unique-timeseries registers in one buffer, 2(R-1)/R times;
      pmax over 'shard': the [m_u] u8 registers again, 2(S-1)/S times
        (the one collective left when R == 1).
    """
    dense, _dw, _mm, hll, planes, uts = shapes
    S = int(mesh.shape[SHARD_AXIS])
    R = int(mesh.shape[REPLICA_AXIS])
    a2a = 2 * (dense[0] // S) * (dense[1] // R) * 4 * (R - 1) // R
    psum = (planes[1] // S) * planes[2] * 4 * 2 * (R - 1) // R
    pmax = ((hll[1] // S) * hll[2] + uts[1]) * 2 * (R - 1) // R
    pmax_shard = uts[1] * 2 * (S - 1) // S
    return a2a + psum + pmax + pmax_shard


def pack_outputs(out: FlushOutputs) -> jax.Array:
    """Flatten every f32-representable flush output into ONE device
    buffer.  Per-launch dispatch cost scales with the number of output
    buffer handles (measured ~0.1 ms/handle on a congested link — see
    BASELINE.md), so the production program hands the host one flat
    vector to slice instead of six arrays; `set_regs` stays separate
    (u8, 4x the bytes as f32, and only consumed when a local tier
    forwards mixed-scope sets)."""
    return jnp.concatenate([
        out.digest_eval.ravel(), out.counter_hi, out.counter_lo,
        out.set_estimates, out.unique_ts[None]])


def unpack_outputs(flat, k: int, n_pct: int, k2: int, s: int):
    """Host-side views into a fetched pack_outputs vector: returns
    (digest_eval [k, n_pct+2], counter_hi [k2], counter_lo [k2],
    set_estimates [s], unique_ts scalar)."""
    ne = k * (n_pct + 2)
    ev = flat[:ne].reshape(k, n_pct + 2)
    chi = flat[ne:ne + k2]
    clo = flat[ne + k2:ne + 2 * k2]
    est = flat[ne + 2 * k2:ne + 2 * k2 + s]
    return ev, chi, clo, est, float(flat[ne + 2 * k2 + s])


def make_serving_flush(mesh: Optional[Mesh]):
    """Build the per-flush program.

    Without a mesh, returns fn(dense_v, dense_w, minmax, percentiles) ->
    [K, P+2] — digests only, because sets/counters/unique-ts resolve on
    host when there is nothing to reduce over (core/arena.py).

    With a mesh, returns the shard_map'd full-family program
    fn(FlushInputs, percentiles, uniform=False, donate=False) ->
    (packed_f32, set_regs_u8): keys and set/counter rows shard over
    'shard'; staged sample depth repartitions over 'replica' with ONE
    all_to_all (each device evaluates K_s/R keys at full depth — no
    redundant replica evaluation), set register lanes and counter planes
    reduce over 'replica' (pmax / psum); the unique-timeseries registers
    pmax over both axes (across processes this is the DCN union of
    per-host tallies).  When the replica axis has size 1 every
    collective is elided at trace time, so the mesh=1 program is the
    single-device program plus wrapper dispatch only.  The f32 outputs
    come back as ONE flat buffer (pack_outputs; unpack with
    unpack_outputs) — per-launch dispatch cost scales with
    output-handle count, so the production flush hands the host two
    buffers, not six.  `donate=True` (static) donates the PER-FLUSH f32
    input buffers — the staged dense matrices, minmax and counter
    planes — killing XLA's copy-on-entry; the u8 unique-ts registers
    (fresh each flush but with no aliasable u8 output) and the live
    set-register lanes (arena state that must survive the call) are
    never donated.  Donate only when the caller will not touch the
    staged buffers again (a forwarding tier re-reads the dense matrices
    for digest export).  On CPU the donations are reported unusable at
    compile (one UserWarning per shape — no f32 output matches the
    staged buffers' layouts); they stay marked for the TPU backend,
    where XLA reuses the donated HBM as scratch.
    """
    if mesh is None:
        @functools.partial(jax.jit, static_argnames=("uniform",))
        def general(dv, dw, minmax, pct, uniform=False):
            return digest_eval(dv, dw, minmax[0], minmax[1], pct,
                               uniform=uniform)

        general_d = jax.jit(
            lambda dv, dw, minmax, pct, uniform=False: digest_eval(
                dv, dw, minmax[0], minmax[1], pct, uniform=uniform),
            static_argnames=("uniform",), donate_argnums=(0, 1, 2))

        @jax.jit
        def depth_variant(dv, depths, pct):
            return digest_eval_uniform(dv, depths, pct)

        # the int16 depth vector stays undonated: no int16 output
        # exists to alias it into, and jax warns on unusable donations
        depth_variant_d = jax.jit(
            lambda dv, depths, pct: digest_eval_uniform(dv, depths, pct),
            donate_argnums=(0,))

        def unmeshed(dv, dw, minmax, pct, uniform=False, donate=False):
            fn = general_d if donate else general
            return fn(dv, dw, minmax, pct, uniform=uniform)

        # the deep tier of a skewed interval (aggregator._dispatch_flush):
        # the few keys past DEEP_TIER_THRESHOLD staged points, weighted,
        # at a fixed depth — the general program under a name and a
        # scope of its own, so a trace tells the tiers apart
        def flush_deep_tier(dv, dw, minmax, pct):
            with jax.named_scope("flush.tier.deep"):
                return digest_eval(dv, dw, minmax[0], minmax[1], pct)

        unmeshed.deep_tier = jax.jit(flush_deep_tier)
        unmeshed.deep_tier_donated = jax.jit(flush_deep_tier,
                                             donate_argnums=(0, 1, 2))
        unmeshed.lower = general.lower
        unmeshed.lower_donated = general_d.lower
        # uniform intervals upload (values, per-row depths) instead of
        # (values, weights) — half the bytes; the aggregator routes
        # there whenever DigestArena.staged_uniform held
        unmeshed.depth_variant = depth_variant
        unmeshed.depth_variant_donated = depth_variant_d
        return unmeshed

    n_replicas = int(mesh.shape[REPLICA_AXIS])
    axis = REPLICA_AXIS if n_replicas > 1 else None
    spec_lanes = P(REPLICA_AXIS, SHARD_AXIS, None)
    # with the all_to_all repartition the evaluation rows shard over
    # BOTH axes (shard-major, replica-minor — exactly the dense build's
    # row order); at R == 1 nothing repartitions
    ev_spec = (P((SHARD_AXIS, REPLICA_AXIS), None) if n_replicas > 1
               else P(SHARD_AXIS, None))
    progs: dict = {}

    def _prog(uniform: bool, donate: bool):
        prog = progs.get((uniform, donate))
        if prog is None:
            from veneur_tpu.parallel import mesh as mesh_mod
            fn = mesh_mod.shard_map(
                functools.partial(flush_body, axis=axis,
                                  shard_axis=SHARD_AXIS,
                                  uniform=uniform),
                mesh=mesh,
                in_specs=(FlushInputs(
                    dense_v=P(SHARD_AXIS, REPLICA_AXIS),
                    dense_w=P(SHARD_AXIS, REPLICA_AXIS),
                    minmax=P(None, SHARD_AXIS),
                    hll_regs=spec_lanes,
                    counter_planes=spec_lanes,
                    uts_regs=P(REPLICA_AXIS, None)), P(None)),
                out_specs=FlushOutputs(
                    digest_eval=ev_spec,
                    counter_hi=P(SHARD_AXIS), counter_lo=P(SHARD_AXIS),
                    set_regs=P(SHARD_AXIS, None),
                    set_estimates=P(SHARD_AXIS),
                    unique_ts=P()))

            # leaf-splayed signature: jit donation is per-argument, and
            # the live set registers (hll_regs) must NOT be donated —
            # so the per-flush buffers travel as the leading arguments
            def run(dense_v, dense_w, minmax, counter_planes, uts_regs,
                    hll_regs, pct):
                out = fn(FlushInputs(
                    dense_v=dense_v, dense_w=dense_w, minmax=minmax,
                    hll_regs=hll_regs, counter_planes=counter_planes,
                    uts_regs=uts_regs), pct)
                return pack_outputs(out), out.set_regs

            # donate the f32 per-flush buffers only: the u8 unique-ts
            # registers are tiny and have no aliasable u8 output (jax
            # warns on unusable donations), and the live set-register
            # lanes must survive the call
            prog = progs[(uniform, donate)] = jax.jit(
                run, donate_argnums=(0, 1, 2, 3) if donate else ())
        return prog

    def _splay(inputs):
        return (inputs.dense_v, inputs.dense_w, inputs.minmax,
                inputs.counter_planes, inputs.uts_regs, inputs.hll_regs)

    def meshed(inputs, pct, uniform=False, donate=False):
        return _prog(uniform, donate)(*_splay(inputs), pct)

    # expose lowering for HLO inspection (dryrun's replica-group check)
    meshed.lower = (
        lambda inputs, pct, uniform=False: _prog(uniform, False).lower(
            *_splay(inputs), pct))
    return meshed


def collective_group_sizes(hlo: str, op: str) -> list[int]:
    """Group sizes of every `op` collective (e.g. "all-to-all") in an
    optimized HLO text, both replica_groups syntaxes — what the dryrun,
    the chip smoke and the chip-compile test check the depth
    repartition against (it must stay inside one replica group)."""
    import re

    sizes = []
    # brace-list form: replica_groups={{0,4},{1,5},...}
    for g in re.findall(op + r"[^\n]*replica_groups=\{(.*?)\}\}", hlo):
        sizes += [len(grp.strip("{}").split(","))
                  for grp in g.split("},{")]
    # iota v2 form: replica_groups=[num_groups,group_size]<=[..]
    for _ng, gs in re.findall(
            op + r"[^\n]*replica_groups=\[(\d+),(\d+)\]<=", hlo):
        sizes.append(int(gs))
    return sizes


@functools.partial(jax.jit, static_argnames=("compression", "cap"))
def digest_export(dense_v: jax.Array, dense_w: jax.Array,
                  rows: jax.Array, compression: float, cap: int
                  ) -> tuple[jax.Array, jax.Array]:
    """Compress the staged points of the given (compacted) rows into wire
    centroids `[F, cap]` for forwarding (ForwardableMetrics,
    `worker.go:179-216` / `MergingDigest.Data`,
    `merging_digest.go:474-483`).  Gathers rows first so both the compute
    and the readback scale with the forwarded subset, not the arena.
    bf16-staged values (compact_general staging) widen here: compress
    accumulates weighted sums, which bf16 would corrupt."""
    dv_r = dense_v[rows]
    if dv_r.dtype == jnp.bfloat16:
        dv_r = dv_r.astype(jnp.float32)
    return td.compress(dv_r, dense_w[rows], compression, cap)


@functools.partial(jax.jit, static_argnames=("compression", "cap"))
def digest_export_uniform(dense_v: jax.Array, depths: jax.Array,
                          rows: jax.Array, compression: float, cap: int
                          ) -> tuple[jax.Array, jax.Array]:
    """digest_export for the depth-vector (uniform) dense build: the 0/1
    weights of the gathered rows are reconstructed ON DEVICE from the
    per-row depths (they never crossed the host->device link)."""
    d = dense_v.shape[1]
    sub_depths = depths[rows].astype(jnp.int32)
    # weights in f32 regardless of the value staging dtype: bf16 cannot
    # represent integer counts above 256, and compress() accumulates
    # them (cumsum/total) — bf16 weights would corrupt exported digests
    dw = (jnp.arange(d, dtype=jnp.int32)[None, :]
          < sub_depths[:, None]).astype(jnp.float32)
    return td.compress(dense_v[rows].astype(jnp.float32), dw,
                       compression, cap)


@functools.partial(jax.jit, static_argnames=("compression", "cap"))
def partial_digests(dense_v: jax.Array, dense_w: jax.Array,
                    compression: float, cap: int
                    ) -> tuple[jax.Array, jax.Array]:
    """One batched compress of a dense `[U, W]` sample matrix into per-row
    partial digests `[U, cap]` — the hot-key pre-reduction: an arbitrarily
    deep backlog collapses into <= cap weighted points per row, which
    re-stage as ordinary samples (weight-preserving, order-invariant).
    The arena launches it at ONE shape, `[HOT_TILE_ROWS, HOT_TILE_WIDTH]`
    (core/arena.py), whatever a tick carries."""
    with jax.named_scope("digest.hot_compress"):
        return td.compress(dense_v, dense_w, compression, cap)


# ---------------------------------------------------------------------------
# Set (HLL) lane kernels — device-resident register state (meshed tiers,
# and an unmeshed arena pre-sized for its deployment: SetArena.resident)
# ---------------------------------------------------------------------------

# An unmeshed resident arena launches a sync tick's staged (row,
# register, rank) triples padded to one of three sizes — a lone triple
# (the server's own 1 %-sampled `ssf.names_unique`) at 1, a tick of up
# to LANE_SCATTER_SMALL at that, anything larger in chunks of
# LANE_SCATTER_CHUNK — and its imported dense register rows in chunks of
# LANE_MERGE_CHUNK rows: the shapes a window can need are then known at
# boot (SetArena.prewarm_lanes launches each once), whatever a fleet
# sends.  2^20 triples are 9 MB of operands a launch, and a large launch
# is worth filling: the chip's compiler lays the tiled u8 plane out flat
# for an elementwise scatter and back again, two passes over the whole
# plane (tests/test_chip_compile.py) — 24 ms a launch on a 1 GiB plane
# from 2^17 triples up, 9 ms at 2^12 (PERF.md section 6, PR 32).  2^12
# triples (36 KB) are what a node's UDP set lines bring a drain tick;
# 128 rows are 2 MiB at p = 14.  Meshed lanes pad each tick to its own power of
# two, one launch, and compile what a fleet sends at its first launch.
LANE_SCATTER_SMALL = 1 << 12
LANE_SCATTER_CHUNK = 1 << 20
LANE_MERGE_CHUNK = 128


def _set_lane_scatter(lanes_regs: jax.Array, rows: jax.Array,
                      idx: jax.Array, rank: jax.Array,
                      lane: int) -> jax.Array:
    """Scatter-max staged (set row, register index, rank) triples into lane
    `lane` of the `[R_s, S, m]` register state — the device half of
    Sketch.Insert (`samplers/samplers.go:242-244`).  Padding entries with
    rank 0 are no-ops (max against an empty register)."""
    with jax.named_scope("set.lane_scatter"):
        return lanes_regs.at[lane, rows, idx].max(rank)


def _set_lane_merge_rows(lanes_regs: jax.Array, rows: jax.Array,
                         regmat: jax.Array, lane: int) -> jax.Array:
    """Register-wise max of imported full register rows `[n, m]` into lane
    `lane` (Set.Merge, `samplers/samplers.go:299-311`).  All-zero padding
    rows are no-ops."""
    with jax.named_scope("set.lane_merge"):
        return lanes_regs.at[lane, rows].max(regmat)


# In-place (donating) updates for the common case, plus COPYING twins.
# SetArena.sync picks per call (see lane_donation_ok): the PJRT CPU
# runtime double-frees donated sharded-update buffers that race an
# in-flight reader on another executable — observed as corrupted set
# estimates and interpreter segfaults under the overlapped flush
# pipeline (tests/test_parallel.py conservation stress) — and a
# dispatched-but-not-fetched flush additionally holds a snapshot the
# update must never scribble over on ANY backend.
set_lane_scatter = functools.partial(
    jax.jit, static_argnames=("lane",),
    donate_argnums=(0,))(_set_lane_scatter)
set_lane_scatter_copy = functools.partial(
    jax.jit, static_argnames=("lane",))(_set_lane_scatter)
set_lane_merge_rows = functools.partial(
    jax.jit, static_argnames=("lane",),
    donate_argnums=(0,))(_set_lane_merge_rows)
set_lane_merge_rows_copy = functools.partial(
    jax.jit, static_argnames=("lane",))(_set_lane_merge_rows)


@functools.lru_cache(maxsize=None)
def lane_donation_ok() -> bool:
    """Whether the in-place (donating) lane-update kernels are safe on
    this backend.  PJRT:CPU mismanages donation of sharded u8 update
    chains when another executable is concurrently in flight (the
    symptom is silent register corruption, sometimes a hard segfault);
    the TPU runtime — where donation is the production norm — is fine.
    Cached once: the backend cannot change within a process."""
    return jax.default_backend() != "cpu"


@jax.jit
def set_reset_rows(lanes_regs: jax.Array, rows: jax.Array) -> jax.Array:
    """Zero the given set rows in every lane.  NOT donating: the flush
    snapshot may still reference the pre-reset buffer while emission runs
    outside the aggregator lock."""
    with jax.named_scope("set.lane_reset"):
        return lanes_regs.at[:, rows].set(0)


@jax.jit
def set_reset_mask(lanes_regs: jax.Array, keep: jax.Array) -> jax.Array:
    """The unmeshed resident arena's reset: rows whose `keep` ([S] u8)
    is 0 come back zeroed, in every lane.  One elementwise pass whose
    one shape is the plane's — a flush's touched count picks no program
    (set_reset_rows' index vector follows it) — and NOT donating, for
    set_reset_rows' reason."""
    with jax.named_scope("set.lane_reset"):
        return lanes_regs * keep[None, :, None]


@jax.jit
def set_estimate_plane(lanes_regs: jax.Array) -> jax.Array:
    """[S] f32 LogLog-Beta estimates of every row's lane-union
    registers, where the registers live: the flush of an unmeshed
    resident arena that forwards no set reads 4 bytes a row back, not
    2^p.  Whole plane, one shape (the host picks the touched rows out of
    the answer): the arena was sized for its deployment's keys, so the
    plane IS the touched rows' bucket, and no touched count compiles.
    NOT donating: the flush pins the lanes (snapshot_lanes)."""
    with jax.named_scope("flush.set_estimate"):
        return hll_mod.estimate(jnp.max(lanes_regs, axis=0))


@jax.jit
def set_regs_pack(set_regs: jax.Array, rows: jax.Array) -> jax.Array:
    """Flat [n * m] u8 readback of merged HLL registers for forwarding
    (Set.Metric marshal, `samplers/samplers.go:279-295`)."""
    return set_regs[rows].reshape(-1)


@jax.jit
def set_gather_rows(lanes_regs: jax.Array, rows: jax.Array) -> jax.Array:
    """[n, m] u8 readback of the lane-union registers for the given rows —
    the flush-side read of resident set arenas (flush_resident_arenas).
    Unmeshed resident state has one lane, so the lane max is a no-op; the
    meshed form is the same reduction flush_body performs.  NOT donating:
    a dispatched-but-unfetched flush pins the lanes (snapshot_lanes)."""
    return jnp.max(lanes_regs, axis=0)[rows]


# ---------------------------------------------------------------------------
# Resident-delta scatter kernels (flush_resident_arenas)
# ---------------------------------------------------------------------------
#
# The device half of the delta-flush dense build: the host streams fixed-
# size (row, pos, value[, weight]) delta chunks into HBM DURING the
# interval (DigestArena.stream_resident), and at flush time the dense
# sample matrix is assembled ON DEVICE — zeros [U, D] plus one scatter per
# chunk — so the flush critical path uploads only the dense-id map and the
# un-streamed tail, never the full key space.  Chunk `rows` are arena-row
# ids; `dense_id` maps them to this flush's compacted dense rows, with
# INT32_MAX marking rows outside the flush (and the padding sentinel slot
# at index capacity), which mode="drop" discards without a host round
# trip.  Positions are the host's per-row arrival cursors, byte-identical
# to build_dense's stable-sort ordinals — the bit-parity contract.

_RESIDENT_DROP = 2**31 - 1  # dense_id value for rows absent from the flush


def _resident_scatter(dense_v: jax.Array, dense_id: jax.Array,
                      rows: jax.Array, pos: jax.Array,
                      vals: jax.Array) -> jax.Array:
    """Scatter one value-only delta chunk (uniform interval: the weight
    matrix never exists, occupancy rides the per-row depth vector)."""
    r = dense_id[rows]
    return dense_v.at[r, pos].set(vals, mode="drop")


def _resident_scatter_w(dense_v: jax.Array, dense_w: jax.Array,
                        dense_id: jax.Array, rows: jax.Array,
                        pos: jax.Array, vals: jax.Array,
                        wts: jax.Array
                        ) -> tuple[jax.Array, jax.Array]:
    """Scatter a weighted delta chunk into the (values, weights) pair."""
    r = dense_id[rows]
    return (dense_v.at[r, pos].set(vals, mode="drop"),
            dense_w.at[r, pos].set(wts, mode="drop"))


def _resident_scatter_w1(dense_v: jax.Array, dense_w: jax.Array,
                         dense_id: jax.Array, rows: jax.Array,
                         pos: jax.Array, vals: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
    """Weighted-build scatter of a chunk streamed while the interval was
    still uniform: its weights are exactly 1.0 and were never uploaded —
    they materialize on device (exact in every eval dtype)."""
    r = dense_id[rows]
    ones = jnp.ones(vals.shape, dense_w.dtype)
    return (dense_v.at[r, pos].set(vals, mode="drop"),
            dense_w.at[r, pos].set(ones, mode="drop"))


# Donating twins consume the dense accumulator chain in place (the
# production TPU shape); the copying twins are the CPU-backend fallback —
# the SAME PJRT:CPU donation race documented at lane_donation_ok applies
# to the resident dense chain (a scatter's donated input racing the
# previous flush's still-in-flight executable), so resident_donation_ok
# gates every assembly the way SetArena.sync gates lane updates.
resident_scatter = jax.jit(_resident_scatter, donate_argnums=(0,))
resident_scatter_copy = jax.jit(_resident_scatter)
resident_scatter_w = jax.jit(_resident_scatter_w, donate_argnums=(0, 1))
resident_scatter_w_copy = jax.jit(_resident_scatter_w)
resident_scatter_w1 = jax.jit(_resident_scatter_w1, donate_argnums=(0, 1))
resident_scatter_w1_copy = jax.jit(_resident_scatter_w1)


def resident_donation_ok() -> bool:
    """Donation gate for the resident dense-assembly chain — one policy
    with the lane kernels (see lane_donation_ok): in-place on TPU,
    copying kernels on PJRT:CPU."""
    return lane_donation_ok()


# One-shot measured staged-vs-resident probe state (ROADMAP #2
# remainder: hosts with a marginal host-device link pick the faster
# assembly path empirically, not by backend name).  Module-level dict
# rather than an lru_cache so /debug/vars can INSPECT the decision
# without forcing a measurement (http_api.link_probe_stats).
_LINK_PROBE: dict = {"measured": False, "probes": 0}
_PROBE_ROWS = 256          # synthetic dense chunk: [rows, depth] f32
_PROBE_DEPTH = 64
_PROBE_CHUNKS = 4          # per-chunk dispatch is what the stream pays
_PROBE_REPS = 3            # best-of timing after a compile warmup


def _measure_link_probe() -> dict:
    """Time the two ways a flush gets its dense matrix into device
    memory: (a) RESIDENT — the interval's delta chunks scatter into a
    device-born accumulator (per-chunk upload of slim COO arrays +
    scatter dispatch); (b) STAGED — the host builds the dense matrix
    and uploads it whole at flush time.  On a real accelerator the
    staged path pays the full dense upload on the flush critical path,
    so (a) wins; on PJRT:CPU "upload" is a memcpy and (a) is pure
    scatter-dispatch overhead, so (b) wins — the measurement reproduces
    the old backend-name heuristic where that heuristic was right, and
    decides marginal links by data.  Small fixed shapes: one compile +
    microseconds of steady-state per process, cached forever."""
    import time

    import numpy as np

    rows = np.tile(np.arange(_PROBE_ROWS, dtype=np.int32),
                   _PROBE_DEPTH // 4)
    pos = np.repeat(np.arange(_PROBE_DEPTH // 4, dtype=np.int32),
                    _PROBE_ROWS)
    vals = np.linspace(0.0, 1.0, rows.size, dtype=np.float32)
    dense_id = jnp.arange(_PROBE_ROWS, dtype=jnp.int32)

    def resident_once():
        dv = resident_dense_zeros((_PROBE_ROWS, _PROBE_DEPTH),
                                  jnp.float32)
        for _ in range(_PROBE_CHUNKS):
            dv = resident_scatter_copy(
                dv, dense_id, jnp.asarray(rows), jnp.asarray(pos),
                jnp.asarray(vals))
        return dv.block_until_ready()

    def staged_once():
        dense = np.zeros((_PROBE_ROWS, _PROBE_DEPTH), np.float32)
        for _ in range(_PROBE_CHUNKS):
            dense[rows, pos] = vals
        return jax.device_put(dense).block_until_ready()

    resident_once(), staged_once()     # compile/warm outside the clock
    res_s = stg_s = float("inf")
    for _ in range(_PROBE_REPS):
        t0 = time.perf_counter()
        resident_once()
        res_s = min(res_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        staged_once()
        stg_s = min(stg_s, time.perf_counter() - t0)
    # device assembly must win CLEARLY: near-parity links keep the
    # staged path (no compile-churn exposure for a wash)
    return {"ok": res_s < 0.8 * stg_s,
            "backend": jax.default_backend(),
            "resident_us": round(res_s * 1e6, 1),
            "staged_us": round(stg_s * 1e6, 1),
            "forced": False}


def resident_link_ok() -> bool:
    """Whether this backend's host<->device link makes flush-time
    device assembly (resident delta stream) faster than the staged
    host-dense-build + upload — decided by a ONE-SHOT measured probe
    (cached per process; `/debug/vars -> resident_link_probe`).
    `VENEUR_TPU_RESIDENT_LINK=0|1` pins the answer without measuring
    (hermetic CI cells).  When False, the digest/moments
    device-assembly half of flush_resident_arenas degrades to the
    staged (chunk-pipelined) flush; the resident SET lanes (u8
    scatter-max, readback-on-checkpoint) stay active everywhere.
    Tests force the device-assembly path via the arenas'
    resident_device_assembly override."""
    if _LINK_PROBE["measured"]:
        return _LINK_PROBE["ok"]
    import os
    forced = os.environ.get("VENEUR_TPU_RESIDENT_LINK")
    if forced is not None and forced != "":
        _LINK_PROBE.update(ok=forced not in ("0", "false", "no"),
                           backend=jax.default_backend(),
                           forced=True, measured=True)
        _LINK_PROBE["probes"] += 1
        return _LINK_PROBE["ok"]
    _LINK_PROBE.update(_measure_link_probe())
    _LINK_PROBE["measured"] = True
    _LINK_PROBE["probes"] += 1
    return _LINK_PROBE["ok"]


def link_probe_stats() -> dict:
    """The cached probe decision for /debug/vars — never forces a
    measurement (`measured: false` until something consulted the
    link)."""
    return dict(_LINK_PROBE)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def resident_dense_zeros(shape, dtype) -> jax.Array:
    """Device-side zero dense accumulator — the resident build's starting
    buffer is born in HBM; nothing crosses the host link for it."""
    return jnp.zeros(shape, dtype)
