"""The sharded global-aggregation flush step — the framework's flagship
SPMD program.

One call evaluates the whole global tier's flush: the interval's staged
weighted points (raw samples and forwarded digest centroids alike) are
evaluated for every key at once, with
  - t-digest reduce  = all_gather(sample slices) over the replica axis +
    one batched sorted evaluation (the collective form of Histo.Merge,
    `samplers/samplers.go:539-543` / `worker.go:402-459`),
  - HLL reduce       = lax.pmax over replica register lanes,
  - counter reduce   = lax.psum over (hi, lo) f32 planes,
  - unique-timeseries tally = pmax over *both* axes + estimate
    (the device analog of tallyTimeseries, `flusher.go:249-258`).

Keys are sharded over the 'shard' mesh axis, so each device only touches
its K/n_shards rows; collectives ride ICI within the replica groups.
Single-device use (entry() in __graft_entry__.py) is the same body with no
collectives.  The body is shared with the production serving path
(veneur_tpu/parallel/serving.py flush_body) — this module only packages it
with example inputs for compile checks and the parity tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from veneur_tpu.parallel import serving
from veneur_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS
from veneur_tpu.sketches import tdigest as td

FlushInputs = serving.FlushInputs
FlushOutputs = serving.FlushOutputs


@functools.partial(jax.jit, static_argnames=("uniform",))
def flush_step(inputs: FlushInputs, percentiles: jax.Array,
               uniform: bool = False) -> FlushOutputs:
    """Single-device flush step (the compile-checked entry point)."""
    return serving.flush_body(inputs, percentiles, axis=None,
                              uniform=uniform)


def _sharded_body(mesh: Mesh):
    """The shard_map'd flush body over a (shard, replica) mesh: keys
    over 'shard', staged depth repartitioned over 'replica' with one
    all_to_all (each device evaluates K_s/R keys at full depth), lane
    reductions over 'replica'.  When the replica axis has size 1 the
    collectives are elided at trace time (the mesh=1 specialization)."""
    from veneur_tpu.parallel import mesh as mesh_mod
    n_replicas = int(mesh.shape[REPLICA_AXIS])
    axis = REPLICA_AXIS if n_replicas > 1 else None
    ev_spec = (P((SHARD_AXIS, REPLICA_AXIS), None) if n_replicas > 1
               else P(SHARD_AXIS, None))
    spec_lanes = P(REPLICA_AXIS, SHARD_AXIS, None)
    return mesh_mod.shard_map(
        functools.partial(serving.flush_body, axis=axis,
                          shard_axis=SHARD_AXIS),
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
            set_regs=P(SHARD_AXIS, None), set_estimates=P(SHARD_AXIS),
            unique_ts=P()))


def make_sharded_flush_step(mesh: Mesh):
    """Build the shard_map'd multi-chip flush step over a
    (shard, replica) mesh, returning unpacked FlushOutputs (the
    compile-check / parity-test shape; production launches the packed
    form, serving.make_serving_flush)."""
    return jax.jit(_sharded_body(mesh))


def example_inputs(n_keys: int = 64, n_lanes: int = 2, n_sets: int = 8,
                   depth: int = 32,
                   compression: float = td.DEFAULT_COMPRESSION,
                   hll_p: int = 10, seed: int = 0,
                   weighted: bool = False) -> FlushInputs:
    """Small synthetic inputs for compile checks and dry runs: every key
    holds `n_lanes * depth` staged weighted points (the dense depth axis
    tiles the replica mesh axis evenly).  Rows pad up to a power of two
    with zero-weight rows, exactly like the production dense builder
    (arena.py build_dense) — the padded rows are part of the honest
    workload.  weighted=True stages integer centroid weights in [1, 8]
    (the shape of re-compressed forwarded digests) instead of the
    weight-1 singletons an under-compressed incoming digest carries."""
    import numpy as np
    rng = np.random.default_rng(seed)
    m = 1 << hll_p
    r, s = n_lanes, n_sets
    k = 1 << (n_keys - 1).bit_length() if n_keys > 1 else 1
    d = r * depth

    vals = rng.gamma(2.0, 10.0, (k, d)).astype(np.float32)
    wts = np.zeros((k, d), np.float32)
    if weighted:
        wts[:n_keys] = rng.integers(1, 9, (n_keys, d)).astype(np.float32)
    else:
        wts[:n_keys] = 1.0
    minmax = np.stack([vals.min(axis=1), vals.max(axis=1)]).astype(
        np.float32)
    counters = rng.integers(0, 100, (r, k)).astype(np.float32)
    planes = np.stack(
        [np.zeros_like(counters), counters], axis=-1)  # values < 2^24
    return FlushInputs(
        dense_v=jnp.asarray(vals), dense_w=jnp.asarray(wts),
        minmax=jnp.asarray(minmax),
        hll_regs=jnp.asarray(
            rng.integers(0, 20, (r, s, m)).astype(np.uint8)),
        counter_planes=jnp.asarray(planes),
        uts_regs=jnp.asarray(
            rng.integers(0, 20, (r, m)).astype(np.uint8)))
