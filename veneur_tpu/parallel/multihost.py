"""Multi-host (DCN) scaling for the sharded aggregation tier.

The reference scales across hosts with gRPC forwarding + the proxy's
consistent-hash ring (SURVEY §2.3); the TPU-native global tier scales the
same state over multiple accelerator hosts with `jax.distributed`: after
`init_multihost`, `jax.devices()` returns every chip in the cluster, and
`mesh.make_mesh` builds the (shard, replica) mesh over all of them.

Axis/topology mapping (why the layout is DCN-friendly):

  * `jax.devices()` orders devices process-by-process, and the mesh
    reshape is row-major, so when `replicas` DIVIDES the per-host device
    count each replica group is a contiguous intra-host run.  The
    flush's only collective (the replica-axis `all_gather` in
    `parallel/serving.py flush_body`) then rides ICI; `make_mesh` warns
    when a configured replica count would straddle hosts;
  * the `shard` axis (key-space partition) spans hosts but needs NO
    collective — each key's digests live on exactly one shard, the
    device analog of the proxy ring assigning each key to one global.
    Cross-host traffic stays where the reference keeps it: the gRPC
    forward/import edge.

**Lockstep contract.** Multi-controller serving is SPMD: every process
runs the same flush program on the same global shapes.  The framework
enforces the mechanics — `serving.put` builds global arrays from each
process's shard view, `serving.fetch` batches readbacks into one DCN
all-gather per flush, and the aggregator agrees on touched-family flags
and dense dimensions with a single small gather before each flush — but
the deployment must provide: (a) a consistent key-registration order
across processes (the control plane's analog of the proxy ring's
membership view), (b) pre-sized set arenas (one-sided growth would
diverge global shapes), and (c) a synchronized flush schedule
(`synchronize_with_interval`).  Contract (a) is now tripwired: the
per-flush gather carries each arena's key-set and key->row fingerprints
(`core/arena.py key_checksum`), and controllers holding the same keys
with different row assignments raise a crisp per-family lockstep error
instead of silently merging unrelated timeseries; ring-style asymmetric
registration (a key present only on its owning controller) remains
legal.  The multi-process mesh serves the GLOBAL
tier; local/forwarding tiers stay single-process and reach it over the
gRPC forward edge, exactly like the reference's proxy ring
(tests/test_multihost.py exercises two real jax.distributed processes
end to end).

Single-host single-process remains the default; none of this is required
until a deployment grows past one accelerator host.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger("veneur_tpu.parallel.multihost")

_initialized = False


def init_multihost(coordinator_address: str,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> None:
    """Join the JAX distributed cluster (idempotent).

    With TPU metadata available (GKE/TPU-VM environments), the arguments
    beyond the coordinator are auto-detected; pass them explicitly
    elsewhere.  Must run before any other JAX call in the process."""
    global _initialized
    if _initialized:
        return
    import jax

    # XLA:CPU runs cross-process collectives only through the gloo
    # transport ("Multiprocess computations aren't implemented on the
    # CPU backend" otherwise) — select it whenever the process is
    # pinned to the CPU platform, BEFORE the backend initializes.  TPU
    # processes keep their native DCN transport untouched.
    platforms = (jax.config.jax_platforms
                 or os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" in str(platforms).lower():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    kwargs = {"coordinator_address": coordinator_address}
    if num_processes is not None and num_processes >= 0:
        kwargs["num_processes"] = num_processes
    if process_id is not None and process_id >= 0:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
    _initialized = True
    logger.info("joined distributed cluster: process %d/%d, "
                "%d global devices (%d local)",
                jax.process_index(), jax.process_count(),
                len(jax.devices()), len(jax.local_devices()))


def maybe_init_from_config(cfg) -> None:
    """Server bootstrap hook: join the cluster when the config names a
    coordinator (no-op otherwise)."""
    if getattr(cfg, "distributed_coordinator", ""):
        init_multihost(
            cfg.distributed_coordinator,
            num_processes=cfg.distributed_num_processes or None,
            process_id=(cfg.distributed_process_id
                        if cfg.distributed_process_id >= 0 else None))


# the arena families whose key dictionaries the lockstep gather compares
# (`_ArenaBase.family` of MetricAggregator._FAMILIES)
LOCKSTEP_FAMILIES = ("digest", "moments", "compactor", "counter", "gauge",
                     "set", "status")


def lockstep_agree(n_digests: int, depth: int, n_counters: int,
                   n_sets: int, uniform: bool, fingerprints: dict) -> tuple:
    """Lockstep agreement before a multi-controller flush: every
    controller must run the same program on the same global shapes and
    the same fetch sequence, whatever ITS families touched this interval
    — one tiny DCN gather of (touched counts, staged depth) decides for
    everyone.  The same gather carries each arena's key-dictionary
    fingerprint (`fingerprints`: family -> (key-set checksum, key->row
    checksum), the snapshot's lock-coherent copy): a registration-order
    divergence between controllers would silently misalign rows (every
    process indexes the same global arrays), so it fails loudly here
    instead.  Returns the agreed (touched digest rows, depth, touched
    counter rows, touched set rows, uniform)."""
    import jax
    import numpy as np
    from jax.experimental import multihost_utils

    names = LOCKSTEP_FAMILIES
    cks = np.asarray(
        [fingerprints[n][0] for n in names]
        + [fingerprints[n][1] for n in names],
        np.uint64).view(np.int64)
    flags = multihost_utils.process_allgather(np.concatenate(
        [np.asarray([n_digests, depth, n_counters, n_sets, int(uniform)],
                    np.int64), cks]))
    g_nd, g_depth, g_nc, g_ns = flags[:, :4].max(axis=0).tolist()
    # the uniform kernel is a STATIC program choice — legal only when
    # every controller's staging was uniform
    g_uniform = bool(flags[:, 4].min())
    nf = len(names)
    keyset_all = flags[:, 5:5 + nf]
    keyrow_all = flags[:, 5 + nf:5 + 2 * nf]
    # same key SET everywhere but different key->row assignment = silent
    # row misalignment (a registration-order divergence).  Differing key
    # sets pass: with O(1) gathered state per family, a shared-key row
    # conflict cannot be distinguished from benign one-sided keys, so
    # this is a best-effort tripwire — it catches the canonical ordering
    # bug outright, and catches an asymmetric-registration row conflict
    # as soon as GC (or registration) makes the key sets converge (at
    # which point the dictionaries genuinely ARE misaligned for the
    # shared keys).  The strict contract remains: shared keys must be
    # registered in the same order everywhere
    diverged = [
        name for i, name in enumerate(names)
        if (keyset_all[:, i] == keyset_all[0, i]).all()
        and not (keyrow_all[:, i] == keyrow_all[0, i]).all()]
    if diverged:
        raise RuntimeError(
            "lockstep violation: controllers hold the same "
            f"keys with DIFFERENT row assignments for famil"
            f"{'ies' if len(diverged) > 1 else 'y'} "
            f"{', '.join(diverged)} (process "
            f"{jax.process_index()} of "
            f"{jax.process_count()}).  All controllers must "
            "register shared keys in the same order "
            "(parallel/multihost.py lockstep contract); "
            "flushing with misaligned rows would silently "
            "merge unrelated timeseries")
    return g_nd, g_depth, g_nc, g_ns, g_uniform
