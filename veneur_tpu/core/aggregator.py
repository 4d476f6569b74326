"""MetricAggregator: ingest/import/flush over the batched arenas.

This is the TPU-native fusion of the reference's Worker
(`worker.go:348-459`: ProcessMetric / ImportMetric scope dispatch) and
flusher (`flusher.go:26-122,286-415`: tally + InterMetric generation with
the local/global flush duality).  Instead of N worker goroutines each
walking per-key sampler maps, one aggregator owns the arenas and every
flush evaluates all keys in a handful of batched XLA calls.

Flush duality (`flusher.go:57-74`):
  - a *local* instance emits histogram aggregates from local-sample
    scalars and NO percentiles for mixed-scope keys (those forward their
    digests to the global tier), but full percentiles for local-only keys;
  - a *global* instance emits percentiles (and digest-derived aggregates
    for global-scope keys), plus sets and global counters/gauges.

Concurrency: ingest threads append to host staging under `lock`; flush
holds the lock only to sync staging, snapshot the (immutable) device state
and host scalars, and reset — evaluation and InterMetric generation run on
the snapshot outside the lock, so ingest continues during flush exactly
like the reference's swap-maps-under-mutex (`worker.go:462-481`).
"""

from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.core import arena as arena_mod
from veneur_tpu.parallel import multihost, serving
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope, UDPMetric
from veneur_tpu.sketches import hll as hll_mod
from veneur_tpu.sketches import tdigest as td


@dataclass
class FlushResult:
    metrics: sm.MetricBatch = field(default_factory=sm.MetricBatch)
    forward: list[sm.ForwardMetric] = field(default_factory=list)
    processed: int = 0
    imported: int = 0
    # HLL estimate of distinct timeseries this interval, or None when
    # count_unique_timeseries is off (flusher.go:42-44)
    unique_ts: Optional[int] = None


class PendingFlush:
    """A dispatched-but-not-yet-emitted flush (see
    MetricAggregator.flush_dispatch): the snapshot is taken, the dense
    staging is resident on device and the program is launched — but
    nothing has waited on the device.  emit() performs the fetch and
    generates the InterMetric batch; it must be called exactly once.
    Between flush_dispatch() and emit() the caller may stage the next
    interval (ingest continues regardless): the snapshot is immutable
    and reset swapped in fresh device buffers, so an overlapping
    dispatch can never alias this flush's inputs."""

    __slots__ = ("_agg", "_snap", "_pend", "_res", "_is_local", "_now",
                 "_seg", "_done")

    def __init__(self, agg, snap, pend, res, is_local, now, seg):
        self._agg = agg
        self._snap = snap
        self._pend = pend
        self._res = res
        self._is_local = is_local
        self._now = now
        self._seg = seg
        self._done = False

    def emit(self) -> "FlushResult":
        if self._done:
            raise RuntimeError("PendingFlush.emit() called twice")
        self._done = True
        return self._agg._emit_pending(self._snap, self._pend, self._res,
                                       self._is_local, self._now,
                                       self._seg)


# The interval ledger: work done FOR the open interval by threads other
# than the flush thread (the import RPCs, the native drain's fold), kept
# in nanoseconds and counts.  Every update happens under the aggregator
# lock, and _snapshot_and_reset swaps the dict out under the same lock —
# exactly as `imported` is — so each RPC and each fold is accounted once,
# to the flush that closes its interval.  flush_dispatch writes the
# swapped-out ledger and the snapshot's own parts into
# last_flush_segments; LEDGER_SEGMENT_KEYS names those keys, whose outlet
# is the flush timeline row (and, traced, flush.seg.snapshot.* spans),
# not a self-metric: core/server.py keeps them out of its
# flush.segment.* loop.
_LEDGER_FIELDS = ("import_rpcs", "import_lock_wait_ns", "import_scan_ns",
                  "import_held_ns", "fold_calls", "fold_lines",
                  "fold_lock_wait_ns", "fold_ns",
                  # the part of fold_ns the drain spent on keys it did
                  # not know: registering the engine's new identities
                  # and resolving an id to its row through row_for (a
                  # birth, or a row found again after the idle GC or an
                  # intern clear) — ingest.NativeIngest._drain_apply
                  "key_birth_held_ns",
                  # the V2 streams' share of the import (sources/
                  # proxy.py send_metrics_v2; their scan, lock wait and
                  # hold are in the three above, with the V1 RPCs'):
                  # streams whose first chunk was imported in the
                  # interval (counted in import_rpcs too), messages
                  # received, the chunks they were imported in, and the
                  # handlers' wall time inside the request iterator
                  # (python-grpc's and the sender's share) and framing
                  # the chunks as MetricList bytes, outside the lock
                  "import_stream_rpcs", "import_stream_msgs",
                  "import_stream_chunks", "import_stream_recv_ns",
                  "import_stream_frame_ns",
                  # plain t-digests import_payload staged as arrays: the
                  # row came from the identity cache (no protobuf parse),
                  # or the record was its key's first sighting since
                  # the cache was last cleared (parsed, resolved,
                  # cached).  Records that keep _import_slow_pb are in
                  # neither.
                  "import_digest_hits", "import_digest_misses",
                  # every record whose row the V1 import asked the
                  # identity->row cache for, of all four families it
                  # serves (counters, gauges, sets, plain digests; both
                  # import_pb_batch and the scanned payload): the cache
                  # had the row, or the record was parsed and resolved
                  # through row_for and cached.  Records that keep
                  # _import_slow_pb, that were refused or failed, and
                  # every record of a guard-armed import are in neither.
                  # And whether the cut that closed the interval cleared
                  # the cache (0 or 1: a row was recycled, or the cache
                  # outgrew the keys it serves)
                  "import_row_hits", "import_row_misses",
                  "import_row_cache_clears",
                  # forwarded set sketches import_payload staged from
                  # the wire scan's columns, by wire form: sparse as
                  # (row, register, rank) triples, dense as a register
                  # row.  Sketches that keep _import_slow_pb (a legacy
                  # encoding, another precision) are in neither.
                  "set_import_sparse", "set_import_dense")
# what the set arena's lanes did over the interval and in its flush, on
# the timeline row and in /debug/vars (-> set_lanes): the register bytes
# the device holds (0: host registers), the rows the chip estimated, the
# bytes the flush uploaded for them (0 on a resident flush) and read
# back, and SetArena.LANE_STATS as set_*
SET_LEDGER_KEYS = ("set_resident_bytes", "set_rows_device",
                   "set_upload_bytes", "set_readback_bytes",
                   "set_scatter_points", "set_scatter_launches",
                   "set_merge_rows", "set_sync_s")
# what a flush's cut did with the staged points (arena._StagedPoints),
# summed over the histogram families: the points it handed to the
# flush, the bytes of accumulated points copied under the lock at the
# cut (0: the hand-off engaged; not 0: the snapshot's own sync() regrew
# a buffer or pre-reduced a hot row), and the buffers' doublings over
# the interval.  On the timeline row and in /debug/vars.
STAGED_LEDGER_KEYS = ("staged_points", "staged_cut_copy_bytes",
                      "staged_regrows")
# what the hot-key lane did over the interval and how the flush built its
# operand, on the timeline row and in /debug/vars (-> hot_lane):
# DigestArena.HOT_STATS as hot_* (rows pre-reduced, points into and out
# of the compress, its launches, the passes' hold of the aggregator lock
# summed over the interval's drain ticks and its cut), the bytes one
# compress launch moves (operands + results, from the tile's shape), and
# the unmeshed digest build: how many operands (tiers) it made and their
# padded value-matrix elements, rows x depth summed (`staged_points`
# over it is the build's fill); and of either digest build
# (DigestArena.BUILD_STATS): whether the one-pass kept-operand build made
# the flush's operand, and the bytes of operands and row-index arrays
# the build allocated anew (0 in a steady flush that engaged it)
HOT_LEDGER_KEYS = ("hot_keys", "hot_points_in", "hot_points_out",
                   "hot_compress_launches", "hot_compress_held_s",
                   "hot_compress_tile_bytes", "dense_tiers", "dense_elems",
                   "build_onepass", "build_fresh_bytes")
# a row's life over the interval the cut closed, summed over the arenas
# (_ArenaBase.births / .recycled / .grows, read at the cut after the
# idle GC and the eviction passes): rows handed to a new key, rows put
# back on a free list, doublings of an arena, and where that leaves the
# arenas — live keys and rows ever handed out.  On the timeline row and
# in /debug/vars (-> key_lifecycle); the deaths of the idle GC by family
# are columns_by_family["deaths"], tags of the columns.end span
KEY_LEDGER_KEYS = ("key_births", "key_deaths", "arena_grows",
                   "arena_rows_live", "arena_rows_hw")
# written by the native drain (ledger_intern_clear) only into the ledger
# of an interval in which it cleared the engine's intern table: the
# clears, the drain calls that made them (readers parked, table wiped)
# and the identities registered again for a row that was still live,
# from the clear to the cut
INTERN_LEDGER_KEYS = ("intern_clears", "intern_clear_s",
                      "intern_reregistered")
# snapshot_columns_s in parts (_snapshot_and_reset measures the first
# four where they happen, under the lock; the last is what is left): the
# import row cache's check against the rows the cut recycled (and its
# clearing, when one was), the arenas' snapshot_part() less their
# take_staged(), their reset_rows() and their end_interval(), and the
# rest — the unique-timeseries swap, the key fingerprints, the lane
# stats, the cardinality guard's and the cubes' end of interval
COLUMNS_PART_KEYS = ("snapshot_cache_s", "snapshot_cut_s",
                     "snapshot_reset_s", "snapshot_end_s",
                     "snapshot_rest_s")
LEDGER_SEGMENT_KEYS = frozenset(
    ["snapshot_lock_wait_s", "snapshot_sync_s", "snapshot_staged_s",
     "snapshot_columns_s", *COLUMNS_PART_KEYS]
    + [f[:-3] + "_s" if f.endswith("_ns") else f for f in _LEDGER_FIELDS])
# every key of last_flush_segments that core/server.py keeps out of that
# loop: the ledger's, and what the device programs (_dispatch_sets,
# _launch_meshed) say of themselves
ROW_ONLY_SEGMENT_KEYS = LEDGER_SEGMENT_KEYS | {
    *SET_LEDGER_KEYS, "set_device_s",
    # the meshed launch's own (_launch_meshed): the dense shape it ran
    # and the bytes its collectives move per device
    "device_rows", "device_depth", "collective_bytes",
    *STAGED_LEDGER_KEYS, *HOT_LEDGER_KEYS, *KEY_LEDGER_KEYS,
    *INTERN_LEDGER_KEYS}


def _new_ledger() -> dict:
    return dict.fromkeys(_LEDGER_FIELDS, 0)


# Ceiling for the logical [rows, depth, ccap] intermediate of one
# digest_export chunk (elements); see _emit_digests' forwarding branch.
_EXPORT_ELEM_BUDGET = 1 << 26

# A flush smaller than chunks * this many dense rows is not worth
# splitting for upload/evaluate overlap (dispatch overhead dominates).
_CHUNK_MIN_ROWS = 8192

# fewer touched set rows than this are estimated by numpy, on either
# side of the residency choice (see arena.SET_DEVICE_MIN_ROWS)
_SET_DEVICE_MIN_ROWS = arena_mod.SET_DEVICE_MIN_ROWS


class MetricAggregator:
    # Every arena, once, by attribute (which is also its section of the
    # crash checkpoint).  What a family is called elsewhere rides its
    # arena class: `family` (key fingerprints, keys_* segments) and, for
    # the histogram families, `ring` and `wire_field`.  A flush cuts
    # them in this order (the scalar families, then the 16 KiB-a-row
    # set registers, then the histogram columns).
    _FAMILIES = ("gauges", "status", "counters", "sets",
                 "digests", "moments", "compactors")
    _HISTO_FAMILIES = _FAMILIES[4:]
    # the families whose touched rows make a flush dispatch (a gauge or
    # a status check alone emits from the snapshot)
    _DISPATCHED = _FAMILIES[2:]
    # the families that forward wire VECTORS and evaluate in a program
    # of their own: (attribute, flush-segment prefix, fetched-quantiles
    # key)
    _VECTOR_FAMILIES = (("moments", "m", "m_qs"),
                        ("compactors", "c", "comp_qs"))

    def _arenas(self, names=_FAMILIES) -> list:
        return [(name, getattr(self, name)) for name in names]

    def __init__(self,
                 percentiles: Optional[list[float]] = None,
                 aggregates: sm.HistogramAggregates = sm.HistogramAggregates(),
                 compression: float = td.DEFAULT_COMPRESSION,
                 set_precision: int = hll_mod.DEFAULT_PRECISION,
                 count_unique_timeseries: bool = False,
                 mesh=None, ingest_lanes: Optional[int] = None,
                 is_local: bool = True, initial_capacity: int = 0,
                 set_initial_capacity: int = 0,
                 hll_legacy_migration: bool = False,
                 digest_float64: bool = False,
                 digest_bf16_staging: bool = False,
                 flush_upload_chunks: int = 2,
                 flush_presharded_staging: bool = True,
                 flush_resident_arenas: bool = False,
                 flush_delta_chunk_keys: int = 0,
                 flush_delta_nbuf: int = 2,
                 resident_device_assembly: Optional[bool] = None,
                 cardinality_key_budget: int = 0,
                 cardinality_tenant_tag: str = "tenant",
                 cardinality_seed: int = 0,
                 sketch_family_default: str = "tdigest",
                 sketch_family_rules: Optional[list] = None,
                 sketch_moments_k: int = 0,
                 sketch_compactor_cap: int = 0,
                 sketch_compactor_levels: int = 0,
                 sketch_compactor_seed: int = 0,
                 cardinality_rollup_family: str = "tdigest",
                 query_window_slots: int = 0,
                 query_slot_seconds: float = 0.0,
                 cube_dimensions: Optional[list] = None,
                 cube_group_budget: int = 0,
                 cube_seed: int = 0,
                 retention_tiers: Optional[list] = None,
                 retention_dir: str = "",
                 retention_max_bytes: int = 256 * 1024 * 1024,
                 retention_max_age_s: float = 0.0,
                 retention_statsd_fn=None):
        self.percentiles = percentiles if percentiles is not None else [0.5]
        self.aggregates = aggregates
        self.lock = threading.Lock()
        self.mesh = mesh
        if mesh is not None and is_local and jax.process_count() > 1:
            # fail at startup, not at the first flush tick: the
            # multi-process mesh serves the GLOBAL tier only (a local/
            # forwarding tier is a single-process server; the gRPC
            # forward/import edge is the cross-host transport)
            raise ValueError(
                "multi-process meshed serving supports the global tier "
                "only: configure is_local=False (forwarding tiers run "
                "single-process; see parallel/multihost.py)")
        # pre-size for expected cardinality (arena growth copies device
        # tensors); rounded up to a power of two.  SetArena's per-row cost
        # is R_s * 2^precision register BYTES (16 KiB/lane at p=14, vs
        # 8 B for a counter), so it has its own knob
        # (set_arena_initial_capacity) for fleets with genuinely large set
        # cardinality; by default it follows initial_capacity only up to
        # 8192 rows (128 MiB/lane) so a digest-sized knob cannot silently
        # pin gigabytes of device registers — sets grow on demand past it.
        kw = {}
        set_kw = {}
        if initial_capacity > arena_mod._INITIAL_CAPACITY:
            # enlarge-only: a small value never shrinks below the arena
            # default (that would reintroduce the growth copies)
            cap = 1 << (initial_capacity - 1).bit_length()
            kw = {"capacity": cap}
            set_kw = {"capacity": min(cap, 8192)}
        if set_initial_capacity > arena_mod._INITIAL_CAPACITY:
            set_kw = {"capacity":
                      1 << (set_initial_capacity - 1).bit_length()}
        if digest_float64:
            # f64 digest evaluation (merging_digest.go:23-40 float64
            # semantics): values past 2^24 keep integer exactness.
            # Device f64 is emulated (slower) and the meshed program is
            # f32-native, so the option is single-device only; x64 must
            # be on before any jit traces.
            if mesh is not None:
                raise ValueError(
                    "digest_float64 is unsupported with a device mesh; "
                    "run f64 evaluation on an unmeshed tier")
            jax.config.update("jax_enable_x64", True)
        self.digest_float64 = digest_float64
        if digest_bf16_staging and digest_float64:
            raise ValueError(
                "digest_bf16_staging contradicts digest_float64 "
                "(half- vs double-precision staging); drop one")
        # device-resident arenas + delta flush (ROADMAP #2): unmeshed
        # tiers keep sketch registers in HBM across intervals and stream
        # staged deltas during the interval; meshed tiers already hold
        # set/counter registers device-resident, so the gate is a no-op
        # there (the digest dense build stays the sharded all_to_all)
        self.flush_resident = bool(flush_resident_arenas)
        resident_unmeshed = self.flush_resident and mesh is None
        # the set registers are resident also where the configuration
        # pre-sized their arena: set_arena_initial_capacity says the
        # deployment's set keys are many, and at 16 KiB a row that many
        # registers cannot be copied under the lock, uploaded and read
        # back every flush (50,000 keys: 0.8 GB each way).  An arena at
        # its default size keeps host registers.
        sets_resident = mesh is None and (
            self.flush_resident
            or set_initial_capacity > arena_mod._INITIAL_CAPACITY)
        # pow2-floored delta granularity, shared by both delta modes
        # (dense ROWS per upload chunk when chunking host-staged builds,
        # staged POINTS per streamed chunk when resident); 0 = defaults
        self._delta_chunk = 1 << max(0, int(
            flush_delta_chunk_keys).bit_length() - 1) \
            if flush_delta_chunk_keys > 0 else 0
        self._delta_nbuf = max(2, int(flush_delta_nbuf))
        self.digests = arena_mod.DigestArena(
            compression=compression, mesh=mesh, n_lanes=ingest_lanes,
            eval_dtype=np.float64 if digest_float64 else np.float32,
            bf16_staging=digest_bf16_staging,
            presharded_staging=flush_presharded_staging,
            resident=resident_unmeshed,
            resident_chunk_points=self._delta_chunk or 32768,
            resident_device_assembly=resident_device_assembly,
            **kw)
        # sketch-family dispatch (ROADMAP #3): per-key choice of
        # tdigest vs moments vs compactor for histogram/timer samples.
        # Rules match at ingest (first hit wins: name glob or tenant
        # tag); imports route by the PAYLOAD (a moments vector or a
        # compactor ladder merges into ITS arena whatever the local
        # rules say — wire self-description beats configuration, so a
        # rules mismatch across tiers degrades to per-tier family
        # choice instead of corrupting any sketch).  The moments and
        # compactor arenas always exist (imports may deliver their
        # payloads regardless of local rules); the dispatch fast path
        # is one bool when no rule can ever fire.
        _FAMS = ("tdigest", "moments", "compactor")
        for fam in (sketch_family_default, cardinality_rollup_family):
            if fam not in _FAMS:
                raise ValueError(
                    f"unknown sketch family {fam!r} "
                    "(tdigest | moments | compactor)")
        self._fam_default = sketch_family_default
        self._rollup_family = cardinality_rollup_family
        self._fam_rules = []
        fams_in_play = {sketch_family_default}
        if cardinality_key_budget > 0:
            fams_in_play.add(cardinality_rollup_family)
        for r in (sketch_family_rules or []):
            fam = r.get("family", "moments")
            if fam not in _FAMS:
                raise ValueError(
                    f"unknown sketch family {fam!r} in rule {r!r}")
            if not (r.get("match") or r.get("tenant")):
                raise ValueError(
                    f"sketch_family rule needs match: or tenant:, "
                    f"got {r!r}")
            self._fam_rules.append((r.get("match"), r.get("tenant"),
                                    fam))
            fams_in_play.add(fam)
        self.family_dispatch = bool(
            self._fam_rules or self._fam_default != "tdigest"
            or (self._rollup_family != "tdigest"
                and cardinality_key_budget > 0))
        if mesh is not None and "compactor" in fams_in_play:
            raise ValueError(
                "the compactor sketch family is unsupported with a "
                "device mesh (its fold/flush programs are "
                "single-device); drop one")
        if (self.family_dispatch and mesh is not None
                and jax.process_count() > 1):
            # single-process meshes shard the moments solver over the
            # key axis (ops/moments_eval.py); the multi-process
            # lockstep gather covers the digest program only
            raise ValueError(
                "sketch_family_* dispatch is unsupported with a "
                "multi-process mesh; drop one")
        self._fam_cache: dict = {}
        # pre-size only when the dispatch can actually route keys here
        # (the ivec plane is f64 and capacity-sized)
        self.moments = arena_mod.MomentsArena(
            k=sketch_moments_k, mesh=None,
            resident=resident_unmeshed,
            resident_chunk_points=self._delta_chunk or 32768,
            resident_device_assembly=resident_device_assembly,
            **(kw if self.family_dispatch else {}))
        from veneur_tpu.ops import moments_eval
        # the solver is row-local, so a (single-process) mesh shards it
        # over the key axis — bit-parity with the unmeshed program is
        # test-pinned (tests/test_moments.py)
        self.moments_fn = moments_eval.make_moments_flush(
            self.moments.k,
            mesh=mesh if jax.process_count() == 1 else None)
        self.last_moments_resid = 0.0
        # relative-error compactor family (ROADMAP #4): always exists —
        # payload-routed imports can land ladders on any tier — but
        # pre-sizes only when dispatch can route raw samples here
        self.compactors = arena_mod.CompactorArena(
            cap=sketch_compactor_cap, levels=sketch_compactor_levels,
            seed=sketch_compactor_seed, mesh=None,
            **(kw if self.family_dispatch else {}))
        from veneur_tpu.ops import compactor_eval
        self.compactor_fn = compactor_eval.make_compactor_flush(
            self.compactors.cc_cap, self.compactors.cc_levels)
        self.sets = arena_mod.SetArena(precision=set_precision, mesh=mesh,
                                       legacy_migration=hll_legacy_migration,
                                       resident=sets_resident,
                                       **set_kw)
        self.counters = arena_mod.CounterArena(mesh=mesh, **kw)
        self.gauges = arena_mod.GaugeArena(**kw)
        self.status = arena_mod.StatusArena(**kw)
        # per-tenant key budget + tail rollup (core/cardinality.py);
        # None = defense off, zero hot-path cost.  Applies at the INGEST
        # edge (process path + native drain): imports arrive pre-rolled
        # from the local tier, whose rollup series are ordinary mergeable
        # keys here.
        from veneur_tpu.core.cardinality import CardinalityGuard
        self.cardinality = (
            CardinalityGuard(cardinality_key_budget,
                             tenant_tag=cardinality_tenant_tag,
                             seed=cardinality_seed)
            if cardinality_key_budget > 0 else None)
        # group-by sketch cubes (veneur_tpu/cubes/): config-declared
        # dimensions mirror each histogram/timer sample into per-group
        # rollup rows — ordinary mergeable arena keys, so they flush,
        # forward, and window through the existing machinery.  Ingest
        # edge only: forwarded cube rows come back through the import
        # path as ordinary wire keys (re-materializing there would
        # double-count).
        self.cubes = None
        if cube_dimensions and cube_group_budget > 0:
            from veneur_tpu.cubes import CubeMaintainer, parse_dimensions
            self.cubes = CubeMaintainer(
                parse_dimensions(cube_dimensions), cube_group_budget,
                seed=cube_seed)
        self.processed = 0
        self.imported = 0
        self._ledger = _new_ledger()    # the interval ledger (see above)
        # the arenas' births / recycled / grows at the last cut
        self._key_life_seen = (0, 0, 0)
        # the calling thread's last import RPC as (scan, lock wait, held)
        # nanoseconds, for the global.import span's tags
        self._import_tls = threading.local()
        # V1 import identity->row cache.  An entry is good for as long
        # as its row holds its key, and only a recycled row (the idle GC
        # in end_interval, release_keys) can break that: the cut clears
        # the whole cache when the arenas' `recycled` totals moved
        # (_check_import_row_cache) and leaves it alone otherwise, so a
        # steady fleet resolves each key once, not once an interval.  A
        # row the cache answers for must be marked touched by the
        # import itself (row_for is not called on a hit).
        self._import_row_cache: dict = {}
        self._import_recycled_seen = 0
        self._native_import = None   # False once the engine is ruled out
        self.count_unique_timeseries = count_unique_timeseries
        self.unique_ts = hll_mod.HLLSketch() if count_unique_timeseries else None
        self.is_local = is_local
        # ONE device program evaluates the flush (parallel/serving.py):
        # mesh-less it is the digest sorted-eval, beside it the set
        # estimate over the snapshot's register copy (_dispatch_sets;
        # counters/unique-ts resolve on host); meshed it is the shard_map'd
        # full-family program (all_gather over sample depth, set pmax,
        # counter psum, unique-ts union).
        self.flush_fn = serving.make_serving_flush(mesh)
        # compile-churn observability: every new (keys, depth) pow2
        # bucket traces+compiles a fresh program; the server reports the
        # counters as self-metrics and the flush watchdog treats an
        # in-progress first-bucket compile as progress, not a hang
        # per-flush measured segments (snapshot/build/dispatch/device/
        # emit seconds + upload/readback bytes): the e2e decomposition
        # the benchmark and self-metrics report
        self.last_flush_segments: dict = {}
        # rounded DOWN to a power of two: dense row counts are pow2, so
        # only pow2 chunk counts tile them exactly (a 3-way split would
        # silently drop the tail rows)
        self._upload_chunks = 1 << max(0, int(
            flush_upload_chunks).bit_length() - 1)
        self._compiled_shapes: set = set()
        self._compiling_shapes: set = set()   # claimed by an active guard
        self._compile_lock = threading.Lock()
        self._compiles_active = 0
        self.compile_events = 0
        self.compile_seconds_total = 0.0
        self.compile_in_progress = threading.Event()
        # lane-kernel launches (set scatter / merge / reset on device
        # lanes) are compile events like the flush program's
        self.sets.compile_guard = functools.partial(self._CompileGuard,
                                                    self)
        # ... and so is the hot-key compress a drain tick launches
        self.digests.compile_guard = self.sets.compile_guard
        self._uts_m = self.unique_ts.m if self.unique_ts is not None \
            else 1 << hll_mod.DEFAULT_PRECISION
        self._pct_arr = jnp.asarray([0.5] + list(self.percentiles),
                                    jnp.float32)
        # live query plane (veneur_tpu/query/): bounded window rings of
        # per-interval mergeable sub-sketches next to each histogram
        # arena's live state.  Rotation rides the flush cut (the slot
        # IS the cut's immutable snapshot part — zero copies, no new
        # lock on the ingest path); reads fuse covered slots on demand.
        # NOT checkpointed: a restore cold-starts the ring (documented
        # cold-ring-on-restore contract, tests/test_query.py).
        self.query_rings = None
        if query_window_slots > 0:
            from veneur_tpu.query.rings import WindowRing
            self.query_rings = {
                ar.ring: WindowRing(query_window_slots, query_slot_seconds)
                for _, ar in self._arenas(self._HISTO_FAMILIES)}
        # multi-resolution retention (veneur_tpu/retention/): the same
        # flush-cut snapshot parts the window ring holds also compact
        # UPWARD into coarser in-memory tiers (minute/hour/day rings of
        # mergeable buckets); buckets evicted from the coarsest tier
        # spill to disk in the spool's CRC-framed segment format under
        # a byte/age budget.  Requires the query plane (the range
        # planner fuses ring slots and tier buckets behind one
        # ?since=&step= surface) — config.apply_defaults enforces it.
        self.retention = None
        if retention_tiers:
            from veneur_tpu.retention import (RetentionTimeline,
                                              TierSegmentStore)
            store = None
            if retention_dir:
                store = TierSegmentStore(retention_dir,
                                         max_bytes=retention_max_bytes,
                                         max_age_s=retention_max_age_s)
            self.retention = RetentionTimeline(
                retention_tiers, store=store, compression=compression,
                statsd_fn=retention_statsd_fn)

    # -- ingest (ProcessMetric, worker.go:348-396) -------------------------

    def process_metric(self, m: UDPMetric) -> None:
        with self.lock:
            self._process_locked(m)

    def process_batch(self, ms: list[UDPMetric]) -> None:
        with self.lock:
            for m in ms:
                self._process_locked(m)

    def _card_resolve(self, key, scope, tags, n: int = 1):
        """Cardinality defense at the ingest edge: under-budget (or
        untenanted) keys pass through; an over-budget tenant's tail
        rewrites to its reserved rollup identity
        (core/cardinality.py)."""
        g = self.cardinality
        if g is None:
            return key, scope, tags
        rolled = g.resolve(key, scope, tags, n)
        return (key, scope, tags) if rolled is None else rolled

    # -- sketch-family dispatch (ROADMAP #3) -------------------------------

    _FAM_CACHE_CAP = 65536

    def _family_of(self, key: MetricKey, tags) -> str:
        """Family choice for one histogram/timer key ("tdigest" |
        "moments" | "compactor"): rollup identities follow
        cardinality_rollup_family, then the first matching rule (name
        glob / tenant tag), then the default.  Memoized on the key
        identity (bounded; a cardinality storm of fresh identities
        falls back to uncached evaluation instead of growing the
        memo)."""
        ck = (key.name, key.joined_tags)
        hit = self._fam_cache.get(ck)
        if hit is not None:
            return hit
        from veneur_tpu.core.cardinality import ROLLUP_TAG
        if ROLLUP_TAG in tags:
            fam = self._rollup_family
        else:
            fam = self._fam_default
            import fnmatch
            for pattern, tenant, rfam in self._fam_rules:
                if pattern is not None:
                    if fnmatch.fnmatchcase(key.name, pattern):
                        fam = rfam
                        break
                elif tenant is not None:
                    if f"tenant:{tenant}" in tags:
                        fam = rfam
                        break
        if len(self._fam_cache) < self._FAM_CACHE_CAP:
            self._fam_cache[ck] = fam
        return fam

    def _family_is_moments(self, key: MetricKey, tags) -> bool:
        return self._family_of(key, tags) == "moments"

    def _histo_arena(self, key: MetricKey, tags):
        """The arena a histogram/timer key's RAW SAMPLES land in (call
        after _card_resolve, so rollup identities route by the rollup
        family).  Imports do NOT come through here — a wire payload is
        self-describing (digest centroids vs moments vector vs
        compactor ladder)."""
        if not self.family_dispatch:
            return self.digests
        fam = self._family_of(key, tags)
        if fam == "moments":
            return self.moments
        if fam == "compactor":
            return self.compactors
        return self.digests

    def _process_locked(self, m: UDPMetric) -> None:
        self.processed += 1
        if self.unique_ts is not None:
            self._sample_timeseries(m)
        t = m.type
        if t == sm.TYPE_COUNTER:
            scope = (MetricScope.GLOBAL_ONLY
                     if m.scope == MetricScope.GLOBAL_ONLY
                     else MetricScope.MIXED)
            key, scope, tags = self._card_resolve(m.key, scope, m.tags)
            row = self.counters.row_for(key, scope, tags)
            self.counters.sample(row, m.value, m.sample_rate)
        elif t == sm.TYPE_GAUGE:
            scope = (MetricScope.GLOBAL_ONLY
                     if m.scope == MetricScope.GLOBAL_ONLY
                     else MetricScope.MIXED)
            key, scope, tags = self._card_resolve(m.key, scope, m.tags)
            row = self.gauges.row_for(key, scope, tags)
            self.gauges.sample(row, m.value)
        elif t in (sm.TYPE_HISTOGRAM, sm.TYPE_TIMER):
            key, scope, tags = self._card_resolve(m.key, m.scope, m.tags)
            arena = self._histo_arena(key, tags)
            row = arena.row_for(key, scope, tags)
            arena.sample(row, m.value, m.sample_rate)
            if self.cubes is not None:
                # cube dimension rollups: the sample ALSO lands in each
                # matching group's row (family dispatch by the cube
                # key, so like groups merge family-coherently across
                # tiers); over-budget groups land in the accounted
                # veneur.cube.other row instead — counted, not lost
                for ck, cs, ctags in self.cubes.rollups(key, scope,
                                                        tags):
                    carena = self._histo_arena(ck, ctags)
                    crow = carena.row_for(ck, cs, ctags)
                    carena.sample(crow, m.value, m.sample_rate)
        elif t == sm.TYPE_SET:
            scope = (MetricScope.LOCAL_ONLY
                     if m.scope == MetricScope.LOCAL_ONLY
                     else MetricScope.MIXED)
            key, scope, tags = self._card_resolve(m.key, scope, m.tags)
            row = self.sets.row_for(key, scope, tags)
            self.sets.sample(row, str(m.value))
        elif t == sm.TYPE_STATUS:
            row = self.status.row_for(m.key, MetricScope.LOCAL_ONLY, m.tags)
            self.status.sample(row, float(m.value), m.message, m.hostname)
        # unknown types are silently skipped, as in worker.go:393-395

    def _sample_timeseries(self, m: UDPMetric) -> None:
        """Unique-timeseries HLL counting (worker.go:301-345): sample iff
        the series is finalized on this instance — always on a global
        instance (worker.go:310-314), else only non-forwarded types."""
        if not self.is_local:
            self.unique_ts.insert(m.digest.to_bytes(8, "little"))
            return
        local_types = {
            sm.TYPE_COUNTER: m.scope != MetricScope.GLOBAL_ONLY,
            sm.TYPE_GAUGE: m.scope != MetricScope.GLOBAL_ONLY,
            sm.TYPE_HISTOGRAM: m.scope == MetricScope.LOCAL_ONLY,
            sm.TYPE_SET: m.scope == MetricScope.LOCAL_ONLY,
            sm.TYPE_TIMER: m.scope == MetricScope.LOCAL_ONLY,
            sm.TYPE_STATUS: True,
        }
        if local_types.get(m.type, False):
            self.unique_ts.insert(m.digest.to_bytes(8, "little"))

    # -- import (ImportMetric, worker.go:402-459) --------------------------

    def import_metric(self, fm: sm.ForwardMetric) -> None:
        scope = MetricScope(fm.scope)
        if fm.kind in (sm.TYPE_COUNTER, sm.TYPE_GAUGE):
            scope = MetricScope.GLOBAL_ONLY
        if scope == MetricScope.LOCAL_ONLY:
            raise ValueError("gRPC import does not accept local metrics")
        key = MetricKey(fm.name, fm.kind, ",".join(sorted(fm.tags)))
        with self.lock:
            self.imported += 1
            if fm.kind == sm.TYPE_COUNTER:
                key, cls, tags = self._card_resolve(
                    key, MetricScope.GLOBAL_ONLY, fm.tags)
                row = self.counters.row_for(key, cls, tags)
                self.counters.merge(row, fm.counter_value)
            elif fm.kind == sm.TYPE_GAUGE:
                key, cls, tags = self._card_resolve(
                    key, MetricScope.GLOBAL_ONLY, fm.tags)
                row = self.gauges.row_for(key, cls, tags)
                self.gauges.merge(row, fm.gauge_value)
            elif fm.kind == sm.TYPE_SET:
                key, cls, tags = self._card_resolve(
                    key, MetricScope.MIXED, fm.tags)
                row = self.sets.row_for(key, cls, tags)
                self.sets.merge(row, fm.hll)
            elif fm.kind in (sm.TYPE_HISTOGRAM, sm.TYPE_TIMER):
                cls = (MetricScope.GLOBAL_ONLY
                       if scope == MetricScope.GLOBAL_ONLY
                       else MetricScope.MIXED)
                key, cls, tags = self._card_resolve(key, cls, fm.tags)
                if fm.moments is not None:
                    # payload self-description wins: a moments vector
                    # merges exactly into the moments arena whatever
                    # this tier's own dispatch rules say
                    row = self.moments.row_for(key, cls, tags)
                    # vnlint: disable=blocking-propagation (the
                    #   flagged asarray converts the WIRE vector — a
                    #   host list off the protobuf — never a device
                    #   array; merge_moments is pure host numpy)
                    self.moments.merge_moments(row, fm.moments)
                elif fm.compactor is not None:
                    # same payload-routing contract for the compactor
                    # family: the ladder merges by concatenate-then-
                    # compact with the coin schedule continued from the
                    # summed counters (deterministic, order-free)
                    row = self.compactors.row_for(key, cls, tags)
                    # vnlint: disable=blocking-propagation (wire
                    #   vector off the protobuf; host numpy merge)
                    self.compactors.merge_compactor(row, fm.compactor)
                else:
                    row = self.digests.row_for(key, cls, tags)
                    self.digests.merge_digest(
                        row, fm.digest_means or [],
                        fm.digest_weights or [],
                        fm.digest_min, fm.digest_max, fm.digest_rsum)
            else:
                raise ValueError(f"unknown metric kind {fm.kind!r}")

    # value-oneof field -> the wire `type` values it may legally carry
    # (metricpb/metric.proto).  The metric family is dispatched from the
    # ONEOF (it names the payload actually present); a wire-legal Metric
    # whose `type` disagrees with its oneof — e.g. type=Timer carrying a
    # CounterValue — is REJECTED (counted in `failed`) instead of being
    # silently landed in either family.  The legacy per-metric path
    # (forward/convert.from_pb) derived kind from `type` and would have
    # merged the counter value into a digest row; neither behavior is
    # defensible for such senders, so the batch paths make the mismatch
    # loud and contractual.
    _ONEOF_LEGAL_TYPES = {
        "counter": (0,),        # metric_pb2.Counter
        "gauge": (1,),          # metric_pb2.Gauge
        "set": (3,),            # metric_pb2.Set
        "histogram": (2, 4),    # metric_pb2.Histogram / Timer
    }

    def import_pb_batch(self, pbs, t_call: Optional[int] = None,
                        stream=None) -> tuple[int, int]:
        """Batched V1 import: ONE lock for the whole MetricList, direct
        protobuf field access, an identity->row cache (kept across
        flushes; the cut clears it when a row was recycled:
        _check_import_row_cache), and vectorized counter/gauge merges
        (merge_batch marks the rows touched, a cache hit's included) —
        the per-metric dataclass conversion, key construction, and
        numpy scalar stores of import_metric are the global tier's V1
        inbound bottleneck at fleet rates.  Scope/nil/local semantics match import_metric;
        metrics whose `type` field contradicts their value oneof are
        rejected (see _ONEOF_LEGAL_TYPES — the legacy convert.from_pb
        path instead trusted `type` and mis-filed the payload).
        Returns (imported, failed).  `t_call` (perf_counter_ns) is when
        the RPC's import began, for the interval ledger: import_payload
        hands its own over so the protobuf parse counts as scan time,
        and the V2 stream's chunk (`stream`) it was called for."""
        from veneur_tpu.protocol import metric_pb2

        if t_call is None:
            t_call = time.perf_counter_ns()
        ok = failed = 0
        counters, gauges, sets, digests = (
            self.counters, self.gauges, self.sets, self.digests)
        cache = self._import_row_cache
        legal = self._ONEOF_LEGAL_TYPES
        c_rows: list = []
        c_vals: list = []
        g_rows: list = []
        g_vals: list = []
        misses = 0
        t_wait = time.perf_counter_ns()
        with self.lock:
            t_held = time.perf_counter_ns()
            for pb in pbs:
                try:
                    which = pb.WhichOneof("value")
                    if which is not None and pb.type not in legal[which]:
                        raise ValueError(
                            f"type/value mismatch: type={pb.type} "
                            f"carrying {which}")
                    if which == "counter":
                        # guard armed: no identity cache at all — every
                        # record must pass through resolve() for touch
                        # accounting, and caching raw identities during
                        # a storm would itself be the unbounded growth
                        # the guard bounds
                        ck = ((pb.name, tuple(pb.tags), 0)
                              if self.cardinality is None else None)
                        row = cache.get(ck) if ck is not None else None
                        if row is None:
                            tags = list(pb.tags)
                            key, cls, tags = self._card_resolve(
                                MetricKey(pb.name, sm.TYPE_COUNTER,
                                          ",".join(sorted(tags))),
                                MetricScope.GLOBAL_ONLY, tags)
                            row = counters.row_for(key, cls, tags)
                            if ck is not None:
                                cache[ck] = row
                                misses += 1
                        c_rows.append(row)
                        c_vals.append(pb.counter.value)
                    elif which == "gauge":
                        ck = ((pb.name, tuple(pb.tags), 1)
                              if self.cardinality is None else None)
                        row = cache.get(ck) if ck is not None else None
                        if row is None:
                            tags = list(pb.tags)
                            key, cls, tags = self._card_resolve(
                                MetricKey(pb.name, sm.TYPE_GAUGE,
                                          ",".join(sorted(tags))),
                                MetricScope.GLOBAL_ONLY, tags)
                            row = gauges.row_for(key, cls, tags)
                            if ck is not None:
                                cache[ck] = row
                                misses += 1
                        g_rows.append(row)
                        g_vals.append(pb.gauge.value)
                    elif which in ("set", "histogram"):
                        # vnlint: disable=blocking-propagation (the
                        #   moments branch's asarray converts wire
                        #   vectors — host lists, no device wait)
                        self._import_slow_pb(pb, which)
                    else:
                        raise ValueError("nil or unknown value")
                    self.imported += 1
                    ok += 1
                except Exception:
                    failed += 1
            if c_rows:
                counters.merge_batch(np.asarray(c_rows, np.int64),
                                     np.asarray(c_vals, np.float64))
            if g_rows:
                gauges.merge_batch(np.asarray(g_rows, np.int64),
                                   np.asarray(g_vals, np.float64))
            if self.cardinality is None:
                self._ledger["import_row_hits"] += (
                    len(c_rows) + len(g_rows) - misses)
                self._ledger["import_row_misses"] += misses
            self._ledger_import(t_call, t_wait, t_held, stream)
        return ok, failed

    def _ledger_import(self, t_call: int, t_wait: int, t_held: int,
                       stream=None) -> None:
        """Account one batch import to the open interval
        (perf_counter_ns marks: the import began, it started waiting
        for the lock, it held the lock): a V1 RPC, or one chunk of a
        V2 stream (`stream`: its sources.proxy.StreamChunk) — a stream
        is one RPC, counted with its first chunk.  Call under
        self.lock, last."""
        held = time.perf_counter_ns() - t_held
        led = self._ledger
        if stream is None:
            led["import_rpcs"] += 1
        else:
            led["import_rpcs"] += stream.first
            led["import_stream_rpcs"] += stream.first
            led["import_stream_chunks"] += 1
            led["import_stream_msgs"] += len(stream.messages)
            led["import_stream_recv_ns"] += stream.recv_ns
            led["import_stream_frame_ns"] += stream.frame_ns
        led["import_scan_ns"] += t_wait - t_call
        led["import_lock_wait_ns"] += t_held - t_wait
        led["import_held_ns"] += held
        self._import_tls.timing = (t_wait - t_call, t_held - t_wait, held)

    def take_import_timing(self) -> Optional[tuple]:
        """(scan, lock wait, held) nanoseconds of the batch import this
        thread just made, once; None when it made none since the last
        take (import_metric, the per-metric path, is not timed)."""
        timing = getattr(self._import_tls, "timing", None)
        self._import_tls.timing = None
        return timing

    def ledger_fold(self, lines: int, t_wait: int, t_held: int,
                    birth_ns: int = 0) -> None:
        """Account one fold of a native drain into the arenas (ingest.
        NativeIngest._drain_apply) to the open interval; `birth_ns` is
        the part of it spent on keys the drain did not know.  Call
        under self.lock, last."""
        led = self._ledger
        led["fold_calls"] += 1
        led["fold_lines"] += lines
        led["fold_lock_wait_ns"] += t_held - t_wait
        led["fold_ns"] += time.perf_counter_ns() - t_held
        led["key_birth_held_ns"] += birth_ns

    def ledger_intern_clear(self, clear_ns: int) -> None:
        """Account one clear of the native intern table to the open
        interval (INTERN_LEDGER_KEYS; only such an interval's ledger
        holds them).  Call under self.lock."""
        led = self._ledger
        led["intern_clears"] = led.get("intern_clears", 0) + 1
        led["intern_clear_ns"] = led.get("intern_clear_ns", 0) + clear_ns
        led.setdefault("intern_reregistered", 0)

    def _import_histo_identity(self, pb):
        """(key, class, tags) a forwarded histogram / timer record
        merges under, after the cardinality guard."""
        from veneur_tpu.protocol import metric_pb2

        tags = list(pb.tags)
        kind = (sm.TYPE_TIMER if pb.type == metric_pb2.Timer
                else sm.TYPE_HISTOGRAM)
        cls = (MetricScope.GLOBAL_ONLY if pb.scope == metric_pb2.Global
               else MetricScope.MIXED)
        return self._card_resolve(
            MetricKey(pb.name, kind, ",".join(sorted(tags))), cls, tags)

    def _import_slow_pb(self, pb, which: str) -> None:
        """Set/histogram import body (sketch merges; call under
        self.lock) — shared by the batch and native-scan paths."""
        from veneur_tpu.protocol import metric_pb2

        if pb.scope == metric_pb2.Local:
            raise ValueError("gRPC import does not accept local metrics")
        if pb.type not in self._ONEOF_LEGAL_TYPES[which]:
            raise ValueError(
                f"type/value mismatch: type={pb.type} carrying {which}")
        if which == "set":
            tags = list(pb.tags)
            key, cls, tags = self._card_resolve(
                MetricKey(pb.name, sm.TYPE_SET, ",".join(sorted(tags))),
                MetricScope.MIXED, tags)
            row = self.sets.row_for(key, cls, tags)
            self.sets.merge(row, pb.set.hyper_log_log)
            return
        key, cls, tags = self._import_histo_identity(pb)
        dig = pb.histogram.t_digest
        if dig.compression <= -1024:
            # compactor-family wire marker (forward/convert.py): the
            # centroid means ARE the f64 ladder vector
            row = self.compactors.row_for(key, cls, tags)
            self.compactors.merge_compactor(
                row, [c.mean for c in dig.main_centroids])
            return
        if dig.compression < 0:
            # moments-family wire marker (forward/convert.py): the
            # centroid means ARE the f64 moments vector
            row = self.moments.row_for(key, cls, tags)
            self.moments.merge_moments(
                row, [c.mean for c in dig.main_centroids])
            return
        row = self.digests.row_for(key, cls, tags)
        self.digests.merge_digest(
            row,
            [c.mean for c in dig.main_centroids],
            [c.weight for c in dig.main_centroids],
            dig.min, dig.max, dig.reciprocalSum)

    def import_payload(self, payload: bytes, stream=None
                       ) -> tuple[int, int]:
        """Batch import from RAW MetricList bytes — a V1 RPC's request,
        or a chunk of a V2 stream framed as one (`stream`: its
        sources.proxy.StreamChunk, for the ledger and for the fallback
        below): the native scanner
        (ingest.import_scan) extracts identity hashes, values and every
        plain t-digest's centroids in C++, so python does one dict
        lookup per metric, one vectorized merge per family and ONE
        staging call for the payload's digests
        (DigestArena.merge_digest_batch) and one for its set sketches
        (_stage_scanned_sets: sparse ones as decoded triples).  What
        the wire says decides per record: the moments / compactor
        markers (compression < 0), a set sketch the scan did not read
        and a key the row cache does not know parse individually via
        their byte ranges.  Falls back to
        import_pb_batch when the native engine is unavailable, the
        cardinality guard is armed, or the scan rejects the payload: a
        V1 payload is then parsed whole (one malformed record fails the
        RPC), a stream's chunk message by message — its bad message
        fails alone and is counted."""
        t_call = time.perf_counter_ns()
        scan = None
        # the native wire scan never materializes tags, which the
        # per-tenant budget classifies on — with the guard armed on
        # this (import) edge, every record takes the parsed path so
        # locals-direct-to-global fleets get the same defense
        if self._native_import is not False and self.cardinality is None:
            try:
                from veneur_tpu import ingest as ingest_mod
                ingest_mod.load_library()
                scan = ingest_mod.import_scan(payload)
            # vnlint: disable=silent-loss (native-scan unavailability is
            #   a FALLBACK, not a drop: scan stays None and the payload
            #   takes the import_pb_batch python path right below)
            except Exception:
                self._native_import = False
        if scan is None:
            from veneur_tpu.protocol import forward_pb2, metric_pb2
            if stream is None:
                return self.import_pb_batch(
                    forward_pb2.MetricList.FromString(payload).metrics,
                    t_call)
            pbs, bad = [], 0
            for raw in stream.messages:
                try:
                    pbs.append(metric_pb2.Metric.FromString(raw))
                # vnlint: disable=silent-loss (counted: `bad` joins the
                #   returned `failed`, which the stream handler adds to
                #   import_errors — /debug/vars import_errors_total)
                except Exception:
                    bad += 1
            ok, failed = self.import_pb_batch(pbs, t_call, stream)
            return ok, failed + bad
        n = scan["n"]
        if n == 0:
            return 0, 0
        from veneur_tpu.protocol import metric_pb2
        h_lo = scan["h_lo"].tolist()
        h_hi = scan["h_hi"].tolist()
        mtype, scope = scan["mtype"], scan["scope"]
        # a histogram record's route, from the scan's columns and
        # before the row cache can short-circuit the checks: 6 = refused
        # (a type its oneof may not carry, or local scope), 5 = a
        # sketch-family marker (python merges it), 4 = a plain digest
        route = scan["which"].copy()
        histo = route == 4
        route[histo & (scan["compression"] < 0)] = 5
        route[histo & (((mtype != metric_pb2.Histogram)
                        & (mtype != metric_pb2.Timer))
                       | (scope == metric_pb2.Local))] = 6
        mtypes = mtype.tolist()
        scopes = scope.tolist()
        vals = scan["value"].tolist()
        offs = scan["rec_off"].tolist()
        lens = scan["rec_len"].tolist()
        # a set record's route: 3 = a sketch the scan read at this
        # arena's precision (staged from its columns), 7 = one python's
        # unmarshal has to look at (the protobuf path: a legacy
        # encoding, rebased registers, another precision), 6 = refused
        # as above
        sets = route == 3
        if sets.any():
            route[sets & ((scan["set_form"] == 0)
                          | (scan["set_p"] != self.sets.precision))] = 7
            route[sets & ((mtype != metric_pb2.Set)
                          | (scope == metric_pb2.Local))] = 6
        wl = route.tolist()
        cache = self._import_row_cache
        counters, gauges, digests = self.counters, self.gauges, self.digests
        c_rows: list = []
        c_vals: list = []
        g_rows: list = []
        g_vals: list = []
        d_recs: list = []       # plain digests staged: record index, row
        d_rows: list = []
        s_recs: list = []       # set sketches staged: record index, row
        s_rows: list = []
        misses = d_misses = 0       # of all four families; of digests
        ok = failed = 0
        t_wait = time.perf_counter_ns()
        with self.lock:
            t_held = time.perf_counter_ns()
            for i in range(n):
                w = wl[i]
                if w == 4:
                    ck = (h_lo[i], h_hi[i], 4, scopes[i])
                    row = cache.get(ck)
                    if row is None:
                        # a key the cache does not know (its first
                        # sighting since the cache was last cleared):
                        # the record is parsed for its name and tags
                        # (and one bad record, e.g. invalid UTF-8 the
                        # wire scanner can't see, must not abort the
                        # payload).  row_for marks the row touched; a
                        # hit's row is marked by _stage_scanned_digests
                        try:
                            pb = metric_pb2.Metric.FromString(
                                payload[offs[i]:offs[i] + lens[i]])
                            row = digests.row_for(
                                *self._import_histo_identity(pb))
                        except Exception:
                            failed += 1
                            continue
                        cache[ck] = row
                        misses += 1
                        d_misses += 1
                    d_recs.append(i)
                    d_rows.append(row)
                    ok += 1
                elif w == 1 or w == 2:
                    # type/value-oneof agreement (same contract as
                    # import_pb_batch): the wire scan already carries
                    # the type field, so mismatches reject without a
                    # protobuf parse — and before the row cache can
                    # short-circuit the check
                    if mtypes[i] != (0 if w == 1 else 1):
                        failed += 1
                        continue
                    ck = (h_lo[i], h_hi[i], w)
                    row = cache.get(ck)
                    if row is None:
                        # per-metric guard like the pb path: one bad
                        # record (e.g. invalid UTF-8 the wire scanner
                        # can't see) must not abort the whole payload
                        try:
                            pb = metric_pb2.Metric.FromString(
                                payload[offs[i]:offs[i] + lens[i]])
                            tags = list(pb.tags)
                            joined = ",".join(sorted(tags))
                            if w == 1:
                                row = counters.row_for(
                                    MetricKey(pb.name, sm.TYPE_COUNTER,
                                              joined),
                                    MetricScope.GLOBAL_ONLY, tags)
                            else:
                                row = gauges.row_for(
                                    MetricKey(pb.name, sm.TYPE_GAUGE,
                                              joined),
                                    MetricScope.GLOBAL_ONLY, tags)
                        except Exception:
                            failed += 1
                            continue
                        cache[ck] = row
                        misses += 1
                    if w == 1:
                        c_rows.append(row)
                        c_vals.append(vals[i])
                    else:
                        g_rows.append(row)
                        g_vals.append(vals[i])
                    ok += 1
                elif w == 3:
                    ck = (h_lo[i], h_hi[i], 3)
                    row = cache.get(ck)
                    if row is None:
                        # a key the cache does not know: parsed for its
                        # name and tags, as a histogram's is (a hit's
                        # row is marked touched by _stage_scanned_sets)
                        try:
                            pb = metric_pb2.Metric.FromString(
                                payload[offs[i]:offs[i] + lens[i]])
                            tags = list(pb.tags)
                            row = self.sets.row_for(
                                MetricKey(pb.name, sm.TYPE_SET,
                                          ",".join(sorted(tags))),
                                MetricScope.MIXED, tags)
                        except Exception:
                            failed += 1
                            continue
                        cache[ck] = row
                        misses += 1
                    s_recs.append(i)
                    s_rows.append(row)
                    ok += 1
                elif w == 5 or w == 7:
                    try:
                        pb = metric_pb2.Metric.FromString(
                            payload[offs[i]:offs[i] + lens[i]])
                        # vnlint: disable=blocking-propagation (the
                        #   moments branch's asarray converts wire
                        #   vectors — host lists, no device wait)
                        self._import_slow_pb(
                            pb, "set" if w == 7 else "histogram")
                        ok += 1
                    except Exception:
                        failed += 1
                else:
                    failed += 1
            self.imported += ok
            if c_rows:
                counters.merge_batch(np.asarray(c_rows, np.int64),
                                     np.asarray(c_vals, np.float64))
            if g_rows:
                gauges.merge_batch(np.asarray(g_rows, np.int64),
                                   np.asarray(g_vals, np.float64))
            if d_recs:
                # vnlint: disable=blocking-propagation (the flagged
                #   asarray converts host lists — record indexes and
                #   arena rows — never a device array)
                self._stage_scanned_digests(scan, d_recs, d_rows)
                self._ledger["import_digest_hits"] += (
                    len(d_recs) - d_misses)
                self._ledger["import_digest_misses"] += d_misses
            if s_recs:
                # vnlint: disable=blocking-propagation (the flagged
                #   asarray converts host lists — record indexes and
                #   arena rows — never a device array)
                self._stage_scanned_sets(scan, payload, s_recs, s_rows)
            self._ledger["import_row_hits"] += (
                len(c_rows) + len(g_rows) + len(d_rows) + len(s_rows)
                - misses)
            self._ledger["import_row_misses"] += misses
            self._ledger_import(t_call, t_wait, t_held, stream)
        return ok, failed

    def _stage_scanned_sets(self, scan: dict, payload: bytes, recs: list,
                            rows: list) -> None:
        """Stage the set sketches of one scanned payload (record
        indexes `recs`, ascending, into arena rows `rows`) as what they
        are: every sparse sketch's decoded (register, rank) pairs as
        ONE chunk of (row, register, rank) triples, a dense sketch as
        its register row.  Marks the rows touched: staging does not,
        and a row the cache answered for has not been through row_for.
        Call under self.lock."""
        recs = np.asarray(recs, np.int64)
        rows = np.asarray(rows, np.int32)
        self.sets.touched[rows] = True
        sparse = scan["set_form"][recs] == 1
        counts = scan["set_n"][recs][sparse]
        idx, rank = scan["set_idx"], scan["set_rank"]
        if int(counts.sum()) != len(idx):
            # the flat columns also hold pairs of records not staged
            # (refused, failed, another precision): take the staged
            # records' ranges, in wire order
            keep = np.zeros(scan["n"], bool)
            keep[recs[sparse]] = True
            keep = np.repeat(keep, scan["set_n"])
            idx, rank = idx[keep], rank[keep]
        if len(idx):
            self.sets.stage_triples(np.repeat(rows[sparse], counts),
                                    idx, rank)
        half = self.sets.m // 2
        for i, row in zip(recs[~sparse].tolist(),
                          rows[~sparse].tolist()):
            packed = np.frombuffer(payload, np.uint8, half,
                                   int(scan["hll_off"][i]) + 8)
            regs = np.empty(self.sets.m, np.uint8)
            regs[0::2] = packed >> 4
            regs[1::2] = packed & 0x0F
            self.sets.merge_regs(row, regs)
        n_sparse = int(sparse.sum())
        self._ledger["set_import_sparse"] += n_sparse
        self._ledger["set_import_dense"] += len(recs) - n_sparse

    def _stage_scanned_digests(self, scan: dict, recs: list,
                               rows: list) -> None:
        """Stage the plain digests of one scanned payload (record
        indexes `recs`, ascending, into arena rows `rows`) as one
        columnar chunk.  Marks the rows touched: merge_digest_batch
        does not, and a row the cache answered for has not been through
        row_for.  Call under self.lock."""
        recs = np.asarray(recs, np.int64)
        rows = np.asarray(rows, np.int64)
        self.digests.touched[rows] = True
        counts = scan["cent_n"][recs]
        means, weights = scan["cent_mean"], scan["cent_weight"]
        if int(counts.sum()) != len(means):
            # the flat columns also hold what was not staged (markers'
            # vectors, refused or failed records): take the staged
            # records' ranges, in wire order
            keep = np.zeros(scan["n"], bool)
            keep[recs] = True
            keep = np.repeat(keep, scan["cent_n"])
            means, weights = means[keep], weights[keep]
        self.digests.merge_digest_batch(
            rows, counts, means, weights,
            scan["dmin"][recs], scan["dmax"][recs], scan["drsum"][recs])

    def sync_staged(self, min_samples: int = 0) -> bool:
        """Push staged samples into device state NOW if the backlog is
        worth a launch (P7 pipelining: the drain loop calls this each tick
        so flush-time sync only covers the final partial tick; the
        threshold keeps idle servers from paying a fixed-cost device wave
        per trickle of samples)."""
        with self.lock:
            if min_samples <= 0:
                # sync is host-side COO consolidation (cost scales with
                # staged samples, plus hot-key pre-reduction when a row
                # outgrows the dense cap); batch enough samples per tick
                # to amortize the fixed numpy overheads
                min_samples = 4096
            arenas = self._arenas()
            if sum(ar.staged_count() for _, ar in arenas) < min_samples:
                return False
            for _, ar in arenas:
                # vnlint: disable=blocking-propagation (arena sync IS
                #   the locked work by design — it consolidates
                #   host-side COO staging; the asarray chains convert
                #   host lists, never device arrays)
                ar.sync()
            if self.flush_resident:
                # resident arenas: mirror the freshly-consolidated
                # prefix to the device NOW, inside the interval — this
                # is the delta-flush amortization (sets already streamed
                # through their lane sync above).  The uploads are
                # asynchronous; the lock hold covers slice + cast only.
                self.digests.stream_resident()
                self.moments.stream_resident()
            return True

    # -- crash checkpoint (core/checkpoint.py) -----------------------------

    def checkpoint_state(self) -> tuple[dict, dict]:
        """One coherent cut of every arena (plus unique-ts registers and
        the cardinality quota ledger), taken under the aggregator lock
        after folding staged samples — the write side of the crash
        checkpoint.  Returns (JSON-able meta, numpy arrays); the disk
        format is core/checkpoint.py's concern."""
        with self.lock:
            arenas = self._arenas()
            for _, ar in arenas:
                # vnlint: disable=blocking-propagation (arena sync is
                #   host-side COO consolidation — asarray of host lists,
                #   no device wait; same rationale as sync_staged)
                ar.sync()
            meta: dict = {"processed": self.processed,
                          "imported": self.imported,
                          "families": {}}
            arrays: dict = {}
            # LOCK-HELD: C-speed captures only; the per-key Python
            # rendering runs after release so ingest is never queued
            # behind O(keys) row formatting
            caps = {name: ar.checkpoint_capture() for name, ar in arenas}
            if self.unique_ts is not None:
                arrays["unique_ts/regs"] = self.unique_ts.regs.copy()
            if self.cardinality is not None:
                # budget-bounded, not key-space-bounded: stays cheap
                meta["cardinality"] = self.cardinality.checkpoint_state()
        for name, cap in caps.items():
            fmeta, farr = getattr(self, name).checkpoint_render(cap)
            meta["families"][name] = fmeta
            for k, v in farr.items():
                arrays[f"{name}/{k}"] = v
        # in-memory retention tiers ride the arena cut (outside the
        # aggregator lock — the timeline has its own lock and is only
        # ever mutated from the flush-emit path, which is not running
        # concurrently with a checkpoint writer's capture by contract).
        # On-disk tier segments are durable on their own; only the
        # in-memory rings need the checkpoint.
        if self.retention is not None:
            rmeta, rarr = self.retention.checkpoint_capture()
            meta["retention"] = rmeta
            for k, v in rarr.items():
                arrays[f"retention/{k}"] = v
        return meta, arrays

    def restore_state(self, meta: dict, arrays: dict) -> None:
        """Rebuild the arenas from a checkpoint (fresh aggregator, at
        boot before any listener runs): every sketch family restores
        bit-exactly — same rows, same registers, same staged points —
        so the flush after a crash emits what the flush before it would
        have.  Every family PRECHECKS compatibility first (changed
        sketch parameters raise CheckpointIncompatible before any
        arena mutates — a clean cold start, never a half-restored
        mix)."""
        with self.lock:
            per_family = {}
            for name in self._FAMILIES:
                if name not in meta["families"]:
                    continue   # pre-family checkpoint: cold start it
                fmeta = meta["families"][name]
                prefix = f"{name}/"
                farr = {k[len(prefix):]: v for k, v in arrays.items()
                        if k.startswith(prefix)}
                getattr(self, name).restore_precheck(fmeta, farr)
                per_family[name] = (fmeta, farr)
            for name, (fmeta, farr) in per_family.items():
                getattr(self, name).restore_state(fmeta, farr)
            # rows now sit where the checkpoint says: nothing cached
            # before holds, and the recycled total is compared afresh
            self._import_row_cache.clear()
            self._check_import_row_cache()
            self.processed = int(meta.get("processed", 0))
            self.imported = int(meta.get("imported", 0))
            uts = arrays.get("unique_ts/regs")
            if (self.unique_ts is not None and uts is not None
                    and uts.shape == self.unique_ts.regs.shape):
                np.maximum(self.unique_ts.regs, uts,
                           out=self.unique_ts.regs)
            if (self.cardinality is not None
                    and meta.get("cardinality") is not None):
                self.cardinality.restore_state(meta["cardinality"])
        # retention tiers restore OUTSIDE the aggregator lock (the
        # timeline has its own lock; keeping the two unnested keeps the
        # lock-order graph acyclic).  Geometry mismatch cold-starts the
        # tiers (documented in retention/timeline.py); absent block
        # (pre-retention checkpoint) cold-starts too.
        if (self.retention is not None
                and meta.get("retention") is not None):
            prefix = "retention/"
            rarr = {k[len(prefix):]: v for k, v in arrays.items()
                    if k.startswith(prefix)}
            self.retention.checkpoint_restore(meta["retention"], rarr)

    # -- flush -------------------------------------------------------------

    def flush(self, is_local: bool, now: Optional[int] = None) -> FlushResult:
        return self.flush_dispatch(is_local, now).emit()

    def flush_dispatch(self, is_local: bool,
                       now: Optional[int] = None) -> "PendingFlush":
        """Phase 1 of a flush: snapshot+reset under the lock, then
        build, stage and LAUNCH the device program — everything up to
        (but not including) waiting on device results.  Returns a
        PendingFlush whose .emit() fetches the outputs and generates the
        InterMetrics.  flush() == flush_dispatch().emit(); splitting
        them lets a caller double-buffer across intervals — stage and
        dispatch interval N+1 while interval N's kernel still runs, and
        block (jax.block_until_ready semantics, via the fetch) only at
        emit time.  Safe by construction: the snapshot is immutable
        (reset swaps in fresh device buffers rather than zeroing shared
        ones) and the emit phase touches only snapshot + fetched data."""
        now = int(now if now is not None else time.time())
        res = FlushResult()

        seg = self.last_flush_segments = {}
        t0 = time.perf_counter()
        with self.lock:
            t_held = time.perf_counter()
            # vnlint: disable=blocking-propagation (the snapshot must
            #   be lock-coherent; its only flagged chain stages a
            #   host-built lanes buffer via serving.put — asarray of
            #   host data, not a device wait.  The unique-ts estimate
            #   reduction is deferred below, outside the lock)
            snap = self._snapshot_and_reset()
            res.processed, res.imported = snap.pop("counts")
            t_cut = time.perf_counter()
        # deferred from the locked snapshot: the unique-ts estimate is
        # a pure reduction over the swapped-out registers, so it runs
        # without the ingest lock held
        uts_raw = snap.pop("uts_raw", None)
        if uts_raw is not None:
            snap["uts_host"] = hll_mod.estimate_np(uts_raw)
        seg["snapshot_s"] = time.perf_counter() - t0
        # snapshot_s in parts: the wait for the lock (the drain's fold
        # and the import hold it), then under the lock the arenas'
        # sync()s, the take_staged() consolidations, and the rest (the
        # per-family column copies, the set registers' copy and the
        # reset); what remains of snapshot_s is the deferred estimate
        # above
        sync_s, staged_s = snap.pop("part_seconds")
        seg.update(zip(STAGED_LEDGER_KEYS, snap.pop("staged_ledger")))
        seg["snapshot_lock_wait_s"] = t_held - t0
        seg["snapshot_sync_s"] = sync_s
        seg["snapshot_staged_s"] = staged_s
        seg["snapshot_columns_s"] = columns_s = (
            t_cut - t_held - sync_s - staged_s)
        # the columns span in parts; cut, reset and end also say which
        # family (a structured value: span tags, not a row field)
        measured = snap.pop("columns_seconds")
        seg.update(zip(COLUMNS_PART_KEYS,
                       (*measured, columns_s - sum(measured))))
        seg["columns_by_family"] = snap.pop("columns_by_family")
        seg.update(zip(KEY_LEDGER_KEYS, snap.pop("key_ledger")))
        # the interval ledger, swapped out at the cut
        for name, v in snap.pop("ledger").items():
            if name.endswith("_ns"):
                seg[name[:-3] + "_s"] = v / 1e9
            else:
                seg[name] = v
        # per-family touched-key counts ride the segment dict so the
        # flush timeline (and the flush.* self-metric gauges) can relate
        # segment times to interval size
        touched = 0
        for name, ar in self._arenas(self._DISPATCHED):
            seg["keys_" + ar.family] = n = len(snap[name]["rows"])
            touched += n
        # rows whose estimate the chip computes this flush
        # (_dispatch_sets, _dispatch_sets_resident); 0 on every flush
        # that launches no set program
        seg["set_rows_device"] = 0
        lanes = self.sets.lanes_regs
        seg["set_resident_bytes"] = 0 if lanes is None else lanes.nbytes
        # what the arenas' own lanes did over the interval (SetArena.
        # LANE_STATS as set_*, DigestArena.HOT_STATS as hot_*)
        for prefix, stats in (("set", snap.pop("set_lane_stats")),
                              ("hot", snap.pop("hot_lane_stats"))):
            for name, v in stats.items():
                if name.endswith("_ns"):
                    seg[f"{prefix}_{name[:-3]}_s"] = v / 1e9
                else:
                    seg[f"{prefix}_{name}"] = v
        seg["hot_compress_tile_bytes"] = self.digests.hot_tile_bytes
        seg["dense_tiers"] = seg["dense_elems"] = 0
        seg["build_onepass"] = seg["build_fresh_bytes"] = 0
        # the window-ring cut timestamp is taken HERE (the cut), but
        # the slot is published at emit time — see _emit_pending
        snap["query_cut_ts"] = time.time()

        # ONE device program call evaluates the flush on the snapshot
        # OUTSIDE the lock, so ingest continues (flusher.go:26-122 +
        # worker.go:402-459 as one program).  Mesh-less, counters/
        # unique-ts resolve on host, and the digest and set programs run
        # only when their rows were touched; an idle interval skips the
        # dispatch entirely.
        # Multi-controller meshes may NEVER take the idle skip: the
        # lockstep agreement gather inside _dispatch_flush is a
        # collective, and a controller that skipped it while a peer
        # entered it would hang that peer for an interval and pair every
        # later flush off by one — the gather itself decides (all-idle
        # => zero-shape program).
        multi_mesh = self.mesh is not None and jax.process_count() > 1
        idle = (not multi_mesh and touched == 0
                and (not snap["have_uts"]
                     or snap["uts_host"] is not None))
        try:
            pend = None if idle else self._dispatch_flush(snap, is_local)
        except BaseException:
            # a failed dispatch (device OOM, in-flush compile error)
            # must release the set-lane snapshot pin, or lane updates
            # stay on the copying kernels for the process lifetime
            # (lanes exist meshed AND unmeshed-resident; the pin exists
            # only when the snapshot took one — "lanes" in the part)
            if "lanes" in snap.get("sets", {}):
                self.sets.unpin_lanes(snap["sets"]["lanes"])
            raise
        return PendingFlush(self, snap, pend, res, is_local, now, seg)

    def _emit_pending(self, snap: dict, pend: Optional[dict],
                      res: FlushResult, is_local: bool, now: int,
                      seg: dict) -> FlushResult:
        """Phase 2 of a flush (PendingFlush.emit body): fetch the
        dispatched device outputs and generate the InterMetric batch."""
        try:
            host = {} if pend is None else self._fetch_flush(snap, pend,
                                                             seg)
        finally:
            if "lanes" in snap.get("sets", {}):
                # fetched, idle-skipped, OR the fetch raised: either way
                # the flush program can no longer read the snapshotted
                # set registers — release the pin so lane updates go
                # back to in-place donation (a leaked pin would pin the
                # copying kernels forever).  Lanes exist meshed AND
                # unmeshed-resident (flush_resident_arenas); the pin
                # exists only when the snapshot took one.
                self.sets.unpin_lanes(snap["sets"]["lanes"])
        if snap.pop("have_uts"):
            res.unique_ts = int(snap["uts_host"]
                                if snap["uts_host"] is not None
                                else host["unique_ts"])

        t0 = time.perf_counter()
        self._emit_counters(res, snap, host, is_local, now)
        self._emit_gauges(res, snap, is_local, now)
        self._emit_status(res, snap, now)
        self._emit_sets(res, snap, host, is_local, now)
        self._emit_digests(res, snap, host, is_local, now)
        for name, _, qs_key in self._VECTOR_FAMILIES:
            if len(snap[name]["rows"]):
                self._emit_vectors(res, snap[name], host[qs_key],
                                   getattr(self, name), is_local, now)
        if "m_resid" in host and len(host["m_resid"]):
            # solver-convergence observability (sketch.* self-metrics)
            self.last_moments_resid = float(
                np.max(np.abs(host["m_resid"])))
            seg["moments_resid"] = self.last_moments_resid
        seg["emit_s"] = time.perf_counter() - t0

        # window-ring rotation rides the cut: the snapshot parts taken
        # at dispatch (immutable by construction — reset swapped in
        # fresh state) become the newest query slot for each histogram
        # family, stamped with the CUT's timestamp.  Published at emit
        # rather than dispatch so the first query's lazy slot
        # finalization (name-hash build + staged-COO sort) lands in
        # the inter-flush gap instead of overlapping the in-flight
        # flush.  O(1) deque appends; empty intervals rotate too,
        # so the staleness contract (answers cover data up to the last
        # completed cut) holds through idle periods.
        if self.query_rings is not None:
            cut_ts = snap["query_cut_ts"]
            cut = {ar.ring: (snap[name], ar)
                   for name, ar in self._arenas(self._HISTO_FAMILIES)}
            for ring, (part, _) in cut.items():
                self.query_rings[ring].rotate(part, cut_ts)
            # the retention timeline compacts the SAME immutable cut
            # upward into its coarser tiers (summarized per-key state,
            # not part references — the part's lifetime stays bound to
            # the ring).  Runs at emit, off the ingest lock, like the
            # rotation it rides.
            if self.retention is not None:
                self.retention.compact_cut(cut, cut_ts)
        return res

    @staticmethod
    def _padded_rows(rows) -> np.ndarray:
        """Pad an index array to a power of two (index 0 repeated) so the
        gather jit cache stays bounded; padding lanes are sliced off after
        the readback."""
        a = np.zeros(arena_mod._pow2(len(rows)), np.int32)
        a[:len(rows)] = rows
        return a

    class _CompileGuard:
        """Marks a flush-program invocation that will trace+compile a
        new (keys, depth) bucket, so the watchdog and self-metrics can
        tell a compile from a hang.  Two independent roles, both under
        _compile_lock: COVER (compile_in_progress, counter-backed) is
        taken by EVERY guard over a not-yet-compiled shape — concurrent
        guards never clear each other's flag, and a loser thread that
        ends up re-doing a failed winner's compile still has watchdog
        cover; COUNT (compile_events/seconds) is taken only by the one
        guard that claims the shape first, so prewarm + flush racing on
        the same bucket count one compile, not two.  A shape registers
        as compiled only when a covering guard exits without an
        exception — a failed first compile retries with full cover."""

        def __init__(self, agg: "MetricAggregator", shape) -> None:
            self.agg, self.shape = agg, shape
            with agg._compile_lock:
                self.covering = shape not in agg._compiled_shapes
                self.counted = (self.covering
                                and shape not in agg._compiling_shapes)
                if self.counted:
                    agg._compiling_shapes.add(shape)

        def __enter__(self):
            if self.covering:
                with self.agg._compile_lock:
                    self.agg._compiles_active += 1
                    self.agg.compile_in_progress.set()
                self._t0 = time.perf_counter()
            return self

        def __exit__(self, exc_type, *exc):
            if self.covering:
                with self.agg._compile_lock:
                    if self.counted:
                        self.agg.compile_events += 1
                        self.agg.compile_seconds_total += (
                            time.perf_counter() - self._t0)
                        self.agg._compiling_shapes.discard(self.shape)
                    if exc_type is None:
                        self.agg._compiled_shapes.add(self.shape)
                    self.agg._compiles_active -= 1
                    if self.agg._compiles_active == 0:
                        self.agg.compile_in_progress.clear()
            return False

    def prewarm(self, depths, max_keys: int, min_keys: int = 128,
                stop: Optional[threading.Event] = None) -> int:
        """Compile the flush program for every pow2 key bucket in
        [min_keys, max_keys] at the given staged depths, so a cardinality
        ramp in production never pays a first-bucket XLA compile inside a
        flush interval (the compiles land in the persistent cache, making
        later boots near-free).  Meant for a background thread at boot;
        `stop` aborts between buckets.  Returns programs compiled
        (per bucket: the uniform and general sort networks, the moments
        pair and the compactor read-off; plus the set estimate, once).

        On a mesh (`_prewarm_meshed`) it is the shard_map'd program at
        the bucket of `max_keys` and at the bucket an interval of the
        server's own telemetry lands in, and the set-lane kernels: a
        meshed tier is a global sized by its configuration, and each of
        its compiles takes what a whole mesh-less sweep does."""
        if self.mesh is not None:
            return self._prewarm_meshed(depths, max_keys, stop)
        n = 0
        if self.sets.host_regs is not None:
            # the set estimate (_dispatch_sets) at the one row bucket
            # known at boot: the set arena's capacity
            # (set_arena_initial_capacity), which a deployment that
            # touches over half its set rows an interval lands in
            s_regs = jax.ShapeDtypeStruct(
                (self.sets.capacity, self.sets.m), np.uint8)
            with self._CompileGuard(self, ("set_estimate", s_regs.shape)):
                hll_mod.estimate.lower(s_regs).compile()
            n += 1
        u = 1 << (max(min_keys, 2) - 1).bit_length()
        max_keys = arena_mod._pow2(max_keys)   # arena rounds up too
        buckets = []
        while u <= max_keys:
            for dpt in depths:
                buckets.append((u, max(2, arena_mod._pow2(dpt))))
            u *= 2
        dt = self.digests.eval_dtype
        # compact_general staging uploads bf16 general values — the
        # prewarmed struct dtype must match or the signature misses
        gen_dt = (self.digests.stage_dtype
                  if self.digests.compact_general else dt)
        for u_pad, d_pad in buckets:
            if stop is not None and stop.is_set():
                break
            # AOT lower+compile from shape structs: populates the jit and
            # persistent caches without allocating or executing anything
            # on the device the live flushes are using.  The WEIGHT
            # struct stays eval_dtype even under compact_general —
            # build_dense narrows values only — or the prewarmed
            # signature would never match a live flush
            dv = jax.ShapeDtypeStruct((u_pad, d_pad), gen_dt)
            dw_s = jax.ShapeDtypeStruct((u_pad, d_pad), dt)
            mm = jax.ShapeDtypeStruct((2, u_pad), dt)
            # both production programs per bucket: the depth-vector
            # uniform variant (raw-sample intervals — the common case on
            # every backend) and the general weighted one.
            # The structs MUST match the production upload dtypes
            # (arena build_dense: stage_dtype values — bf16 when the
            # option is on — and int16 depths) or the prewarmed
            # signature misses and the first flush pays an uncovered
            # in-flush compile
            dv_u = jax.ShapeDtypeStruct((u_pad, d_pad),
                                        self.digests.stage_dtype)
            dep = jax.ShapeDtypeStruct((u_pad,), np.int16)
            # compile the variant production will launch: global tiers
            # donate their per-flush buffers (donation is part of the
            # executable — input/output aliasing — so the donated and
            # plain programs cache separately)
            donate = not self.is_local
            du = (self.flush_fn.depth_variant_donated if donate
                  else self.flush_fn.depth_variant)
            dg = (self.flush_fn.lower_donated if donate
                  else self.flush_fn.lower)
            with self._CompileGuard(self, ((u_pad, d_pad), True, donate)):
                du.lower(dv_u, dep, self._pct_arr).compile()
            n += 1
            with self._CompileGuard(self, ((u_pad, d_pad), False, donate)):
                dg(dv, dw_s, mm, self._pct_arr, uniform=False).compile()
            n += 1
            # moments family: both program variants per bucket, with
            # the EXACT live operand dtypes (f32 dense + f32 ab/lab/imp
            # conversions, int16 depth vector) — prewarm-parity
            # (analysis/rules/prewarm.py) checks these signatures
            # against the _dispatch_moments call sites.  Covered even
            # with dispatch rules off: moments WIRE payloads still
            # route into the moments arena (self-description beats
            # configuration), so any tier can see moments rows
            mk = self.moments.k
            m_dv = jax.ShapeDtypeStruct((u_pad, d_pad), np.float32)
            m_dw = jax.ShapeDtypeStruct((u_pad, d_pad), np.float32)
            m_ab = jax.ShapeDtypeStruct((2, u_pad), np.float32)
            m_lab = jax.ShapeDtypeStruct((2, u_pad), np.float32)
            m_imp = jax.ShapeDtypeStruct((u_pad, 2 * (mk + 1)),
                                         np.float32)
            m_dep = jax.ShapeDtypeStruct((u_pad,), np.int16)
            mg = self.moments_fn.lower
            md = self.moments_fn.depth_variant
            with self._CompileGuard(
                    self, ("moments", (u_pad, d_pad), False)):
                mg(m_dv, m_dw, m_ab, m_lab, m_imp,
                   self._pct_arr).compile()
            n += 1
            with self._CompileGuard(
                    self, ("moments", (u_pad, d_pad), True)):
                md.lower(m_dv, m_dep, m_ab, m_lab, m_imp,
                         self._pct_arr).compile()
            n += 1
            # compactor family: the read-off shape depends on keys
            # only (ladder state replaces staged depth), so one
            # program per key bucket, skipped on depth repeats
            if ("compactor", u_pad) not in self._compiled_shapes:
                c_cap = self.compactors.cc_cap
                c_lv = self.compactors.cc_levels
                c_cv = jax.ShapeDtypeStruct((u_pad, c_lv * c_cap),
                                            np.float32)
                c_cc = jax.ShapeDtypeStruct((u_pad, c_lv), np.int32)
                c_cs = jax.ShapeDtypeStruct((u_pad,), np.float32)
                c_mm = jax.ShapeDtypeStruct((2, u_pad), np.float32)
                with self._CompileGuard(self, ("compactor", u_pad)):
                    self.compactor_fn.lower(
                        c_cv, c_cc, c_cs, c_mm,
                        self._pct_arr).compile()
                n += 1
        return n

    def prewarm_launch(self, depths, max_keys: int) -> int:
        """What an unmeshed node launches BEFORE it listens
        (`prewarm_flush_shapes`; Server.start blocks on it, as the meshed
        boot blocks on _prewarm_meshed): each program a steady interval
        of a deployment sized for `max_keys` keys can need, once, on
        zeros, through the flush's own launches (_launch_digests,
        DigestArena._hot_compress) — the
        executable a data flush looks up is the one compiled here.  A
        closed list:

          1. the digest program at the two row buckets an interval that
             touches more than a quarter of `max_keys` lands in, cut
             into upload chunks as a flush cuts them, at each of
             `depths`, in the depth-vector form and the weighted one;
          2. the deep tier's program at its smallest row bucket
             (DEEP_TIER_MIN_ROWS x DENSE_DEPTH_CAP) and the hot-key
             compress tile.

        The set estimate is not in it: its row bucket follows the set
        keys an interval touches, which no option states (a resident
        arena has its own boot).
        A bucket the list lacks (a cardinality ramp's, the first
        intervals' of the server's own telemetry) compiles in its first
        flush, under the guard; prewarm()'s ahead-of-time sweep of them
        is no longer part of a server's boot.  Returns the programs
        this call compiled."""
        d = self.digests
        donate = not self.is_local
        # compact_general staging uploads bf16 general values
        gen_dt = d.stage_dtype if d.compact_general else d.eval_dtype
        with self._compile_lock:
            before = set(self._compiled_shapes)
        top = arena_mod._pow2(max_keys)
        outs = []
        # (rows, depth, deep tier) of the weighted launches
        weighted = [(arena_mod.DEEP_TIER_MIN_ROWS,
                     arena_mod.DENSE_DEPTH_CAP, True)]
        for bucket in (top, max(top // 2, 1)):
            rows = bucket // self._upload_chunk_count(bucket,
                                                      self.is_local)
            for depth in depths:
                d_pad = d.dense_depth(depth)
                weighted.append((rows, d_pad, False))
                outs.append(self._launch_digests(
                    *d.put_dense_uniform(
                        np.zeros((rows, d_pad), d.stage_dtype),
                        np.zeros(rows, np.int16)), None, True, donate))
        for rows, depth, deep in weighted:
            outs.append(self._launch_digests(
                *d.put_dense(np.zeros((rows, depth), gen_dt),
                             np.zeros((rows, depth), d.eval_dtype),
                             np.zeros((2, rows), d.eval_dtype)),
                False, donate, deep))
        d.prewarm_hot()
        jax.block_until_ready(outs)
        with self._compile_lock:
            return len(self._compiled_shapes - before)

    def _uts_lanes(self, uts: Optional[np.ndarray]):
        """[R, m] unique-timeseries register lanes on the mesh, this
        process's tally (if any) in lane 0; the program pmaxes over both
        mesh axes (across processes this is the DCN union of per-host
        tallies)."""
        from veneur_tpu.parallel.mesh import REPLICA_AXIS
        lanes = np.zeros((self.mesh.shape[REPLICA_AXIS], self._uts_m),
                         np.uint8)
        if uts is not None:
            lanes[0] = uts
        return serving.put(lanes, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(REPLICA_AXIS, None)))

    def _prewarm_meshed(self, depths, max_keys: int,
                        stop: Optional[threading.Event]) -> int:
        """Compile what a meshed global launches in steady state, by
        launching each program once on zeros through the flush's own
        launch (_launch_meshed), so that the executable a data flush
        looks up is the one compiled here — shapes, shardings, donation
        and all.  In this order:

          1. the flush program at the bucket `max_keys` touched rows land
             in (arena_initial_capacity: the deployment's key count) at
             each of `depths`, in the uniform form and the general one
             where the chip tells them apart;
          2. the set-lane kernels (SetArena.prewarm_lanes);
          3. the flush program at the smallest bucket, which an interval
             that brought nothing but the server's own telemetry lands in
             (the first intervals after boot, a fleet gone quiet).

        Nothing in between: a global receives its fleet's whole key set
        from its first interval on, a meshed compile is tens of seconds
        cold, and the pow2 sweep the mesh-less branch makes (11 buckets
        from 128 to 131,072, two forms each) would still be compiling
        minutes after the first forward arrived.  A bucket not compiled
        here compiles in its first flush, under the same guard.  The
        zeros run beside live flushes: the set lanes they read are
        pinned like a flush's snapshot, every other buffer is their own.
        Returns the programs this call compiled; 0 once all are."""
        d = self.digests
        donate = not self.is_local

        def launch(u_pad: int, d_pad: int, uniform: bool) -> None:
            shapes = ((u_pad, d_pad), (u_pad, d_pad), (2, u_pad),
                      self.sets.lanes_regs.shape,
                      self.counters.values.shape + (2,),
                      (d.n_replicas, self._uts_m))
            with self._compile_lock:
                if (shapes, uniform, donate) in self._compiled_shapes:
                    return
            dv = np.zeros((u_pad, d_pad), d.eval_dtype)
            with self.lock:
                lanes = self.sets.snapshot_lanes()
            try:
                _in, flat, _regs, _don = self._launch_meshed(
                    dv, dv.copy(), np.zeros((2, u_pad), d.eval_dtype),
                    lanes, self.counters.planes_from(
                        np.zeros(shapes[4][:2])),
                    self._uts_lanes(None), uniform, self.is_local, {})
                flat.block_until_ready()
            finally:
                self.sets.unpin_lanes(lanes)

        with self._compile_lock:
            before = set(self._compiled_shapes)
        buckets = [(max_keys, dpt) for dpt in depths] + [(1, 1)]
        for i, (rows, depth) in enumerate(buckets):
            if stop is not None and stop.is_set():
                break
            if i == len(buckets) - 1:
                self.sets.prewarm_lanes()
            u_pad = d.n_shards * d.dense_block_per_shard(rows)
            d_pad = d.dense_depth(depth)
            # the uniform (key-only) network is a program of its own
            # only where the Pallas kernel takes the per-device shape
            if serving.pallas_eval_applies(
                    u_pad // d.n_shards // d.n_replicas, d_pad,
                    d.eval_dtype):
                launch(u_pad, d_pad, True)
            launch(u_pad, d_pad, False)
        with self._compile_lock:
            return len(self._compiled_shapes - before)

    def _dispatch_flush(self, snap: dict, is_local: bool) -> dict:
        """Build, stage and LAUNCH the per-flush device program on the
        snapshot (outside the lock) — everything asynchronous; no device
        wait happens here.  Returns the pending-launch state that
        _fetch_flush consumes at emit time.

        Mesh-less: the set estimate over the snapshot's register copy
        (_dispatch_sets), then one digest program call per upload chunk
        (dense upload -> [K, P+2] readback); counters/unique-ts were
        already resolved on host at snapshot.  Meshed: the full-family
        shard_map'd program as ONE packed launch over pre-sharded staged
        buffers.  On a non-forwarding (global) tier every per-flush
        input buffer is DONATED to the program, killing XLA's
        copy-on-entry; forwarding tiers keep the dense matrices alive
        for digest export."""
        dpart = snap["digests"]
        nd = len(dpart["rows"])
        seg = self.last_flush_segments
        pend: dict = {"nd": nd, "meshed": self.mesh is not None}
        if "host_regs" in snap["sets"]:
            # first, so the register upload rides the transfer engine
            # under the digest build/layout below
            pend["sets"] = self._dispatch_sets(snap["sets"])
        elif self.mesh is None and "lanes" in snap["sets"]:
            # resident registers with rows touched: first too, so the
            # program runs under the digest build
            pend["sets"] = self._dispatch_sets_resident(snap["sets"],
                                                        is_local)
        # the moments family launches its own program — a dense
        # segmented-sum merge + batched maxent solve, a different
        # compute class from the digest sort network — so it dispatches
        # first and its kernel overlaps the digest staging; the
        # compactor read-off (a third compute class: implied-weight
        # eval of folded ladder state) rides the same overlap
        pend["moments"] = self._dispatch_moments(snap)
        pend["compactors"] = self._dispatch_compactors(snap)
        if self.mesh is None:
            if nd == 0:
                return pend
            donate = not is_local
            rpart = dpart.pop("resident", None)
            t0 = time.perf_counter()
            if rpart is not None and not rpart["dirty"]:
                # resident delta path: the dense matrices assemble ON
                # DEVICE from the interval's streamed chunks plus the
                # tail (arena.assemble_resident) — the critical-path
                # upload is the dense-id map + tail; everything else
                # already crossed the link during the interval
                *dev, critical = self.digests.assemble_resident(
                    rpart, dpart["staged"], dpart["rows"],
                    dpart["d_min"], dpart["d_max"], dpart["uniform"],
                    donate)
                seg["resident"] = 1.0
                seg["amortized_bytes"] = (
                    seg.get("amortized_bytes", 0)
                    + rpart["streamed_bytes"])
                builds = [{"sel": None, "deep": False,
                           "uniform": dpart["uniform"],
                           "shape": tuple(dev[0].shape), "n_chunks": 1,
                           "operands": lambda sl: dev}]
            else:
                builds, critical = [], 0
                for tier in self._build_tiers(dpart):
                    dv, dw, minmax = tier.pop("dense")
                    # uniform tiers: dw is the [U] int16 depth vector,
                    # not the [U, D] weight matrix, and minmax stays
                    # host-side — roughly half the build and the
                    # uploaded bytes
                    critical += (dv.nbytes + dw.nbytes
                                 + (0 if tier["uniform"] else minmax.nbytes))
                    tier.update(
                        shape=dv.shape,
                        n_chunks=self._upload_chunk_count(dv.shape[0],
                                                          is_local),
                        operands=functools.partial(
                            self._put_tier, dv, dw, minmax,
                            tier["uniform"]))
                    builds.append(tier)
            seg["build_s"] = time.perf_counter() - t0
            seg["upload_bytes"] = seg.get("upload_bytes", 0) + critical
            seg["dense_tiers"] = len(builds)
            seg["dense_elems"] = sum(b["shape"][0] * b["shape"][1]
                                     for b in builds)
            layout_s = dispatch_s = 0.0
            t_dispatch0 = None
            for b in builds:
                n_chunks = b["n_chunks"]
                rows_per = b["shape"][0] // n_chunks
                # (the pending flush keeps the tier, not its host operand)
                operands = b.pop("operands")
                outs = []
                chunk_stats = [] if n_chunks > 1 else None
                first_dev = None
                for c in range(n_chunks):
                    t0 = time.perf_counter()
                    dvd, dwd, mmd = operands(
                        slice(c * rows_per, (c + 1) * rows_per))
                    up_s = time.perf_counter() - t0
                    layout_s += up_s
                    t0 = time.perf_counter()
                    if first_dev is None:
                        first_dev = (dvd, dwd)
                    if t_dispatch0 is None:
                        t_dispatch0 = t0
                    outs.append(self._launch_digests(
                        dvd, dwd, mmd, b["uniform"], donate, b["deep"]))
                    d_s = time.perf_counter() - t0
                    dispatch_s += d_s
                    if chunk_stats is not None:
                        chunk_stats.append({"rows": rows_per,
                                            "upload_s": up_s,
                                            "dispatch_s": d_s})
                        # stage 3 of the pipeline: start this chunk's
                        # D2H readback now, so it drains while the NEXT
                        # chunk uploads and evaluates
                        for leaf in jax.tree_util.tree_leaves(outs[-1]):
                            leaf.copy_to_host_async()
                        if c + 1 >= self._delta_nbuf:
                            # backpressure at the in-flight window
                            # (flush_delta_nbuf): wait for the OLDEST
                            # in-flight chunk, not the one just
                            # dispatched — the classic double-buffer
                            # drain
                            j = c + 1 - self._delta_nbuf
                            t0 = time.perf_counter()
                            jax.block_until_ready(outs[j])
                            chunk_stats[j]["drain_s"] = (
                                time.perf_counter() - t0)
                # donated buffers are consumed by the program; a
                # forwarding tier (never donating) keeps the first
                # chunk for export
                b.update(outs=outs, chunk_stats=chunk_stats,
                         first_dev=None if donate else first_dev)
            seg["layout_s"] = layout_s
            seg["dispatch_s"] = dispatch_s
            self._account_build(
                seg, [b["outs"] for b in builds],
                [b["first_dev"] for b in builds if b["first_dev"]])
            pend.update(tiers=builds, t_dispatch0=t_dispatch0)
            return pend
        else:
            multi = jax.process_count() > 1
            if multi and is_local:
                # a local/forwarding tier is a single-process server; the
                # multi-process mesh serves the GLOBAL tier (the gRPC
                # forward/import edge is the cross-host transport, like
                # the reference's proxy ring — multihost.py)
                raise NotImplementedError(
                    "multi-process meshed serving supports the global "
                    "tier only (is_local=False)")
            crows = snap["counters"]["rows"]
            srows = snap["sets"]["rows"]
            if multi:
                g_nd, g_depth, g_nc, g_ns, g_uniform = \
                    multihost.lockstep_agree(
                        nd, self.digests.staged_depth(dpart["staged"]),
                        len(crows), len(srows),
                        snap["digests"]["uniform"],
                        snap["key_fingerprints"])   # lock-coherent
            else:
                g_nd, g_depth = nd, 0
                g_nc, g_ns = len(crows), len(srows)
                g_uniform = snap["digests"]["uniform"]
            t0 = time.perf_counter()
            (dv, dw, minmax), = self.digests.build_dense(
                dpart["staged"], dpart["rows"],
                dpart["d_min"], dpart["d_max"],
                u_floor=g_nd, d_floor=g_depth)
            seg["build_s"] = time.perf_counter() - t0
            seg["upload_bytes"] = (seg.get("upload_bytes", 0)
                                   + dv.nbytes + dw.nbytes
                                   + minmax.nbytes)
            inputs, flat_dev, set_regs_out, donate = self._launch_meshed(
                dv, dw, minmax, snap["sets"]["lanes"],
                snap["counters"]["planes"](), snap["uts_regs"], g_uniform,
                is_local, seg)
            dvd, dwd = inputs.dense_v, inputs.dense_w
            t0 = time.perf_counter()
            set_regs_dev = None
            ps = None
            if (g_ns and is_local
                    and (snap["sets"]["scopes"]
                         == int(MetricScope.MIXED)).any()):
                ps = self._padded_rows(srows)
                set_regs_dev = serving.set_regs_pack(
                    set_regs_out, jnp.asarray(ps))
            seg["dispatch_s"] += time.perf_counter() - t0
            self._account_build(seg, [flat_dev],
                                [] if donate else [(dvd, dwd)])
            pend.update(
                flat_dev=flat_dev, set_regs_dev=set_regs_dev, ps=ps,
                k_rows=inputs.dense_v.shape[0],
                k2=inputs.counter_planes.shape[1],
                n_sets_cap=inputs.hll_regs.shape[1],
                crows=crows, srows=srows,
                dense_dev=None if donate else (dvd, dwd))
            return pend

    def _account_build(self, seg: dict, outs: list, kept_dev: list):
        """After the digest launch(es): what the build did, onto the
        timeline row, and — where the arena's kept operands served it —
        what DigestArena.hold_dense asks of a caller: the launches'
        results, and the device operands a forwarding tier keeps."""
        stats = self.digests.take_build_stats()
        seg["build_onepass"] = stats["onepass"]
        seg["build_fresh_bytes"] = stats["fresh_bytes"]
        if stats["onepass"]:
            self.digests.hold_dense(outs)
            for dev in kept_dev:
                self.digests.lend_dense(dev)

    def _build_tiers(self, dpart: dict) -> list:
        """The unmeshed flush's host-built operand(s) from a digest
        part (`DigestArena.build_dense`): one over every touched row —
        the single `[U, D]` operand — unless the snapshot named a deep
        tier (`DigestArena.deep_rows`).  Then two: the long tail in the
        form its weights allow at its own depth, and the deep rows
        weighted, DENSE_DEPTH_CAP deep, at a pow2 row bucket of at
        least DEEP_TIER_MIN_ROWS.  Each tier: `sel` (its rows'
        positions in the part; None = all), `deep`, `uniform`, `dense`
        (its triple)."""
        deep = dpart.get("deep")
        if deep is None:
            sels, forms = None, (dpart["uniform"],)
        else:
            sels = self.digests.tier_rows(len(dpart["rows"]), deep)
            forms = (dpart["shallow_uniform"], False)
        built = self.digests.build_dense(
            dpart["staged"], dpart["rows"], dpart["d_min"],
            dpart["d_max"], uniform=forms[0], sels=sels)
        return [{"sel": sel, "deep": k == 1, "uniform": uniform,
                 "dense": dense}
                for k, (sel, uniform, dense) in enumerate(
                    zip(sels or (None,), forms, built))]

    def _put_tier(self, dv, dw, minmax, uniform: bool, sl: slice):
        """Device-put rows `sl` of one host-built tier."""
        if uniform:
            return (*self.digests.put_dense_uniform(dv[sl], dw[sl]), None)
        return self.digests.put_dense(dv[sl], dw[sl], minmax[:, sl])

    def _upload_chunk_count(self, n_rows: int, is_local: bool) -> int:
        """Upload/evaluate/readback overlap (the _dma_pipeline double
        buffer lifted to the host<->HBM boundary): a big GLOBAL-tier
        operand splits into row chunks — chunk i+1's upload rides the
        transfer engine while chunk i's program runs and chunk i-1's
        readback drains (copy_to_host_async), with at most _delta_nbuf
        chunks in flight before the host blocks.  Forwarding tiers keep
        one piece (the digest export gathers from the whole dense
        matrix)."""
        if is_local:
            return 1
        if self._delta_chunk and n_rows >= 2 * self._delta_chunk:
            # explicit rows-per-chunk override (flush_delta_chunk_keys);
            # pow2 over pow2 rows always tiles exactly
            return n_rows // self._delta_chunk
        if (self._upload_chunks > 1
                and n_rows >= self._upload_chunks * _CHUNK_MIN_ROWS):
            return self._upload_chunks
        return 1

    def _launch_digests(self, dvd, dwd, mmd, uniform: bool, donate: bool,
                        deep: bool = False):
        """LAUNCH the unmeshed digest program on one chunk's device
        operands — the one way it is ever called, whether the operands
        were assembled on the device (resident), built on the host and
        put, or zeros at boot (prewarm_launch): the guard's key, the
        program form (uniform: dwd is the depth vector and minmax stays
        on the host; deep: the deep tier's own program) and the
        donation rule live here."""
        shape = (int(dvd.shape[0]), int(dvd.shape[1]))
        if deep:
            with self._CompileGuard(self, ("deep_tier", shape, donate)):
                fn = (self.flush_fn.deep_tier_donated if donate
                      else self.flush_fn.deep_tier)
                return fn(dvd, dwd, mmd, self._pct_arr)
        with self._CompileGuard(self, (shape, bool(uniform), donate)):
            if uniform:
                fn = (self.flush_fn.depth_variant_donated if donate
                      else self.flush_fn.depth_variant)
                return fn(dvd, dwd, self._pct_arr)
            return self.flush_fn(dvd, dwd, mmd, self._pct_arr,
                                 uniform=False, donate=donate)

    def _launch_meshed(self, dv, dw, minmax, lanes, planes, uts_regs,
                       uniform: bool, is_local: bool, seg: dict):
        """Place one flush's host-built dense matrices on the mesh and
        LAUNCH the shard_map'd program on them and the lane state — the
        one way the meshed program is ever called, by _dispatch_flush on
        an interval's snapshot and by prewarm on zeros, so the two
        cannot drift apart in shape, sharding or donation.  Returns
        (inputs, flat f32 output, merged set registers, donated)."""
        from veneur_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS

        # pre-sharded staging: each device's blocks are placed
        # directly (no process-wide re-layout on program entry)
        t0 = time.perf_counter()
        dvd, dwd, mmd = self.digests.put_dense_sharded(dv, dw, minmax)
        inputs = serving.FlushInputs(
            dense_v=dvd, dense_w=dwd, minmax=mmd, hll_regs=lanes,
            counter_planes=planes, uts_regs=uts_regs)
        seg["layout_s"] = time.perf_counter() - t0
        # per-device eval shape decides whether the Pallas network
        # choice is a distinct program (see pallas_eval_applies):
        # after the all_to_all repartition each device evaluates
        # K/(S*R) rows at the full staged depth
        n_dev_rows = (dvd.shape[0] // self.mesh.shape[SHARD_AXIS]
                      // self.mesh.shape[REPLICA_AXIS])
        uniform = bool(uniform and serving.pallas_eval_applies(
            n_dev_rows, dvd.shape[1], dvd.dtype))
        # a forwarding tier re-reads the dense matrices for digest
        # export; only a global tier donates its staged buffers
        donate = not is_local
        shapes = tuple(x.shape for x in inputs)
        # what ran where, on the flush timeline row: the dense shape
        # launched (padding included) and what its collectives move
        seg["device_rows"], seg["device_depth"] = dvd.shape
        seg["collective_bytes"] = serving.collective_bytes(
            self.mesh, shapes)
        t0 = time.perf_counter()
        with self._CompileGuard(self, (shapes, uniform, donate)):
            # ONE flat f32 buffer + the u8 set registers — the
            # packed launch shape (serving.pack_outputs): dispatch
            # cost scales with output-handle count
            flat_dev, set_regs_out = self.flush_fn(
                inputs, self._pct_arr, uniform=uniform, donate=donate)
        seg["dispatch_s"] = time.perf_counter() - t0
        return inputs, flat_dev, set_regs_out, donate

    def _dispatch_sets(self, spart: dict) -> dict:
        """Upload the snapshot's register copy and LAUNCH the set
        estimate on it (mesh-less host registers, outside the lock):
        [rows bucket, m] u8 -> [rows bucket] f32 LogLog-Beta estimates,
        the program the meshed flush runs inside flush_body.  The
        readback starts here and is waited for in _fetch_flush.  A
        handful of rows (_SET_DEVICE_MIN_ROWS) is estimated here, by
        the numpy twin, and launches nothing."""
        seg = self.last_flush_segments
        regs = spart["host_regs"]
        n = len(spart["rows"])
        if n < _SET_DEVICE_MIN_ROWS:
            return {"ests": hll_mod.estimate_np_rows(regs[:n])}
        t0 = time.perf_counter()
        regs_dev = serving.put(regs, None)
        t1 = time.perf_counter()
        with self._CompileGuard(self, ("set_estimate", regs.shape)):
            ests = hll_mod.estimate(regs_dev)
        ests.copy_to_host_async()
        seg["set_rows_device"] = n
        seg["set_upload_bytes"] = regs.nbytes
        seg["upload_bytes"] = seg.get("upload_bytes", 0) + regs.nbytes
        return {"out": ests, "form": "bucket", "t0": t0,
                "stats": {"rows": n, "upload_s": t1 - t0,
                          "dispatch_s": time.perf_counter() - t1}}

    def _dispatch_sets_resident(self, spart: dict, is_local: bool) -> dict:
        """LAUNCH the flush's read of an unmeshed resident arena's
        pinned lane snapshot (outside the lock; nothing is uploaded).
        A tier that forwards no set this flush — a global, or a local
        with no mixed-scope set row — estimates where the registers
        live: the whole-plane program, [capacity] f32 back.  A
        forwarding local needs the registers themselves to marshal, and
        fewer than _SET_DEVICE_MIN_ROWS rows are not worth the plane's
        pass: both gather the touched rows' u8 registers back, for
        hll.estimate_np_rows at the fetch."""
        seg = self.last_flush_segments
        rows = spart["rows"]
        n = len(rows)
        t0 = time.perf_counter()
        forwards = is_local and bool(
            (spart["scopes"] == int(MetricScope.MIXED)).any())
        if forwards or n < _SET_DEVICE_MIN_ROWS:
            form, out = "regs", self.sets.lane_gather(
                spart["lanes"], self._padded_rows(rows))
        else:
            form, out = "plane", self.sets.lane_estimate(spart["lanes"])
            seg["set_rows_device"] = n
        out.copy_to_host_async()
        seg["set_upload_bytes"] = 0
        return {"out": out, "form": form, "t0": t0,
                "stats": {"rows": n, "upload_s": 0.0,
                          "dispatch_s": time.perf_counter() - t0}}

    def _dispatch_moments(self, snap: dict) -> Optional[dict]:
        """Build, stage and LAUNCH the moments-family program on the
        snapshot (outside the lock): compact dense build of the staged
        samples (uniform depth-vector variant on raw-sample intervals),
        host f64 conversion of the ivec accumulators to Chebyshev
        contributions, one program call (merge kernel + maxent solver,
        ops/moments_eval.py).  Returns None when no moments rows were
        touched."""
        mpart = snap["moments"]
        nm = len(mpart["rows"])
        if nm == 0:
            return None
        seg = self.last_flush_segments
        m = self.moments
        uniform = mpart["uniform"]
        rpart = mpart.pop("resident", None)
        t0 = time.perf_counter()
        if rpart is not None and not rpart["dirty"]:
            # resident delta path (flush_resident_arenas): dense sample
            # matrices assemble on device from the streamed chunks +
            # tail; only the ivec Chebyshev contributions (subset-sized)
            # and the dense-id/tail cross the link at flush time.  The
            # moments program never donates, so the scatter chain runs
            # its copying form (donate=False).
            dv, dw, _, critical = m.assemble_resident(
                rpart, mpart["staged"], mpart["rows"],
                mpart["d_min"], mpart["d_max"], uniform, donate=False)
            seg["resident"] = 1.0
            seg["amortized_bytes"] = (seg.get("amortized_bytes", 0)
                                      + rpart["streamed_bytes"])
        else:
            # (no hold_dense after it: the arena makes its operands
            # anew every flush)
            (dv, dw, _), = m.build_dense(
                mpart["staged"], mpart["rows"],
                mpart["d_min"], mpart["d_max"], uniform=uniform)
            critical = dv.nbytes + dw.nbytes
        shape = (int(dv.shape[0]), int(dv.shape[1]))
        imp, ab, lab = m.import_contrib(mpart, shape[0])
        seg["m_build_s"] = time.perf_counter() - t0
        seg["upload_bytes"] = (seg.get("upload_bytes", 0) + critical
                               + imp.nbytes + ab.nbytes + lab.nbytes)
        t0 = time.perf_counter()
        # the one launch of the moments program: asarray puts a
        # host-built matrix and passes a device-assembled one through
        dvd, dwd, abd, labd, impd = (
            jnp.asarray(dv), jnp.asarray(dw), jnp.asarray(ab),
            jnp.asarray(lab), jnp.asarray(imp))
        with self._CompileGuard(self, ("moments", shape, uniform)):
            if uniform:
                out = self.moments_fn.depth_variant(
                    dvd, dwd, abd, labd, impd, self._pct_arr)
            else:
                out = self.moments_fn(dvd, dwd, abd, labd, impd,
                                      self._pct_arr)
        seg["m_dispatch_s"] = time.perf_counter() - t0
        return {"out": out, "n": nm}

    def _dispatch_compactors(self, snap: dict) -> Optional[dict]:
        """Fold and LAUNCH the compactor-family read-off on the
        snapshot (outside the lock): the interval's staged points fold
        into the snapshot ladder states in batched compact_batch
        rounds (arena.fold_flush — cached in the part, shared with
        forwarding export and the query plane), then ONE program
        evaluates every touched key's quantiles from the implied
        ``2**level`` item weights (ops/compactor_eval.py).  Counts and
        sums come exact from the host scalar accumulators.  Returns
        None when no compactor rows were touched."""
        part = snap["compactors"]
        nc = len(part["rows"])
        if nc == 0:
            return None
        seg = self.last_flush_segments
        cp = self.compactors
        t0 = time.perf_counter()
        u_pad = arena_mod._pow2(max(nc, 2))
        cv, cc, cscale, mm = cp.flush_operands(part, part["staged"],
                                               u_pad)
        seg["c_build_s"] = time.perf_counter() - t0
        seg["upload_bytes"] = (seg.get("upload_bytes", 0) + cv.nbytes
                               + cc.nbytes + cscale.nbytes + mm.nbytes)
        t0 = time.perf_counter()
        cvd, ccd, csd, mmd = (jnp.asarray(cv), jnp.asarray(cc),
                              jnp.asarray(cscale), jnp.asarray(mm))
        with self._CompileGuard(self, ("compactor", u_pad)):
            out = self.compactor_fn(cvd, ccd, csd, mmd, self._pct_arr)
        seg["c_dispatch_s"] = time.perf_counter() - t0
        return {"out": out, "n": nc}

    def _fetch_flush(self, snap: dict, pend: dict, seg: dict) -> dict:
        """Wait on a dispatched flush's device outputs and read them
        back as host numpy — the ONLY place a flush blocks on the
        device.  Either way the readback is a handful of slim arrays:
        device traffic scales with the interval's samples and touched
        keys."""
        dpart = snap["digests"]
        nd = pend["nd"]
        n_cols = len(self._pct_arr)  # median + configured percentiles
        host: dict = {}
        for name, tag, qs_key in self._VECTOR_FAMILIES:
            fp = pend.get(name)
            if fp is None:
                continue
            t0 = time.perf_counter()
            out = serving.fetch(fp["out"])
            seg[tag + "_device_s"] = time.perf_counter() - t0
            seg["readback_bytes"] = (seg.get("readback_bytes", 0)
                                     + out.nbytes)
            host[qs_key] = out[:fp["n"], :n_cols]
            if name == "moments":
                # the solver's residual rides the last column
                host["m_resid"] = out[:fp["n"], -1]
        if not pend["meshed"]:
            sp = pend.get("sets", {})
            set_t0 = sp.get("t0")       # None: nothing was launched
            set_wait = 0.0
            if set_t0 is not None:
                srows = snap["sets"]["rows"]
                t0 = time.perf_counter()
                got = serving.fetch(sp["out"])
                set_wait = time.perf_counter() - t0
                sp["stats"]["wait_s"] = seg["set_device_s"] = set_wait
                seg["device_sets"] = sp["stats"]
                seg["set_readback_bytes"] = got.nbytes
                seg["readback_bytes"] = (seg.get("readback_bytes", 0)
                                         + got.nbytes)
                if sp["form"] == "regs":
                    # resident registers, gathered: exact u8 rows,
                    # estimated HOST-side; they double as the
                    # forwarding marshal source (host["set_regs"])
                    regs = got[:len(srows)]
                    host["set_ests"] = hll_mod.estimate_np_rows(regs)
                    host["set_regs"] = regs
                elif sp["form"] == "plane":
                    # the resident plane's estimates, every row's
                    host["set_ests"] = got[srows]
                else:
                    # the uploaded bucket's: its padding rows stop here
                    host["set_ests"] = got[:len(srows)]
            elif sp:
                host["set_ests"] = sp["ests"]
            if nd == 0:
                if set_t0 is not None:
                    seg["device_s"] = set_wait
                return host
            t0 = time.perf_counter()
            qs, piped, readback = None, [], 0
            for tier in pend["tiers"]:
                cs = tier["chunk_stats"]
                if cs is not None:
                    # pipelined chunks fetch one at a time so each
                    # chunk's residual wait is attributable (the
                    # readbacks were started at dispatch via
                    # copy_to_host_async)
                    fetched = []
                    for i, o in enumerate(tier["outs"]):
                        t1 = time.perf_counter()
                        fetched.append(serving.fetch(o))
                        cs[i]["wait_s"] = time.perf_counter() - t1
                    piped += cs
                else:
                    fetched = serving.fetch(tuple(tier["outs"]))
                ev = (fetched[0] if len(fetched) == 1
                      else np.concatenate(fetched))
                readback += ev.nbytes
                sel = tier["sel"]
                if sel is None:
                    qs = ev[:nd, :n_cols]
                else:
                    # a tier answers for its own rows of the part
                    if qs is None:
                        qs = np.empty((nd, n_cols), ev.dtype)
                    qs[sel] = ev[:len(sel), :n_cols]
            if piped:
                seg["device_chunks"] = piped
                # device_s stays the residual blocking wait; the
                # device-BUSY window since the first chunk's dispatch —
                # which OVERLAPS the later chunks' layout/dispatch
                # segments, the causal proof of the pipeline — lands in
                # device_window_s and is what the flight recorder lays
                # as the flush.seg.device span
                seg["device_window_s"] = (
                    time.perf_counter()
                    - (pend["t_dispatch0"] if set_t0 is None else set_t0))
            # the flush's blocking wait on the device: the digest
            # outputs and, before them, the set estimates
            seg["device_s"] = time.perf_counter() - t0 + set_wait
            seg["readback_bytes"] = (seg.get("readback_bytes", 0)
                                     + readback)
            # what a forwarding tier's digest export gathers from
            host["dense_tiers"] = [
                (t["sel"], t["first_dev"], t["uniform"])
                for t in pend["tiers"]]
            # counts/sums come from the exact f64 host accumulators on
            # BOTH staging shapes (they cover every staged point,
            # merged-digest centroids included) — sourcing only the
            # uniform path from the host made a series' reported
            # count/sum precision shift whenever staging flipped
            # uniform/non-uniform between intervals (ADVICE r5 #6); the
            # device ev columns carry the same totals in eval dtype and
            # remain the meshed path's (collective-reduced) source
            host["qs"] = qs
            host["counts"] = np.asarray(dpart["d_weight"], np.float64)
            host["sums"] = np.asarray(dpart["d_sum"], np.float64)
            return host
        else:
            t0 = time.perf_counter()
            flat_t, set_regs_t = serving.fetch(
                (pend["flat_dev"], pend["set_regs_dev"]))
            seg["device_s"] = time.perf_counter() - t0
            seg["readback_bytes"] = (
                seg.get("readback_bytes", 0) + flat_t.nbytes
                + (0 if set_regs_t is None else set_regs_t.nbytes))
            ev_t, c_hi_t, c_lo_t, set_ests_t, uts = \
                serving.unpack_outputs(flat_t, pend["k_rows"], n_cols,
                                       pend["k2"], pend["n_sets_cap"])
            host["unique_ts"] = uts
            crows, srows = pend["crows"], pend["srows"]
            if len(crows):
                host["c_hi"] = c_hi_t.astype(np.float64)[crows]
                host["c_lo"] = c_lo_t.astype(np.float64)[crows]
            if len(srows):
                host["set_ests"] = set_ests_t[srows]
            if set_regs_t is not None:
                host["set_regs"] = set_regs_t.reshape(
                    len(pend["ps"]), -1)[:len(srows)]
            host["dense_dev"] = pend["dense_dev"]
            if nd == 0:
                return host
            ev = ev_t
        host["qs"] = ev[:nd, :n_cols]
        host["counts"] = ev[:nd, n_cols].astype(np.float64)
        host["sums"] = ev[:nd, n_cols + 1].astype(np.float64)
        return host

    def _snapshot_and_reset(self) -> dict:
        """Under lock: sync staging, cut every arena's part of touched
        rows (arena.snapshot_part: copies, never aliases of live
        state), reset, end the interval (the idle GC, the eviction
        passes) and, last, look whether the import row cache survives
        it.  The parts' columns are the arenas' own.  The
        clock is read per family and step, never per row, for
        flush_dispatch's split of the columns span."""
        clock = time.perf_counter
        arenas = self._arenas()
        copied = -sum(ar.staged_copied_bytes for _, ar in arenas)
        t = clock()
        for _, ar in arenas:
            ar.sync()
        sync_s = clock() - t
        snap = {"counts": (self.processed, self.imported),
                "ledger": self._ledger}
        self.processed = 0
        self.imported = 0
        self._ledger = _new_ledger()
        snap["have_uts"] = self.unique_ts is not None
        if self.unique_ts is not None:
            uts = self.unique_ts.regs
            self.unique_ts = hll_mod.HLLSketch(self.unique_ts.p)
        else:
            uts = None
        snap["uts_host"] = None
        if self.mesh is None:
            # nothing to pmax over without a mesh: estimate on host (the
            # digest-only program never sees these registers).  The
            # register array is swapped out here; the O(m) estimate
            # reduction runs in flush_dispatch AFTER the lock releases
            # (blocking-propagation finding: ingest threads were queued
            # behind a numpy reduction over 16 KiB of registers)
            snap["uts_raw"] = uts
            snap["uts_regs"] = None
        else:
            snap["uts_regs"] = self._uts_lanes(uts)

        staged_s = 0.0
        points = regrows = 0
        by_family = {"cut": {}, "reset": {}, "end": {}}
        t = clock()
        for name, ar in arenas:
            snap[name] = ar.snapshot_part()
            t, t_was = clock(), t
            # take_staged() inside it is the staged span's, not the cut's
            by_family["cut"][name] = t - t_was - ar.snapshot_staged_s
            staged_s += ar.snapshot_staged_s
            points += ar.snapshot_staged_points
            regrows += ar.snapshot_staged_regrows
            copied += ar.staged_copied_bytes
        # what the syncs and the take_staged hand-offs took, for
        # flush_dispatch's split of snapshot_s, and what they handed
        # over (STAGED_LEDGER_KEYS)
        snap["part_seconds"] = (sync_s, staged_s)
        snap["staged_ledger"] = (points, copied, regrows)

        # key-dictionary fingerprints for the multi-controller lockstep
        # gather — snapshotted HERE, under the lock and before the GC in
        # end_interval, so the flush gathers one coherent (keyset,
        # key->row) pair per family (a lock-free read during _run_flush
        # could tear against a concurrent registration and trip a
        # spurious lockstep error)
        snap["key_fingerprints"] = {
            ar.family: (ar.keyset_checksum, ar.key_checksum)
            for _, ar in arenas}

        t = clock()
        deaths = {}
        for name, ar in arenas:
            ar.reset_rows(snap[name]["rows"])
            t_reset = clock()
            deaths[name] = ar.end_interval()
            t, t_was = clock(), t
            by_family["reset"][name] = t_reset - t_was
            by_family["end"][name] = t - t_reset
        snap["set_lane_stats"] = self.sets.take_lane_stats()
        snap["hot_lane_stats"] = self.digests.take_hot_stats()
        if self.cardinality is not None:
            self._cardinality_end_interval()
        if self.cubes is not None:
            self._cube_end_interval()
        # after everything that can recycle a row (the idle GC above,
        # the two eviction passes' release_keys)
        t = clock()
        reason = self._check_import_row_cache()
        cache_s = clock() - t
        if reason:
            snap["ledger"]["import_row_cache_clears"] = 1
            by_family["cache"] = {reason: cache_s}
        snap["columns_seconds"] = (cache_s, *(
            sum(by_family[step].values())
            for step in ("cut", "reset", "end")))
        by_family["deaths"] = {name: n for name, n in deaths.items() if n}
        snap["columns_by_family"] = by_family
        # a row's life over this interval (KEY_LEDGER_KEYS), after
        # everything that can free a row
        life = tuple(sum(getattr(ar, attr) for _, ar in arenas)
                     for attr in ("births", "recycled", "grows"))
        snap["key_ledger"] = (
            *(now - was for now, was in zip(life, self._key_life_seen)),
            sum(len(ar.kdict) for _, ar in arenas),
            sum(ar.hw for _, ar in arenas))
        self._key_life_seen = life
        return snap

    def _check_import_row_cache(self) -> str:
        """The cut's look at the V1 import's identity->row cache (under
        the lock): clear all of it if any arena put a row back on its
        free list since the last cut ("recycled": a cached row may now
        hold another key), or if it holds more than twice the keys of
        the arenas it serves ("size": import_pb_batch keys on the tags
        in wire order, so a sender that permutes them mints many keys
        for one row).  Returns the reason, "" when the cache stays —
        as it does at every cut of a fleet whose keys are steady."""
        recycled = sum(ar.recycled for _, ar in self._arenas())
        moved = recycled != self._import_recycled_seen
        self._import_recycled_seen = recycled
        cache = self._import_row_cache
        if not cache:
            return ""           # a node that imports nothing
        if moved:
            reason = "recycled"
        elif len(cache) > 2 * (len(self.counters.kdict)
                               + len(self.gauges.kdict)
                               + len(self.sets.kdict)
                               + len(self.digests.kdict)):
            reason = "size"
        else:
            return ""
        cache.clear()
        return reason

    def _arena_for_type(self, mtype: str, key: Optional[MetricKey] = None):
        if mtype == sm.TYPE_COUNTER:
            return self.counters
        if mtype == sm.TYPE_GAUGE:
            return self.gauges
        if mtype == sm.TYPE_SET:
            return self.sets
        # histogram / timer: family dispatch decides (the cardinality
        # release path passes the key so evicted moments/compactor
        # rows release from the arena that actually holds them)
        if key is not None and self.family_dispatch:
            tags = key.joined_tags.split(",") if key.joined_tags else []
            fam = self._family_of(key, tags)
            if fam == "moments":
                return self.moments
            if fam == "compactor":
                return self.compactors
        return self.digests

    def _cardinality_end_interval(self) -> None:
        """Apply the guard's count-ordered eviction pass (under the
        aggregator lock, after the snapshot has copied and reset the
        arenas).  The callback is the `arena.evict` failpoint edge and
        the eager row release; a fault injected there aborts the pass
        with the quota state untouched — reclamation is delayed one
        interval (idle GC still bounds the rows), never corrupted."""
        def release(dks):
            from veneur_tpu import failpoints
            failpoints.inject("arena.evict")
            by_arena: dict = {}
            for dk in dks:
                arena = self._arena_for_type(dk[0].type, dk[0])
                if dk[0].type in (sm.TYPE_HISTOGRAM, sm.TYPE_TIMER):
                    # release from the arena that ACTUALLY holds the
                    # key, not the one the rules would pick today:
                    # payload-routed imports can land a key in the
                    # moments/compactor arena on a tier whose rules
                    # say tdigest (the supported cross-tier
                    # rules-mismatch), and a rules-derived release
                    # would silently skip it
                    if dk in self.moments.kdict:
                        arena = self.moments
                    elif dk in self.compactors.kdict:
                        arena = self.compactors
                    elif dk in self.digests.kdict:
                        arena = self.digests
                by_arena.setdefault(id(arena), (arena, []))[1].append(dk)
            for arena, lst in by_arena.values():
                arena.release_keys(lst)

        try:
            self.cardinality.end_interval(release)
        except Exception as e:
            import logging
            logging.getLogger("veneur_tpu.core.aggregator").warning(
                "cardinality eviction pass aborted (%s); retrying next "
                "interval", e)

    def _cube_end_interval(self) -> None:
        """The cube maintainer's promotion pass — same shape and
        failure contract as the guard's: a fault on the arena.evict
        edge aborts with the cube membership untouched."""
        def release(dks):
            from veneur_tpu import failpoints
            failpoints.inject("arena.evict")
            by_arena: dict = {}
            for dk in dks:
                # cube rows are histogram/timer keys; release from the
                # arena that ACTUALLY holds the key (family-rules drift
                # across restarts must not skip a release)
                if dk in self.moments.kdict:
                    arena = self.moments
                elif dk in self.compactors.kdict:
                    arena = self.compactors
                elif dk in self.digests.kdict:
                    arena = self.digests
                else:
                    continue    # never materialized (pure candidate)
                by_arena.setdefault(id(arena), (arena, []))[1].append(dk)
            for arena, lst in by_arena.values():
                arena.release_keys(lst)

        try:
            self.cubes.end_interval(release)
        except Exception as e:
            import logging
            logging.getLogger("veneur_tpu.core.aggregator").warning(
                "cube eviction pass aborted (%s); retrying next "
                "interval", e)

    # -- emitters ----------------------------------------------------------

    @staticmethod
    def _scalar_family(res, part, vals, is_local, now, mtype, fwd):
        """Shared counter/gauge emission: forward global-only rows when
        local, columnar-emit the rest as one segment.  Names/tags/scopes
        come from the arena's columnar metadata (no per-row object
        walks)."""
        bases = part["names"].tolist()
        tags = part["tags"].tolist()
        if is_local:
            glob = part["scopes"] == int(MetricScope.GLOBAL_ONLY)
            if glob.any():
                for i in np.nonzero(glob)[0].tolist():
                    res.forward.append(fwd(bases[i], tags[i], vals[i]))
                sel = np.nonzero(~glob)[0]
                res.metrics.add_segment(sm.MetricSegment(
                    bases, tags, "", vals[sel], mtype, now, sel=sel))
                return
        res.metrics.add_segment(sm.MetricSegment(
            bases, tags, "", np.asarray(vals, np.float64), mtype, now))

    def _emit_counters(self, res, snap, host, is_local, now):
        part = snap["counters"]
        rows = part["rows"]
        if len(rows) == 0:
            return
        if part["host_totals"] is not None:
            vals = part["host_totals"]  # float64 host sum (no mesh)
        else:
            # device psum'd hi/lo planes -> exact totals (< 2^48)
            vals = host["c_hi"] * serving.COUNTER_SPLIT + host["c_lo"]
        self._scalar_family(
            res, part, vals, is_local, now, sm.COUNTER,
            lambda name, tags, v: sm.ForwardMetric(
                name=name, tags=tags, kind=sm.TYPE_COUNTER,
                scope=MetricScope.GLOBAL_ONLY, counter_value=int(v)))

    def _emit_gauges(self, res, snap, is_local, now):
        part = snap["gauges"]
        if len(part["rows"]) == 0:
            return
        self._scalar_family(
            res, part, part["values"], is_local, now, sm.GAUGE,
            lambda name, tags, v: sm.ForwardMetric(
                name=name, tags=tags, kind=sm.TYPE_GAUGE,
                scope=MetricScope.GLOBAL_ONLY, gauge_value=float(v)))

    def _emit_status(self, res, snap, now):
        part = snap["status"]
        for row, name, tags, val in zip(part["rows"], part["names"],
                                        part["tags"], part["values"]):
            res.metrics.append(sm.InterMetric(
                name=name, timestamp=now, value=float(val),
                tags=tags, type=sm.STATUS,
                message=part["messages"][int(row)],
                hostname=part["hostnames"][int(row)]))

    def _emit_sets(self, res, snap, host, is_local, now):
        part = snap["sets"]
        rows = part["rows"]
        if len(rows) == 0:
            return
        ests = host["set_ests"]
        if part.get("legacy_ests") is not None:
            # migration lane: hash-incompatible legacy sketches never mix
            # registers; the emitted estimate is max(primary, legacy)
            ests = np.maximum(np.asarray(ests, np.float64),
                              part["legacy_ests"])
        bases = part["names"].tolist()
        tags = part["tags"].tolist()
        if is_local:
            mixed = part["scopes"] == int(MetricScope.MIXED)
            if mixed.any():
                # merged registers for forwarding: host snapshot copies
                # (mesh-less) or the packed device readback (meshed) —
                # [n, m] either way, never the whole register state
                regs = part.get("host_regs")
                if regs is None:
                    regs = host["set_regs"]
                for i in np.nonzero(mixed)[0].tolist():
                    res.forward.append(sm.ForwardMetric(
                        name=bases[i], tags=tags[i],
                        kind=sm.TYPE_SET, scope=MetricScope.MIXED,
                        hll=hll_mod.marshal(regs[i])))
                sel = np.nonzero(~mixed)[0]
                res.metrics.add_segment(sm.MetricSegment(
                    bases, tags, "", ests[sel], sm.GAUGE, now, sel=sel))
                return
        res.metrics.add_segment(sm.MetricSegment(
            bases, tags, "", ests, sm.GAUGE, now))

    def _emit_digests(self, res, snap, host, is_local, now):
        part = snap["digests"]
        rows = part["rows"]
        if len(rows) == 0:
            return
        n = len(rows)
        qs = host["qs"]
        counts = host["counts"]
        sums = host["sums"]
        d_min = np.asarray(part["d_min"], np.float64)
        d_max = np.asarray(part["d_max"], np.float64)
        d_rsum = np.asarray(part["d_rsum"], np.float64)

        bases = part["names"].tolist()
        tags = part["tags"].tolist()
        if is_local:
            forwarded = part["scopes"] != int(MetricScope.LOCAL_ONLY)
        else:
            forwarded = np.zeros(n, bool)

        if forwarded.any():
            # wire centroids for forwarding: ONE bounded compress over the
            # forwarded rows' staged points (MergingDigest.Data,
            # merging_digest.go:474-483) — compute and readback scale with
            # the forwarded subset
            fidx = np.nonzero(forwarded)[0]
            compression = self.digests.compression
            ccap = self.digests.ccap
            # the dense operand(s) the flush evaluated, each with the
            # positions in the part of the rows it holds (None: all, in
            # order — the single operand, and the meshed one)
            tiers = host.get("dense_tiers") or [
                (None, host["dense_dev"], False)]
            sel_mean = sel_weight = None
            for sel, (dvd, dwd), t_uniform in tiers:
                if sel is None:
                    mine, local = slice(None), fidx
                else:
                    at = np.full(n, -1, np.int64)
                    at[sel] = np.arange(len(sel))
                    mine = np.nonzero(at[fidx] >= 0)[0]
                    local = at[fidx[mine]]
                depth = int(dvd.shape[1])
                # Chunk the export so the fused [rows, depth, ccap]
                # comparison-sum inside td.compress stays under an
                # element budget whether or not XLA fuses it (a 100k-key
                # forwarding tier with 512-deep staging would otherwise
                # imply a multi-GB logical intermediate).  Full chunks
                # share one compiled shape; only the final partial chunk
                # pads down.
                max_rows = _EXPORT_ELEM_BUDGET // max(1, depth * ccap)
                max_rows = 1 << max(3, max_rows.bit_length() - 1)
                m_parts, w_parts = [], []
                for off in range(0, len(local), max_rows):
                    chunk = local[off:off + max_rows]
                    fpad = self._padded_rows(chunk)
                    if t_uniform:
                        # depth-vector build: dwd holds per-row depths;
                        # the 0/1 weights rebuild on device for the
                        # subset
                        mexp, wexp = serving.digest_export_uniform(
                            dvd, dwd, jnp.asarray(fpad), compression,
                            ccap)
                    else:
                        mexp, wexp = serving.digest_export(
                            dvd, dwd, jnp.asarray(fpad), compression,
                            ccap)
                    fetched_m, fetched_w = serving.fetch((mexp, wexp))
                    m_parts.append(fetched_m[:len(chunk)])
                    w_parts.append(fetched_w[:len(chunk)])
                if not m_parts:
                    continue
                if sel_mean is None:
                    sel_mean = np.zeros((len(fidx), ccap),
                                        m_parts[0].dtype)
                    sel_weight = np.zeros_like(sel_mean)
                sel_mean[mine] = np.concatenate(m_parts)
                sel_weight[mine] = np.concatenate(w_parts)
            fwd = res.forward
            kinds = part["kinds"]
            scopes = part["scopes"]
            for j, i in enumerate(fidx.tolist()):
                w = sel_weight[j]
                occ = w > 0
                fwd.append(sm.ForwardMetric(
                    name=bases[i], tags=tags[i], kind=kinds[i],
                    scope=MetricScope(int(scopes[i])),
                    digest_means=sel_mean[j][occ].tolist(),
                    digest_weights=w[occ].tolist(),
                    digest_min=float(d_min[i]), digest_max=float(d_max[i]),
                    digest_sum=float(sums[i]), digest_rsum=float(d_rsum[i]),
                    digest_compression=compression))

        self._emit_histo_aggregates(res, part, qs, counts, sums,
                                    is_local, now, forwarded)

    def _emit_vectors(self, res, part, qs, arena, is_local, now):
        """Moments- and compactor-family emission: the same aggregate /
        percentile surface as the digest family (sinks cannot tell the
        families apart), with forwarding as wire VECTORS in the
        ForwardMetric field the arena names (`wire_field`) instead of
        centroid lists — the moments vector, or the compactor's
        self-describing header + level items (the folded flush state,
        shared with the eval via arena.fold_flush's part cache)."""
        counts = np.asarray(part["d_weight"], np.float64)
        sums = np.asarray(part["d_sum"], np.float64)
        if is_local:
            forwarded = part["scopes"] != int(MetricScope.LOCAL_ONLY)
        else:
            forwarded = np.zeros(len(part["rows"]), bool)
        if forwarded.any():
            fidx = np.nonzero(forwarded)[0]
            vecs = arena.assemble_vectors(part, part["staged"], fidx)
            bases = part["names"].tolist()
            tags = part["tags"].tolist()
            kinds = part["kinds"]
            scopes = part["scopes"]
            for j, i in enumerate(fidx.tolist()):
                res.forward.append(sm.ForwardMetric(
                    name=bases[i], tags=tags[i], kind=kinds[i],
                    scope=MetricScope(int(scopes[i])),
                    **{arena.wire_field: vecs[j].tolist()}))
        self._emit_histo_aggregates(res, part, qs, counts, sums,
                                    is_local, now, forwarded)

    def _emit_histo_aggregates(self, res, part, qs, counts, sums,
                               is_local, now, forwarded):
        """The aggregate/percentile emission shared by both histogram
        sketch families: sparse-emission guards per aggregate mirror
        Histo.Flush (samplers/samplers.go:359-514) as column masks over
        the snapshot's host scalar copies."""
        l_weight = np.asarray(part["l_weight"], np.float64)
        l_min = np.asarray(part["l_min"], np.float64)
        l_max = np.asarray(part["l_max"], np.float64)
        l_sum = np.asarray(part["l_sum"], np.float64)
        l_rsum = np.asarray(part["l_rsum"], np.float64)
        d_min = np.asarray(part["d_min"], np.float64)
        d_max = np.asarray(part["d_max"], np.float64)
        d_rsum = np.asarray(part["d_rsum"], np.float64)
        bases = part["names"].tolist()
        tags = part["tags"].tolist()
        use_global = part["scopes"] == int(MetricScope.GLOBAL_ONLY)

        # alive: rows that emit anything locally (a forwarded global-only
        # row emits nothing here, flusher.go:57-74); sparse-emission
        # guards per aggregate mirror Histo.Flush
        # (samplers/samplers.go:359-514) as column masks.
        alive = ~(forwarded & use_global)
        aggs = self.aggregates.value
        A = sm.Aggregate
        inf = np.inf
        batch = res.metrics

        def seg(mask, values, suffix, mtype=sm.GAUGE):
            if mask.all():
                batch.add_segment(sm.MetricSegment(
                    bases, tags, suffix, values, mtype, now))
                return
            sel = np.nonzero(mask)[0]
            if sel.size:
                batch.add_segment(sm.MetricSegment(
                    bases, tags, suffix, values[sel], mtype, now, sel=sel))

        with np.errstate(divide="ignore", invalid="ignore"):
            if aggs & A.MAX:
                seg(alive & (use_global | ((l_max > -inf) & (l_max < inf))),
                    np.where(use_global, d_max, l_max), ".max")
            if aggs & A.MIN:
                seg(alive & (use_global | ((l_min > -inf) & (l_min < inf))),
                    np.where(use_global, d_min, l_min), ".min")
            if aggs & A.SUM:
                seg(alive & ((l_sum != 0) | use_global),
                    np.where(use_global, sums, l_sum), ".sum")
            if aggs & A.AVERAGE:
                seg(alive & (use_global | ((l_sum != 0) & (l_weight != 0))),
                    np.where(use_global, sums / counts, l_sum / l_weight),
                    ".avg")
            if aggs & A.COUNT:
                seg(alive & ((l_weight != 0) | use_global),
                    np.where(use_global, counts, l_weight), ".count",
                    sm.COUNTER)
            if aggs & A.MEDIAN:
                # emitted unconditionally when configured
                # (samplers.go:466-479)
                seg(alive, qs[:, 0], ".median")
            if aggs & A.HARMONIC_MEAN:
                # d_rsum == 0 with nonzero count -> nan, not inf
                # (samplers.go hmean guard)
                g_hmean = np.where(d_rsum != 0, counts / d_rsum, np.nan)
                seg(alive & (use_global | ((l_rsum != 0) & (l_weight != 0))),
                    np.where(use_global, g_hmean, l_weight / l_rsum),
                    ".hmean")
            # reference percentile naming: int(p*100), samplers.go:495-507
            emit_pcts = alive & ~forwarded
            for j, p in enumerate(self.percentiles):
                seg(emit_pcts, qs[:, j + 1], f".{int(p * 100)}percentile")
