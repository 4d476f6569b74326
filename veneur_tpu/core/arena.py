"""Metric arenas: the TPU-native replacement for per-key sampler objects.

The reference holds one Go object per metric key in 13 scope-partitioned
maps (`worker.go:58-82`) and walks them sequentially at flush.  Here each
sampler family is an *arena*: a key dictionary mapping
(MetricKey, scope) -> row index, plus batched state where row i of a set of
device tensors / numpy arrays is that key's sampler.  Ingest appends to
host-side COO staging buffers; `sync()` scatters staging into dense wave
tensors and folds them into device state with one XLA call per wave; flush
evaluates every key at once (quantiles, estimates) and emits only rows
touched this interval.

Scope partitioning (`worker.go:106-175` Upsert) becomes per-row metadata
(kind, scope) instead of separate maps, so one device call covers all
histogram classes.

Min/max/reciprocal-sum are tracked host-side as ground truth: re-ingesting a
forwarded digest's centroids reproduces its quantile shape but not its exact
scalar accessors (a centroid mean never reaches the true min/max), so
imports merge the wire scalars directly (`worker.go:402-459` semantics) and
flush pushes them into the device state before evaluation.

Rows persist across intervals (the reference re-allocates maps each flush,
`worker.go:462-481`); `reset()` zeroes state and the touched mask instead,
and idle keys are garbage-collected after IDLE_GC_INTERVALS flushes so
cardinality churn cannot grow the arena unboundedly.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.parallel import serving
from veneur_tpu.samplers import metric_key
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope
from veneur_tpu.sketches import hll as hll_mod
from veneur_tpu.sketches import tdigest as td

# staged depth beyond which a row pre-reduces into <= C weighted points
# (bounds the flush dense matrix width)
DENSE_DEPTH_CAP = 512

# The hot-key lane's compress tile: EVERY pre-reduction launch
# (DigestArena._pre_reduce -> serving.partial_digests) has this one
# shape, whatever a drain tick carries, so the program is known at boot
# (prewarm_hot launches it once) and no window compiles it.  A row's
# backlog splits into "virtual rows" of HOT_TILE_WIDTH points, a pass
# launches them HOT_TILE_ROWS at a time, zero-padded.  Sized from the
# tick of a skewed node (200,000 timer samples per 2 s over 50,000 keys,
# Zipf 0.99, drained every 0.2 s): ~10 rows stand past the cap at a
# tick, the hottest with ~3,500 new samples on its <= ccap centroids;
# a key that brings more in one tick takes more virtual rows, not a
# wider program.  2 x 0.5 MiB of f32 operands a launch.
HOT_TILE_ROWS = 32
HOT_TILE_WIDTH = 4096
# The flush operand's tiers (DigestArena.deep_rows): touched rows with
# more staged points than this — or re-staged by a pre-reduce, whose
# centroids carry weights — are built apart from the long tail, in the
# weighted form, DENSE_DEPTH_CAP deep, at pow2 row buckets no smaller
# than DEEP_TIER_MIN_ROWS; the tail keeps the form its weights allow at
# its own depth.  64: under Zipf 0.99 the keys above 64 samples are
# ~0.9 % of the touched ones (276 of 30,000) and the tail's operand at
# depth 64 is an eighth of the single one's 512; at 8 the deep tier
# would hold 2,250 rows x 512 x 2 arrays (more bytes than the tail), at
# 128 the tail doubles for 140 rows.  512 rows: the deep count of that
# profile (276 +- 10) sits beside the 256 bucket's edge, and a program
# per side of an edge is a compile in a window; below the floor the
# padding is 1 MiB an array.
DEEP_TIER_THRESHOLD = 64
DEEP_TIER_MIN_ROWS = 512
# flush intervals a key may stay untouched before its row is recycled
IDLE_GC_INTERVALS = 10


class CheckpointIncompatible(ValueError):
    """The checkpoint was written under a different sketch
    configuration (set precision, digest compression): restoring it
    would mix unmergeable state.  Raised by restore_precheck BEFORE
    any arena mutates, so the caller can cold-start cleanly."""


def _pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length() if n > 1 else 1

_INITIAL_CAPACITY = 1024

# Fewer touched set rows than this are estimated by numpy (0.3 ms a row
# on a v5e's host, outside the lock) instead of on the chip: a launch
# costs the host more than that below a few rows, and the one row a
# server's own telemetry touches in a flush out of a hundred
# (ssf.names_unique, sampled at 1 %) must not compile a [1, m] program
# inside that flush.  8 = the rows of one f32 tile.  Host registers
# estimate from the snapshot's copy (aggregator._dispatch_sets); a
# resident arena gathers the rows' u8 registers back first
# (SetArena.lane_gather, at row buckets its boot has launched).
SET_DEVICE_MIN_ROWS = 8


class _StagedPoints:
    """One interval's consolidated staged points: three preallocated
    columns (rows int64, vals / wts float64) of which `[:n]` are filled,
    in arrival order.  sync() writes each drain tick's points into the
    next free slots (`extend`), so the flush's cut joins nothing:
    `take()` hands the filled views to the part — theirs from then on —
    and leaves a fresh, untouched buffer behind."""

    __slots__ = ("rows", "vals", "wts", "n", "regrows", "copied_bytes")

    POINT_BYTES = 24
    # smallest buffer: an arena that stages nothing holds 96 KiB
    FLOOR = 4096

    def __init__(self):
        self.n = 0
        # buffer doublings since the last take()
        self.regrows = 0
        # running total of bytes of already-accumulated points copied
        # again (a regrow, a replace); a flush that reads it before its
        # sync() and after its cut knows what the cut copied
        self.copied_bytes = 0
        self._alloc(self.FLOOR)

    def _alloc(self, capacity: int) -> None:
        # np.empty: no page is touched until a tick writes it
        self.rows = np.empty(capacity, np.int64)
        self.vals = np.empty(capacity, np.float64)
        self.wts = np.empty(capacity, np.float64)

    def views(self):
        """The filled (rows, vals, wts); views of the live buffer."""
        n = self.n
        return self.rows[:n], self.vals[:n], self.wts[:n]

    def extend(self, k: int):
        """Append k slots and return them as (rows, vals, wts) views
        for the caller to fill; a full buffer doubles until they fit
        (the points before them are copied across)."""
        n, cap = self.n, len(self.rows)
        if n + k > cap:
            old = self.views()
            while cap < n + k:
                cap *= 2
                self.regrows += 1
            self._alloc(cap)
            for dst, src in zip((self.rows, self.vals, self.wts), old):
                dst[:n] = src
            self.copied_bytes += n * self.POINT_BYTES
        self.n = n + k
        return (self.rows[n:n + k], self.vals[n:n + k],
                self.wts[n:n + k])

    def replace(self, rows, vals, wts) -> None:
        """Make these points the whole content (a pre-reduce's
        re-staged points, a restored checkpoint).  The arrays may be
        built from views(), not be them."""
        self.n = 0
        for dst, src in zip(self.extend(len(rows)), (rows, vals, wts)):
            dst[:] = src
        self.copied_bytes += len(rows) * self.POINT_BYTES

    def take(self):
        """Hand the interval's points over: the filled views, whose
        buffer the arena lets go of.  The next buffer is sized to what
        this interval reached (a power of two), so a steady fleet's
        second interval regrows nothing; an interval that staged
        nothing keeps its buffer."""
        n = self.n
        self.regrows = 0
        if n == 0:
            z = np.zeros(0)
            return z.astype(np.int64), z, z
        staged = self.views()
        self.n = 0
        self._alloc(max(self.FLOOR, _pow2(n)))
        return staged


@dataclass
class RowMeta:
    key: MetricKey
    tags: list[str]
    scope: MetricScope
    # pre-rendered flush names, filled lazily (e.g. "x.max", "x.50percentile")
    names: dict[str, str] = field(default_factory=dict)

    def flush_name(self, suffix: str) -> str:
        n = self.names.get(suffix)
        if n is None:
            n = self.key.name + suffix if suffix else self.key.name
            self.names[suffix] = n
        return n


class _ArenaBase:
    """Key dictionary + row lifecycle shared by all arenas."""

    _TRACK_KIND = False  # DigestArena opts in (kind_col)

    # what the family is called outside the arena: its key fingerprint
    # in the lockstep gather and its keys_* flush segment
    family = ""
    # the per-row host columns a flush snapshots and then resets, as
    # (attribute, the value an untouched row holds), indexed by row
    # along axis 0.  The ONE list of a family's columns: snapshot_part,
    # reset_rows and the histogram arenas' growth walk it, subclasses
    # extend it, and the part's keys are the attribute names — the
    # format import_contrib / fold_flush / assemble_vectors here,
    # query/rings.py and retention/timeline.py read back.
    _COLUMNS: tuple = ()
    # what the last snapshot_part() says of its staged points (a
    # family that stages none leaves them 0): the seconds take_staged()
    # took, the points it handed to the flush, and the doublings of the
    # accumulator's buffer over the interval
    snapshot_staged_s = 0.0
    snapshot_staged_points = 0
    snapshot_staged_regrows = 0
    # running total of bytes of accumulated staged points copied again
    # (_StagedPoints.copied_bytes); _snapshot_and_reset reads it on both
    # sides of the cut
    staged_copied_bytes = 0

    def __init__(self, capacity: int = _INITIAL_CAPACITY):
        self.capacity = capacity
        self.kdict: dict[tuple[MetricKey, MetricScope], int] = {}
        self.meta: list[Optional[RowMeta]] = [None] * capacity
        # columnar metadata mirrors (name / tags / kind / scope int) —
        # flush snapshots fancy-index these instead of walking RowMeta
        # objects row by row (at 1M keys those Python loops were ~30% of
        # the flush's host time)
        self.name_col = np.empty(capacity, object)
        self.tags_col = np.empty(capacity, object)
        # per-row hash(name) mirror: the live query plane's window
        # slots look keys up by ONE vectorized int64 compare instead
        # of an object-array scan (or a per-slot python hash pass) —
        # maintained incrementally here because rows persist across
        # intervals, so the cost is O(1) per registration, not
        # O(keys) per query slot.  Process-local (python str hashes),
        # never serialized.
        self.name_hash_col = np.zeros(capacity, np.int64)
        # only the digest snapshot consumes per-row kinds (histogram vs
        # timer for forwarding); other families skip the column
        self.kind_col = (np.empty(capacity, object)
                         if self._TRACK_KIND else None)
        self.scope_col = np.zeros(capacity, np.int8)
        self.touched = np.zeros(capacity, bool)
        self.idle = np.zeros(capacity, np.int32)
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        # high-water row: every row ever handed out lies in [0, hw) —
        # the free list hands rows out in ascending order and freed rows
        # are re-used first, so a row at or beyond hw has never held a
        # key (touched false, no name) and end_interval leaves it alone.
        # Raised in row_for, rebuilt in restore_state, never lowered.
        self.hw = 0
        # running total of rows put back on the free list (the idle GC
        # in end_interval + release_keys): a cache of key -> row kept
        # outside the arena is good for as long as this has not moved
        self.recycled = 0
        # running totals of the other two events of a row's life: rows
        # handed to a new key (row_for's allocation) and doublings of the
        # arena (_grow).  births - recycled moves with len(kdict); the
        # aggregator's cut reads all three (KEY_LEDGER_KEYS)
        self.births = 0
        self.grows = 0
        self.lock = threading.Lock()
        # incremental fingerprints of the key dictionary: XOR-folds of
        # fnv1a per live mapping.  keyset_checksum covers the keys alone
        # (fnv1a_64 of identity_string); key_checksum additionally binds
        # each key's row (the same hash continued over "\0<row>").  A
        # row's pair is hashed ONCE, at its birth (row_for,
        # restore_state), and kept in fp_col[row] for as long as the row
        # is live: XOR is its own inverse and neither the key nor the
        # row changes in between, so a death folds out exactly what the
        # birth folded in and hashes nothing (_recycle: one vector XOR
        # over the freed rows).  A free row holds (0, 0), so each
        # checksum is the XOR of its whole column.  Process state like
        # name_hash_col, never checkpointed.  Multi-
        # controller serving gathers both per flush (lockstep contract,
        # parallel/multihost.py): identical key sets with different row
        # assignments — the silent-misalignment case — fail loudly,
        # while ring-style asymmetric registration (a key registered
        # only on its owning controller, destinations.go:129-142's
        # membership analog) differs in BOTH and stays legal
        self.key_checksum = 0
        self.keyset_checksum = 0
        # [:, 0] folds into keyset_checksum, [:, 1] into key_checksum
        self.fp_col = np.zeros((capacity, 2), np.uint64)
        # (key_checksum, rendered key-table arrays): the checkpoint
        # writer's memo — a stable key table re-renders nothing
        self._ckpt_render_cache = None

    def _fold_in_key(self, key: MetricKey, scope: MetricScope,
                     row: int) -> None:
        """A key's birth on `row`: hash its two fingerprints (the one
        place they are computed), keep them in fp_col and fold them
        into the checksums."""
        keys_fp, rows_fp = metric_key.key_fingerprints(
            metric_key.identity_string(key, scope), row)
        fp = self.fp_col[row]
        fp[0] = keys_fp
        fp[1] = rows_fp
        self.keyset_checksum ^= keys_fp
        self.key_checksum ^= rows_fp

    def _recycle(self, rows: np.ndarray) -> None:
        """Put `rows` (an index array: distinct, live until now, in the
        order the free list is to take them) back on the free list — the
        part of a death that has a vector form, shared by the idle GC
        and release_keys: the rows' stored fingerprints XOR out of the
        checksums in one reduce, every metadata column is cleared by
        one indexed write.  What has none (meta[row] = None, which
        NativeIngest's row binding is revalidated against, and the
        kdict entry) is the caller's one tight pass."""
        fp = np.bitwise_xor.reduce(self.fp_col[rows], axis=0)
        self.keyset_checksum ^= int(fp[0])
        self.key_checksum ^= int(fp[1])
        self.fp_col[rows] = 0
        self.name_col[rows] = None
        self.tags_col[rows] = None
        self.name_hash_col[rows] = 0
        if self.kind_col is not None:
            self.kind_col[rows] = None
        self.scope_col[rows] = 0
        self.idle[rows] = 0
        self._free.extend(rows.tolist())
        self.recycled += len(rows)

    def _init_mesh_lanes(self, mesh, family: str) -> int:
        """Shared mesh plumbing for device-resident arenas: validate the
        key-shard divisibility, record the lane sharding, and return the
        replica count (= lane count for families whose lanes exist only to
        feed the replica axis)."""
        self.mesh = mesh
        if mesh is not None:
            from veneur_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS
            if self.capacity % mesh.shape[SHARD_AXIS]:
                raise ValueError(
                    f"{family} arena capacity {self.capacity} not "
                    f"divisible by {mesh.shape[SHARD_AXIS]} key shards")
            n_replicas = mesh.shape[REPLICA_AXIS]
        else:
            n_replicas = 1
        self._lane_shd = serving.lane_sharding(mesh)
        return n_replicas

    @staticmethod
    def _pad_pow2(n: int) -> int:
        return 1 << (n - 1).bit_length() if n > 1 else 1

    def _reset_index(self, rows: np.ndarray) -> np.ndarray:
        """Padded row-index vector for the device reset kernels.  Empty
        `rows` yields [0]: zeroing row 0 is a no-op THEN (an interval that
        touched no rows left every row zeroed by its own flush), but the
        kernel still returns a FRESH buffer — required so the flush
        snapshot never aliases the live buffer a later donating ingest
        kernel would delete."""
        n = len(rows)
        if n == 0:
            return np.zeros(1, np.int64)
        padded = self._pad_pow2(n)
        idx = np.empty(padded, np.int64)
        idx[:n] = rows
        idx[n:] = rows[0]
        return idx

    # key -> context manager around a device launch the arena makes
    # itself (set lane kernels, the hot-key compress); the aggregator
    # installs its _CompileGuard here so that such a program's first
    # launch is a compile event like any other
    compile_guard = None

    def _guard(self, key):
        """The owner's compile guard around one of the arena's own
        launches (the aggregator's _CompileGuard: a first launch of
        `key` counts as a compile event); nothing for a bare arena."""
        if self.compile_guard is None:
            return contextlib.nullcontext()
        return self.compile_guard(key)

    def _grow(self) -> None:
        old = self.capacity
        self.capacity = old * 2
        self.grows += 1
        self.meta.extend([None] * old)
        self.name_col = np.concatenate(
            [self.name_col, np.empty(old, object)])
        self.tags_col = np.concatenate(
            [self.tags_col, np.empty(old, object)])
        self.name_hash_col = np.concatenate(
            [self.name_hash_col, np.zeros(old, np.int64)])
        self.fp_col = np.concatenate(
            [self.fp_col, np.zeros((old, 2), np.uint64)])
        if self.kind_col is not None:
            self.kind_col = np.concatenate(
                [self.kind_col, np.empty(old, object)])
        self.scope_col = np.concatenate(
            [self.scope_col, np.zeros(old, np.int8)])
        self.touched = np.concatenate([self.touched, np.zeros(old, bool)])
        self.idle = np.concatenate([self.idle, np.zeros(old, np.int32)])
        self._free.extend(range(self.capacity - 1, old - 1, -1))
        self._grow_state(old)

    def _grow_state(self, old: int) -> None:
        """Extend the family's per-row state by `old` fresh rows."""
        for name, fill in self._COLUMNS:
            setattr(self, name, self._grown(getattr(self, name), old, fill))

    @staticmethod
    def _grown(a: np.ndarray, old: int, fill) -> np.ndarray:
        return np.concatenate(
            [a, np.full((old,) + a.shape[1:], fill, a.dtype)])

    def row_for(self, key: MetricKey, scope: MetricScope,
                tags: list[str]) -> int:
        """Upsert: find or allocate the row for (key, scope)."""
        dk = (key, scope)
        row = self.kdict.get(dk)
        if row is None:
            if not self._free:
                self._grow()
            row = self._free.pop()
            if row >= self.hw:
                self.hw = row + 1
            self.kdict[dk] = row
            self._fold_in_key(key, scope, row)
            self.meta[row] = RowMeta(key=key, tags=tags, scope=scope)
            self.name_col[row] = key.name
            self.tags_col[row] = tags
            self.name_hash_col[row] = hash(key.name)
            if self.kind_col is not None:
                self.kind_col[row] = key.type
            self.scope_col[row] = int(scope)
            self.idle[row] = 0
            self.births += 1
        self.touched[row] = True
        return row

    def touched_rows(self) -> np.ndarray:
        return np.nonzero(self.touched)[0]

    def sync(self) -> None:
        """Fold staged input into the arena's state (the staging
        families override; the rest stage nothing)."""

    def staged_count(self) -> int:
        return 0

    def snapshot_part(self) -> dict:
        """The flush's cut of this arena — call under the aggregator
        lock, after sync() and before reset_rows(part["rows"]): the
        touched rows' identity columns and a copy of every _COLUMNS
        column, none aliasing live state, so the flush evaluates and
        emits from the part outside the lock while ingest refills the
        rows.  Families add what their flush needs on top."""
        rows = self.touched_rows()
        part = {"rows": rows,
                "names": self.name_col[rows],
                "tags": self.tags_col[rows],
                "scopes": self.scope_col[rows]}
        if self.kind_col is not None:
            part["kinds"] = self.kind_col[rows]
        # indexing by a row array already copies: the part owns these
        for name, _ in self._COLUMNS:
            part[name] = getattr(self, name)[rows]
        return part

    def reset_rows(self, rows: np.ndarray) -> None:
        if len(rows) == 0:
            return
        for name, fill in self._COLUMNS:
            getattr(self, name)[rows] = fill

    def release_keys(self, dks: list) -> int:
        """Immediately recycle the rows of the given (MetricKey, scope)
        pairs (cardinality eviction, core/cardinality.py): the eager
        form of the idle GC in end_interval, for keys a tenant's budget
        has demoted to the rollup, and the same batched free (_recycle:
        the fingerprints kept from each row's birth fold back out, no
        key is hashed again), with the touched flags and the rows' state
        reset on top.  Call under the aggregator lock, after the flush
        snapshot has copied everything it needs.  Returns rows
        released."""
        rows: list[int] = []
        kdict, meta = self.kdict, self.meta
        for dk in dks:
            row = kdict.pop(dk, None)
            if row is not None:
                meta[row] = None
                rows.append(row)
        if rows:
            idx = np.asarray(rows, np.int64)
            self._recycle(idx)
            self.touched[idx] = False
            self.reset_rows(idx)
        return len(rows)

    # -- crash checkpoint (core/checkpoint.py) -----------------------------

    def checkpoint_state(self) -> tuple[dict, dict]:
        """(meta, arrays) snapshot of the key table + family state —
        call under the aggregator lock, after sync().  Restoring the
        pair into a FRESH arena reproduces rows bit-exactly (same row
        indices, same registers/scalars/staging), which is what makes
        the crash chaos arms' conservation checks EXACT rather than
        approximate."""
        return self.checkpoint_render(self.checkpoint_capture())

    def checkpoint_capture(self) -> dict:
        """The lock-held half of a checkpoint: C-speed copies only
        (dict items list, fancy-indexed columns, family state arrays) —
        the per-key Python rendering runs lock-free afterwards, so the
        ingest path is never queued behind it."""
        items = list(self.kdict.items())
        rows = (np.fromiter((r for _, r in items), np.int64,
                            len(items))
                if items else np.zeros(0, np.int64))
        extra: dict = {}
        self._checkpoint_extra(extra)
        return {"items": items,
                "tags": (self.tags_col[rows].copy() if len(items)
                         else np.empty(0, object)),
                "rows": rows,
                "idle": self.idle[rows].copy(),
                "touched": self.touched[rows].copy(),
                "capacity": int(self.capacity),
                "key_checksum": self.key_checksum,
                "arrays": self._checkpoint_arrays(),
                "extra": extra}

    def checkpoint_render(self, cap: dict) -> tuple[dict, dict]:
        """The lock-free half: render the captured key table to numpy
        string/int arrays (no per-key JSON — a 20k-row table rendered
        as nested lists held the GIL long enough to tax concurrent
        flushes).  The rendered table is CACHED on the arena's
        incremental key fingerprint: a steady-state key table (the
        production common case) re-renders nothing, so periodic
        checkpoints cost array copies, not O(keys) Python.  MetricKey
        fields are immutable and tags lists are never mutated in
        place, so the captured refs stay coherent after the lock
        releases."""
        cached = self._ckpt_render_cache
        # the checksum binds the key->row MAP but is order-insensitive
        # (XOR fold): a GC + re-registration can return to the same
        # checksum with a permuted kdict order, which would misalign
        # the cached name/row arrays with this capture's idle/touched
        # vectors — so a hit additionally requires elementwise row
        # agreement (rows are unique, so equal rows in equal positions
        # + an equal map pins every position to the same key)
        if (cached is not None and cached[0] == cap["key_checksum"]
                and np.array_equal(cached[1]["key_rows"],
                                   cap["rows"])):
            key_arrays = cached[1]
        else:
            items = cap["items"]
            n = len(items)
            names = [None] * n
            types = [None] * n
            jtags = [None] * n
            scopes = np.zeros(n, np.int8)
            for i, ((key, scope), _row) in enumerate(items):
                names[i] = key.name
                types[i] = key.type
                jtags[i] = key.joined_tags
                scopes[i] = int(scope)
            def _str_arr(lst):
                return (np.asarray(lst, dtype=np.str_) if lst
                        else np.zeros(0, "<U1"))

            key_arrays = {
                "key_names": _str_arr(names),
                "key_types": _str_arr(types),
                "key_jtags": _str_arr(jtags),
                # tags lists join on "," (a tag cannot carry a comma
                # on the wire, and an empty-string tag cannot occur,
                # so "" unambiguously encodes the empty list)
                "key_tags": _str_arr(
                    [",".join(t) if t else "" for t in cap["tags"]]),
                "key_scopes": scopes,
                "key_rows": cap["rows"],
            }
            self._ckpt_render_cache = (cap["key_checksum"], key_arrays)
        arrays = dict(cap["arrays"])
        arrays.update(key_arrays)
        arrays["key_idle"] = cap["idle"]
        arrays["key_touched"] = cap["touched"]
        meta = {"capacity": cap["capacity"]}
        meta.update(cap["extra"])
        return meta, arrays

    def restore_state(self, meta: dict, arrays: dict) -> None:
        """Rebuild from a checkpoint into this (fresh) arena: rows land
        at their recorded indices, fingerprints re-fold, the free list
        excludes live rows."""
        if self.kdict:
            raise RuntimeError(
                "checkpoint restore requires a fresh arena "
                f"({len(self.kdict)} keys already registered)")
        while self.capacity < int(meta["capacity"]):
            self._grow()
        used = set()
        for name, mtype, jtags, scope_i, row, tags_joined, idle, \
                touched in zip(arrays["key_names"],
                               arrays["key_types"],
                               arrays["key_jtags"],
                               arrays["key_scopes"],
                               arrays["key_rows"],
                               arrays["key_tags"],
                               arrays["key_idle"],
                               arrays["key_touched"]):
            key = MetricKey(str(name), str(mtype), str(jtags))
            scope = MetricScope(int(scope_i))
            row = int(row)
            tags = (str(tags_joined).split(",") if tags_joined
                    else [])
            self.kdict[(key, scope)] = row
            self.meta[row] = RowMeta(key=key, tags=list(tags),
                                     scope=scope)
            self.name_col[row] = key.name
            self.tags_col[row] = list(tags)
            self.name_hash_col[row] = hash(key.name)
            if self.kind_col is not None:
                self.kind_col[row] = key.type
            self.scope_col[row] = int(scope)
            self.idle[row] = int(idle)
            self.touched[row] = bool(touched)
            self._fold_in_key(key, scope, row)
            used.add(row)
        self._free = [r for r in range(self.capacity - 1, -1, -1)
                      if r not in used]
        self.hw = max(used) + 1 if used else 0
        self._restore_arrays(meta, arrays)

    def _checkpoint_arrays(self) -> dict:
        raise NotImplementedError

    def _checkpoint_extra(self, meta: dict) -> None:
        """Hook for family-specific JSON-able state."""

    def restore_precheck(self, meta: dict, arrays: dict) -> None:
        """Raise CheckpointIncompatible BEFORE any mutation when this
        checkpoint cannot restore into the current configuration
        (changed sketch parameters across the restart).  The
        aggregator prechecks EVERY family first, so a mismatch is a
        clean cold start instead of a half-restored arena set."""

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        raise NotImplementedError

    @staticmethod
    def _restore_into(dst: np.ndarray, src: np.ndarray) -> None:
        """Copy a checkpointed array into the (possibly larger) live
        array along the capacity (last) axis."""
        if src.ndim == 1:
            dst[:len(src)] = src
        else:
            dst[:, :src.shape[1]] = src

    def end_interval(self) -> int:
        """Reset touched state and GC idle rows (after flush), over the
        rows ever handed out ([0, hw)) and not over the capacity: a
        pre-sized arena that holds a handful of keys pays for the
        handful.  The interval's dead rows are freed in one batch
        (_recycle): their fingerprints were hashed at their birth and
        are read back from fp_col, so the cut hashes no key and writes
        no column row by row; an interval without a death stops at the
        empty candidate list.  Returns the rows freed (also added to
        `recycled`)."""
        hw = self.hw
        touched = self.touched[:hw]
        idle = self.idle[:hw]
        # touched rows restart at 0, every other row counts one more
        idle += 1
        np.putmask(idle, touched, 0)
        # liveness from the name column (live rows always have a name),
        # asked only of the rows old enough to die: a freed row under hw
        # keeps counting and stays a candidate the name test drops; in a
        # steady interval there is no candidate and no object compare
        cand = np.nonzero(idle >= IDLE_GC_INTERVALS)[0]
        dead = (cand[self.name_col[cand] != None]  # noqa: E711
                if len(cand) else cand)
        if len(dead):
            kdict, meta = self.kdict, self.meta
            for row in dead.tolist():
                m = meta[row]
                meta[row] = None
                del kdict[(m.key, m.scope)]
            self._recycle(dead)
        touched[:] = False
        return len(dead)


class CounterArena(_ArenaBase):
    """int64 accumulators (samplers/samplers.go:97-150); mixed and
    global-only counters share the arena, separated by row scope.

    Values accumulate host-side in float64 (integer-exact below 2^53) as
    `[R_c, capacity]` lane stripes, lane = row % R_c.  At flush the lanes
    upload as (hi, lo) float32 planes (value = hi * 2^24 + lo, each plane
    exact below 2^24 so the device total is exact below 2^48) and the
    family flush program reduces them with `lax.psum` over the mesh replica
    axis — the device-collective form of Counter.Merge
    (`samplers/samplers.go:143-145` / `worker.go:402-459`)."""

    family = "counter"

    def __init__(self, capacity: int = _INITIAL_CAPACITY, mesh=None):
        super().__init__(capacity)
        self.n_lanes = self._init_mesh_lanes(mesh, "counter")
        self.values = np.zeros((self.n_lanes, capacity), np.float64)
        self._zero_planes = None

    def _grow_state(self, old: int) -> None:
        self.values = np.concatenate(
            [self.values, np.zeros((self.n_lanes, old), np.float64)], axis=1)

    def sample(self, row: int, value: float, sample_rate: float) -> None:
        # Sample divides by rate at ingest (samplers.go:109-111)
        self.values[row % self.n_lanes, row] += int(value / sample_rate)

    def sample_batch(self, rows: np.ndarray, vals: np.ndarray) -> None:
        """Columnar pre-divided counter increments (native drain path)."""
        np.add.at(self.values, (rows % self.n_lanes, rows), vals)

    def merge(self, row: int, value: int) -> None:
        self.values[row % self.n_lanes, row] += value

    def merge_batch(self, rows: np.ndarray, vals: np.ndarray) -> None:
        """Vectorized import merges (duplicate rows accumulate)."""
        np.add.at(self.values, (rows % self.n_lanes, rows), vals)
        self.touched[rows] = True

    def snapshot_part(self) -> dict:
        part = super().snapshot_part()
        if self.mesh is None:
            # no mesh => no psum; total the float64 host stripes directly
            # (exact below 2^53, and no plane upload at all)
            part["host_totals"] = self.values.sum(axis=0)[part["rows"]]
            vals = None
        else:
            # cheap host copy of the lane stripes, before reset zeroes
            # them in place; the (hi, lo) split and the upload wait for
            # the dispatch, outside the lock
            part["host_totals"] = None
            vals = self.values.copy()
        part["planes"] = lambda: self.planes_from(vals)
        return part

    def planes_from(self, vals: np.ndarray):
        """Device-put the (hi, lo) split of snapshotted lane stripes as
        `[R_c, capacity, 2]` f32 for the family flush program (runs
        outside the lock; the split + transfer are the expensive part).

        Without a mesh there is nothing to psum over, so the aggregator
        totals the float64 host stripes directly (exact below 2^53) and
        the program receives a cached [R_c, 1, 2] zero plane — no upload
        at all."""
        if self._lane_shd is None:
            if self._zero_planes is None:
                self._zero_planes = serving.put(
                    np.zeros((self.n_lanes, 1, 2), np.float32), None)
            return self._zero_planes
        hi = np.floor(vals / serving.COUNTER_SPLIT)
        lo = vals - hi * serving.COUNTER_SPLIT
        planes = np.stack([hi, lo], axis=-1).astype(np.float32)
        return serving.put(planes, self._lane_shd)

    def reset_rows(self, rows: np.ndarray) -> None:
        self.values[:, rows] = 0

    def _checkpoint_arrays(self) -> dict:
        return {"values": self.values.copy()}

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        src = arrays["values"]
        if src.shape[0] != self.n_lanes:
            # lane layout changed across the restart (mesh reconfig):
            # fold the lanes down — counter lanes are additive
            folded = np.zeros((self.n_lanes, src.shape[1]), np.float64)
            for lane in range(src.shape[0]):
                folded[lane % self.n_lanes] += src[lane]
            src = folded
        self._restore_into(self.values, src)


class GaugeArena(_ArenaBase):
    """Last-write-wins gauges (samplers/samplers.go:152-202)."""

    family = "gauge"
    _COLUMNS = (("values", 0),)

    def __init__(self, capacity: int = _INITIAL_CAPACITY):
        super().__init__(capacity)
        self.values = np.zeros(capacity, np.float64)

    def sample(self, row: int, value: float) -> None:
        self.values[row] = value

    def merge(self, row: int, value: float) -> None:
        self.values[row] = value  # Merge overwrites (samplers.go:200-202)

    def merge_batch(self, rows: np.ndarray, vals: np.ndarray) -> None:
        """Vectorized import merges.  Gauge Merge is last-write-wins
        (samplers.go:200-202), and NumPy documents the result of fancy
        assignment with repeated indices as UNSPECIFIED — so duplicate
        rows are deduplicated to their final occurrence before the
        assignment instead of relying on in-practice ordering."""
        if len(rows) > 1:
            # np.unique on the reversed rows keeps the FIRST reversed
            # occurrence = the LAST original one
            uniq, rev_first = np.unique(rows[::-1], return_index=True)
            if len(uniq) != len(rows):
                rows = uniq
                vals = vals[len(vals) - 1 - rev_first]
        self.values[rows] = vals
        self.touched[rows] = True

    def _checkpoint_arrays(self) -> dict:
        return {"values": self.values.copy()}

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        self._restore_into(self.values, arrays["values"])


class StatusArena(_ArenaBase):
    """Service-check state: last value + message + hostname
    (samplers/samplers.go:210-231)."""

    family = "status"
    _COLUMNS = (("values", 0),)

    def __init__(self, capacity: int = _INITIAL_CAPACITY):
        super().__init__(capacity)
        self.values = np.zeros(capacity, np.float64)
        self.messages: dict[int, str] = {}
        self.hostnames: dict[int, str] = {}

    def sample(self, row: int, value: float, message: str,
               hostname: str) -> None:
        self.values[row] = value
        self.messages[row] = message
        self.hostnames[row] = hostname

    def snapshot_part(self) -> dict:
        part = super().snapshot_part()
        rows = part["rows"].tolist()
        part["messages"] = {r: self.messages.get(r, "") for r in rows}
        part["hostnames"] = {r: self.hostnames.get(r, "") for r in rows}
        return part

    def reset_rows(self, rows: np.ndarray) -> None:
        super().reset_rows(rows)
        for r in rows:
            self.messages.pop(int(r), None)
            self.hostnames.pop(int(r), None)

    def _checkpoint_arrays(self) -> dict:
        return {"values": self.values.copy()}

    def _checkpoint_extra(self, meta: dict) -> None:
        meta["messages"] = {str(r): m for r, m in self.messages.items()}
        meta["hostnames"] = {str(r): h
                             for r, h in self.hostnames.items()}

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        self._restore_into(self.values, arrays["values"])
        self.messages = {int(r): str(m)
                         for r, m in (meta.get("messages") or {}).items()}
        self.hostnames = {int(r): str(h)
                          for r, h in
                          (meta.get("hostnames") or {}).items()}


class SetArena(_ArenaBase):
    """Unique-count sets as HLL register rows (Set sampler,
    `samplers/samplers.go:242-311`).

    Where the registers live follows what the configuration says of the
    arena's size.  At its default size an unmeshed arena keeps them on
    the HOST (`[capacity, m]` uint8): inserts are one vectorized
    `np.maximum.at`, merges a register-wise max, and a flush uploads a
    copy of the touched rows for the device estimate
    (`aggregator._dispatch_sets`) — a node's thousand set keys are
    16 MB a flush.

    Pre-sized for its deployment (`set_arena_initial_capacity`), or
    with `flush_resident_arenas`, an unmeshed arena is RESIDENT: the
    registers are one `[1, capacity, m]` device lane — 1 GiB at 65,536
    rows of p = 14, which no flush could copy under the lock, upload
    and read back.  Staged inserts and forwarded sparse sketches
    scatter-max into it as (row, register, rank) triples during the
    interval, forwarded dense sketches merge as rows, and the flush
    estimates on the device and reads back 4 bytes a row
    (`serving.set_estimate_plane`); only a tier that forwards its sets
    reads u8 registers back.  Launches come at fixed lengths
    (`serving.LANE_SCATTER_SMALL`, `LANE_SCATTER_CHUNK`,
    `LANE_MERGE_CHUNK`), so the programs a window can need are known at
    boot (`prewarm_lanes`).

    With a mesh the registers are device-resident lane stripes
    `[R_s, S, m]` sharded (rows over 'shard', lanes over 'replica');
    staged inserts scatter-max into a round-robin lane and the flush
    program pmaxes the lanes over ICI and estimates all rows at once —
    the collective form of Set.Merge (`samplers/samplers.go:299-311`).
    """

    family = "set"
    # the interval's lane work: triples scattered and the launches that
    # took, dense rows merged, and the nanoseconds sync() held its
    # caller (the aggregator lock) for
    LANE_STATS = ("scatter_points", "scatter_launches", "merge_rows",
                  "sync_ns")

    def __init__(self, capacity: int = _INITIAL_CAPACITY,
                 precision: int = hll_mod.DEFAULT_PRECISION, mesh=None,
                 legacy_migration: bool = False,
                 resident: bool = False):
        super().__init__(capacity)
        self.precision = precision
        self.m = 1 << precision
        self.n_lanes = self._init_mesh_lanes(mesh, "set")
        # resident: an UNMESHED arena keeps its registers on the device
        # too — the same [1, capacity, m] lane machinery the meshed
        # tiers run (scatter-max sync, pinned snapshots, the copying-
        # kernel donation fallback), with one lane and no sharding.
        # The aggregator asks for it when the configuration pre-sized
        # the arena (set_arena_initial_capacity) or says
        # flush_resident_arenas; see the class docstring.
        self.resident = bool(resident) and mesh is None
        # Rolling-upgrade migration lane (hll_legacy_migration): legacy
        # 'VH' imports carry blake2b-hashed members which do NOT union
        # meaningfully with metro-hashed registers (the same member lands
        # on different registers, inflating the union up to ~2x).  When
        # enabled, legacy sketches merge into a host-side side lane and
        # the flush estimate is max(primary, legacy) per row — exact for
        # the common upgrade case (both fleet halves see the same member
        # population), a lower bound otherwise, and never hash-mixing.
        self.legacy_migration = legacy_migration
        self._legacy_regs: dict[int, np.ndarray] = {}
        if mesh is None and not self.resident:
            self.host_regs = np.zeros((capacity, self.m), np.uint8)
            self.lanes_regs = None
        else:
            self.host_regs = None
            self.lanes_regs = self._zero_lanes()
        # count of dispatched-but-not-yet-fetched flushes holding a
        # lane-register snapshot (incremented by snapshot_lanes(),
        # decremented by unpin_lanes() after the flush fetch): while
        # nonzero — or always on the CPU backend, whose runtime
        # mismanages donated sharded update chains (see
        # serving.lane_donation_ok) — lane updates route through the
        # COPYING kernels so the in-flight program's snapshot is never
        # handed to XLA as scratch.
        self._snapshot_inflight = 0
        self._seq = 0
        # staging: raw hashes per batch (vectorized split at sync)
        self._stage_rows: list[int] = []
        self._stage_hashes: list[int] = []
        # pre-hashed array staging from the native ingest engine
        self._stage_chunks: list[tuple[np.ndarray, np.ndarray]] = []
        # (rows, register index, rank) arrays staged as what they are:
        # forwarded sparse sketches, decoded by the import's wire scan
        self._stage_triples: list[tuple] = []
        # imported dense register rows, unioned host-side until sync
        self._merge_rows: dict[int, np.ndarray] = {}
        # what the lane syncs of the open interval did, for the flush
        # timeline's row (take_lane_stats, at the cut)
        self._lane_stats = dict.fromkeys(self.LANE_STATS, 0)

    def _zero_lanes(self):
        """A fresh all-zero lane plane of the live one's shape and
        sharding; unmeshed it is made on the device (no gigabyte of host
        zeros to upload)."""
        shape = (self.n_lanes, self.capacity, self.m)
        if self._lane_shd is None:
            return jnp.zeros(shape, jnp.uint8)
        return serving.put(np.zeros(shape, np.uint8), self._lane_shd)

    def take_lane_stats(self) -> dict:
        """The open interval's LANE_STATS, handed over and zeroed (call
        under the aggregator lock, at the cut)."""
        out, self._lane_stats = (self._lane_stats,
                                 dict.fromkeys(self.LANE_STATS, 0))
        return out

    def _grow_state(self, old: int) -> None:
        if self.host_regs is not None:
            self.host_regs = np.concatenate(
                [self.host_regs,
                 np.zeros((old, self.m), np.uint8)], axis=0)
            return
        import jax
        if jax.process_count() > 1:
            # one-sided growth would diverge the controllers' global
            # shapes; multi-process meshes must pre-size instead
            raise RuntimeError(
                "set arena cannot grow under a multi-process mesh; "
                "pre-size with set_arena_initial_capacity")
        nr = np.zeros((self.n_lanes, self.capacity, self.m), np.uint8)
        nr[:, :old] = np.asarray(self.lanes_regs)
        self.lanes_regs = serving.put(nr, self._lane_shd)

    def sample(self, row: int, member: str) -> None:
        self._stage_rows.append(row)
        self._stage_hashes.append(hll_mod.hash64(member.encode()))

    def stage_hash_batch(self, rows: np.ndarray, hashes: np.ndarray) -> None:
        """Stage members already metro-hashed by the native ingest engine."""
        self._stage_chunks.append((rows, hashes))

    def stage_triples(self, rows: np.ndarray, idx: np.ndarray,
                      rank: np.ndarray) -> None:
        """Stage decoded sparse sketches: register `idx[i]` of arena
        row `rows[i]` is at least `rank[i]` (a 17-member set is 17
        triples, never a 16 KiB row)."""
        self._stage_triples.append((rows, idx, rank))

    def staged_count(self) -> int:
        return (len(self._stage_rows)
                + sum(len(r) for r, _ in self._stage_chunks)
                + sum(len(r) for r, _, _ in self._stage_triples)
                + len(self._merge_rows))

    def merge(self, row: int, payload: bytes) -> None:
        other, legacy = hll_mod.unmarshal_ex(payload)
        if len(other) != self.m:
            raise ValueError(
                f"set sketch of {len(other)} registers, arena has "
                f"{self.m}")
        if legacy and self.legacy_migration:
            mine = self._legacy_regs.get(row)
            if mine is None:
                self._legacy_regs[row] = other.copy()
            else:
                np.maximum(mine, other, out=mine)
            return
        self.merge_regs(row, other)

    def merge_regs(self, row: int, regs: np.ndarray) -> None:
        """Union one dense register row `[m]` into `row` (a forwarded
        dense sketch; the caller's array is kept)."""
        mine = self._merge_rows.get(row)
        if mine is None:
            self._merge_rows[row] = regs
        else:
            np.maximum(mine, regs, out=mine)

    def legacy_estimates(self, rows: np.ndarray) -> "np.ndarray | None":
        """Per-row LogLog-Beta estimates of the migration side lane (0
        where a row has no legacy imports), or None when the lane is
        idle.  Call under the aggregator lock at snapshot time."""
        if not self._legacy_regs:
            return None
        out = np.zeros(len(rows), np.float64)
        hits = [(i, self._legacy_regs[int(r)])
                for i, r in enumerate(rows)
                if int(r) in self._legacy_regs]
        if hits:
            ests = hll_mod.estimate_np_rows(
                np.stack([regs for _, regs in hits]))
            for (i, _), e in zip(hits, ests):
                out[i] = e
        return out

    def _scatter_pad(self, n: int) -> int:
        """The length one launch of a tick's `n` staged triples is
        padded to.  Unmeshed resident: the closed set prewarm_lanes
        launched (serving.LANE_SCATTER_*), a large tick taking several
        launches.  Meshed: the tick's own power of two, one launch."""
        if not self.resident:
            return self._pad_pow2(n)
        if n == 1:
            return 1
        return (serving.LANE_SCATTER_SMALL
                if n <= serving.LANE_SCATTER_SMALL
                else serving.LANE_SCATTER_CHUNK)

    def _staged_triples(self, padded: bool = False):
        """Consume raw staging into (count, rows, register index, rank)
        arrays, joined once.  `padded`: zero-padded up to a multiple of
        the count's launch length (_scatter_pad) — the device launches'
        chunks are then views of them, not second copies."""
        parts_r: list[np.ndarray] = []
        parts_h: list[np.ndarray] = []
        if self._stage_rows:
            parts_r.append(np.asarray(self._stage_rows, np.int64))
            parts_h.append(np.asarray(self._stage_hashes, np.uint64))
            self._stage_rows, self._stage_hashes = [], []
        for r, h in self._stage_chunks:
            parts_r.append(r)
            parts_h.append(h)
        self._stage_chunks = []
        triples = self._stage_triples
        self._stage_triples = []
        if parts_r:
            triples.append((np.concatenate(parts_r), *hll_mod.split_hashes(
                np.concatenate(parts_h), self.precision)))
        n = sum(len(r) for r, _, _ in triples)
        pad = self._scatter_pad(n) if padded else 1
        out = [np.zeros(-(-n // pad) * pad, dt)
               for dt in (np.int32, np.int32, np.uint8)]
        for buf, col in zip(out, zip(*triples)):
            np.concatenate(col, out=buf[:n], casting="same_kind")
        return (n, *out)

    def sync(self) -> None:
        """Fold staged inserts and imported rows into the registers."""
        staged = (self._stage_rows or self._stage_chunks
                  or self._stage_triples)
        if not staged and not self._merge_rows:
            return
        t0 = time.perf_counter_ns()
        stats = self._lane_stats
        if self.host_regs is not None:
            if staged:
                n, rows, idx, rank = self._staged_triples()
                np.maximum.at(self.host_regs, (rows, idx), rank)
                stats["scatter_points"] += n
            for row, regs in self._merge_rows.items():
                np.maximum(self.host_regs[row], regs,
                           out=self.host_regs[row])
        else:
            # device lanes (padding entries are all-zero ranks and
            # registers, which max() ignores: the padding only buys
            # program reuse)
            if staged:
                self._scatter_staged(*self._staged_triples(padded=True))
            if self._merge_rows:
                self._merge_staged(sorted(self._merge_rows.items()))
        stats["merge_rows"] += len(self._merge_rows)
        self._merge_rows = {}
        stats["sync_ns"] += time.perf_counter_ns() - t0

    def _scatter_staged(self, n: int, pr, pi, pk) -> None:
        """Launch the padded triples (_staged_triples), _scatter_pad at
        a time."""
        pad = self._scatter_pad(n)
        for off in range(0, len(pr), pad):
            lane = self._seq % self.n_lanes
            self._seq += 1
            self.lanes_regs = self._lane_scatter(
                self.lanes_regs, pr[off:off + pad], pi[off:off + pad],
                pk[off:off + pad], lane, self._lane_donate_ok())
            self._lane_stats["scatter_launches"] += 1
        self._lane_stats["scatter_points"] += n

    def _merge_staged(self, items: list) -> None:
        """Launch the imported dense rows: LANE_MERGE_CHUNK at a time
        on the resident plane, the tick's own power of two meshed."""
        chunk = (serving.LANE_MERGE_CHUNK if self.resident
                 else self._pad_pow2(len(items)))
        for off in range(0, len(items), chunk):
            pr = np.zeros(chunk, np.int32)
            mat = np.zeros((chunk, self.m), np.uint8)
            for i, (row, regs) in enumerate(items[off:off + chunk]):
                pr[i] = row
                mat[i] = regs
            lane = self._seq % self.n_lanes
            self._seq += 1
            self.lanes_regs = self._lane_merge(
                self.lanes_regs, pr, mat, lane, self._lane_donate_ok())

    def _lane_scatter(self, lanes, pr, pi, pk, lane: int, donate: bool):
        """Launch the scatter-max of padded (row, register, rank)
        triples into `lane` of `lanes` — sync()'s launch, and the one
        prewarm_lanes compiles."""
        scatter = (serving.set_lane_scatter if donate
                   else serving.set_lane_scatter_copy)
        with self._guard(("set_lane_scatter", lanes.shape, len(pr), lane,
                          donate)):
            return scatter(lanes, jnp.asarray(pr), jnp.asarray(pi),
                           jnp.asarray(pk), lane)

    def _lane_merge(self, lanes, pr, mat, lane: int, donate: bool):
        """Launch the register-wise max of padded dense rows into
        `lane` of `lanes`."""
        merge = (serving.set_lane_merge_rows if donate
                 else serving.set_lane_merge_rows_copy)
        with self._guard(("set_lane_merge", lanes.shape, len(pr), lane,
                          donate)):
            return merge(lanes, jnp.asarray(pr), jnp.asarray(mat), lane)

    def _lane_reset(self, lanes, rows: np.ndarray):
        """Launch the row reset (a fresh buffer, `rows` zeroed): the
        meshed lanes by a padded index vector, the unmeshed resident
        plane by a keep-mask of its one shape."""
        if self.resident:
            keep = np.ones(lanes.shape[1], np.uint8)
            keep[rows] = 0
            with self._guard(("set_lane_reset", lanes.shape)):
                return serving.set_reset_mask(lanes, jnp.asarray(keep))
        idx = self._reset_index(rows)
        with self._guard(("set_lane_reset", lanes.shape, len(idx))):
            return serving.set_reset_rows(lanes, jnp.asarray(idx))

    def lane_estimate(self, lanes):
        """Launch the whole-plane estimate on a pinned lane snapshot:
        [capacity] f32, asynchronous (the flush of a resident arena
        that forwards no set)."""
        with self._guard(("set_estimate_plane", lanes.shape)):
            return serving.set_estimate_plane(lanes)

    def lane_gather(self, lanes, padded_rows: np.ndarray):
        """Launch the u8 readback gather of `padded_rows`' lane-union
        registers from a pinned lane snapshot (a forwarding tier's
        marshal source; a handful of rows for the numpy estimate)."""
        with self._guard(("set_gather_rows", lanes.shape,
                          len(padded_rows))):
            return serving.set_gather_rows(lanes,
                                           jnp.asarray(padded_rows))

    def prewarm_lanes(self) -> int:
        """Compile the lane programs whose shapes are known at boot, by
        running each once on a scratch all-zero lane buffer of the live
        one's shape and sharding (never the live registers: a donating
        kernel would have to run under the aggregator lock).  Returns
        the programs launched.

        Meshed: the per-flush row reset at the one-row index an
        untouched interval and a single touched row both use, and the
        one-sample scatter — the server's own 1 %-sampled
        `ssf.names_unique` SET — into every lane, in the donating form
        and the copying one a pinned snapshot forces.  What a fleet
        sends beyond that compiles at its first launch, counted by the
        guard.

        Unmeshed resident — a closed set, so that no window compiles:
        the mask reset; the scatter at its three launch lengths
        (_scatter_pad) and the dense-row merge at LANE_MERGE_CHUNK, each
        donating and copying; the whole-plane estimate; and the u8
        gather at the row buckets below the device estimate's floor (an
        interval that brought only the server's own set)."""
        if self.lanes_regs is None:
            return 0
        n = 1
        lanes = self._lane_reset(self._zero_lanes(),
                                 np.zeros(0, np.int64))
        forms = (True, False) if serving.lane_donation_ok() else (False,)
        pads = ((1, serving.LANE_SCATTER_SMALL, serving.LANE_SCATTER_CHUNK)
                if self.resident else (1,))
        for lane in range(self.n_lanes):
            for donate in forms:
                for pad in pads:
                    zeros = np.zeros(pad, np.int32)
                    lanes = self._lane_scatter(
                        lanes, zeros, zeros, np.zeros(pad, np.uint8),
                        lane, donate)
                    n += 1
                if self.resident:
                    lanes = self._lane_merge(
                        lanes, np.zeros(serving.LANE_MERGE_CHUNK, np.int32),
                        np.zeros((serving.LANE_MERGE_CHUNK, self.m),
                                 np.uint8), lane, donate)
                    n += 1
        if self.resident:
            outs = [self.lane_estimate(lanes)]
            bucket = 1
            while bucket <= _pow2(SET_DEVICE_MIN_ROWS - 1):
                outs.append(self.lane_gather(
                    lanes, np.zeros(bucket, np.int32)))
                bucket *= 2
            n += len(outs)
            for out in outs:
                out.block_until_ready()
        lanes.block_until_ready()
        return n

    def _lane_donate_ok(self) -> bool:
        """In-place (donating) lane updates are legal only when no
        dispatched flush still reads a register snapshot AND the backend
        handles donation correctly (serving.lane_donation_ok)."""
        return (not self._snapshot_inflight
                and serving.lane_donation_ok())

    def snapshot_lanes(self) -> jnp.ndarray:
        """Meshed only: immutable ref to the current lane registers (sync
        first); the flush program pmax-merges and estimates them.  Marks
        a flush IN FLIGHT until unpin_lanes(): from dispatch to fetch
        the launched program reads this snapshot, and a donating
        in-place lane update in that window corrupts it (updates route
        through the copying kernels while the count is nonzero)."""
        self.sync()
        self._snapshot_inflight += 1
        return self.lanes_regs

    def unpin_lanes(self, ref=None) -> None:
        """Release one snapshot hold (call once the flush that took it
        has fetched its outputs — the program can no longer read the
        registers, so in-place donating updates are safe again)."""
        del ref  # kept for call-site symmetry; holds are counted
        self._snapshot_inflight = max(0, self._snapshot_inflight - 1)

    def host_regs_copy(self, rows: np.ndarray) -> np.ndarray:
        """Mesh-less only: the given rows' registers, gathered into a
        zero-padded power-of-two row bucket (call under the aggregator
        lock, sync first).  ONE copy serves the flush: the bucket is
        the shape the device estimate compiles for (all-zero padding
        rows estimate to 0 and are sliced off at the fetch), and its
        first len(rows) rows are the forwarding marshal source."""
        n = len(rows)
        out = np.empty((_pow2(n), self.m), np.uint8)
        # mode="clip": rows are arena row ids, and the default "raise"
        # would gather through a second buffer
        np.take(self.host_regs, rows, axis=0, out=out[:n], mode="clip")
        out[n:] = 0
        return out

    def snapshot_part(self) -> dict:
        part = super().snapshot_part()
        rows = part["rows"]
        # migration side lane (legacy blake2b imports): host-side
        # estimates to max against the primary lane at emission
        part["legacy_ests"] = self.legacy_estimates(rows)
        if self.host_regs is not None:
            # host registers: under the lock, copy and nothing else.
            # The estimate runs from the copy at dispatch, on the chip
            # (aggregator._dispatch_sets); a forwarding tier marshals
            # its MIXED rows from the same copy (post-reset)
            if len(rows):
                part["host_regs"] = self.host_regs_copy(rows)
        elif self.mesh is not None or len(rows):
            # device lanes — meshed, or unmeshed-resident: the flush
            # reads the pinned lane snapshot (pmax-merge meshed; the
            # whole-plane estimate resident, or the u8 gather where the
            # tier forwards its sets).  Meshed always pins (the SPMD
            # program takes the full lane plane every flush); resident
            # pins only when set rows were touched — an untouched
            # interval dispatches no set program, so nothing would ever
            # read the snapshot.
            # Whoever holds the part owes the unpin_lanes()
            part["lanes"] = self.snapshot_lanes()
        return part

    def reset_rows(self, rows: np.ndarray) -> None:
        self.sync()
        if self._legacy_regs:
            # the migration lane is interval-scoped like the registers
            for r in rows:
                self._legacy_regs.pop(int(r), None)
        if self.host_regs is not None:
            if len(rows):
                self.host_regs[rows] = 0
            return
        if len(rows) == 0 and self._snapshot_inflight == 0:
            # nothing to clear and no pinned snapshot that could alias
            # the live buffer — skip the swap kernel (it walks the full
            # lane plane, a real per-flush cost on untouched intervals
            # in the unmeshed-resident mode where idle flushes never
            # pin)
            return
        # runs even for empty rows while a snapshot is pinned: the
        # kernel swaps in a fresh buffer so the flush snapshot never
        # aliases the live (donatable) one
        self.lanes_regs = self._lane_reset(self.lanes_regs, rows)

    def _checkpoint_arrays(self) -> dict:
        # call after sync(): staging and imported-row unions are folded
        # into the registers, so the register planes ARE the state.
        # Only LIVE rows serialize (registers are 16 KiB/row at p=14;
        # a default arena's full plane would be 16 MB of zeros)
        live = np.asarray(sorted(self.kdict.values()), np.int64)
        out = {"reg_rows": live}
        if self.host_regs is not None:
            out["host_regs"] = self.host_regs[live].copy()
        else:
            out["lanes_regs"] = np.asarray(self.lanes_regs)[:, live]
        if self._legacy_regs:
            rows = sorted(self._legacy_regs)
            out["legacy_rows"] = np.asarray(rows, np.int64)
            out["legacy_regs"] = np.stack(
                [self._legacy_regs[r] for r in rows])
        return out

    def _checkpoint_extra(self, meta: dict) -> None:
        meta["precision"] = int(self.precision)

    def restore_precheck(self, meta: dict, arrays: dict) -> None:
        if int(meta.get("precision", self.precision)) != self.precision:
            raise CheckpointIncompatible(
                "set checkpoint precision "
                f"{meta.get('precision')} != configured "
                f"{self.precision}; registers are not mergeable "
                "across precisions")

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        rows = arrays.get("reg_rows")
        if rows is not None and len(rows):
            rows = rows.astype(np.int64, copy=False)
            if "host_regs" in arrays:
                src = arrays["host_regs"]
                if self.host_regs is not None:
                    self.host_regs[rows] = src
                else:
                    # unmeshed checkpoint restored onto a meshed arena:
                    # registers land in lane 0 (pmax unions them anyway)
                    lanes = np.asarray(self.lanes_regs).copy()
                    lanes[0, rows] = np.maximum(lanes[0, rows], src)
                    self.lanes_regs = serving.put(lanes, self._lane_shd)
            elif "lanes_regs" in arrays:
                src = arrays["lanes_regs"]
                if self.host_regs is not None:
                    # meshed checkpoint onto an unmeshed arena: union
                    self.host_regs[rows] = src.max(axis=0)
                else:
                    lanes = np.asarray(self.lanes_regs).copy()
                    for lane in range(src.shape[0]):
                        tgt = lane % self.n_lanes
                        lanes[tgt, rows] = np.maximum(lanes[tgt, rows],
                                                      src[lane])
                    self.lanes_regs = serving.put(lanes, self._lane_shd)
        if "legacy_rows" in arrays:
            self._legacy_regs = {
                int(r): regs.copy()
                for r, regs in zip(arrays["legacy_rows"],
                                   arrays["legacy_regs"])}


class DigestArena(_ArenaBase):
    """All histogram/timer digests as host-staged weighted points plus
    host scalar accumulators; one device program per flush evaluates every
    touched key at once (veneur_tpu/parallel/serving.py).

    There is NO persistent device centroid state.  An interval's samples —
    and imported digest centroids (`Histo.Merge`,
    `samplers/samplers.go:539-543`), which are just weighted points —
    accumulate in host COO staging; flush uploads a compact dense
    `[K_t, D]` matrix (touched rows only, D = pow2 max per-key depth) and
    reads back one `[K_t, P+2]` evaluation.  Device traffic is therefore
    proportional to the interval's samples, and nothing rewrites
    hundreds of MB of HBM state per flush.

    The hot-key lane.  Keys whose staged depth outgrows DENSE_DEPTH_CAP
    pre-reduce on device into <= C weighted points via
    `serving.partial_digests` and re-stage — the two-stage amortization
    of `mergeAllTemps` (`merging_digest.go:105-137`) — in launches of
    ONE tile shape (`HOT_TILE_ROWS` x `HOT_TILE_WIDTH`; `_pre_reduce`,
    `prewarm_hot`).  And an unmeshed flush whose interval is skewed does
    not pad every key to the hottest: `deep_rows` names the touched rows
    past `DEEP_TIER_THRESHOLD` points (or re-staged with weights), the
    aggregator builds those apart — weighted, DENSE_DEPTH_CAP deep, a
    few hundred rows — and the long tail at its own depth in the form
    its weights allow (`_dispatch_flush`).  An interval with no deep
    key, or one whose split would not halve the operand, builds the one
    `[K_t, D]` matrix.  Either way `build_dense` makes the operand(s) in
    one native pass over the staged points into buffers the arena keeps
    (`hold_dense` is the rule for them); `build_dense_numpy` is its
    plain reference and what builds where that pass declines.

    With a mesh, the dense matrix shards keys over 'shard' and depth over
    'replica'; the flush all_gathers depth slices over ICI (the
    collective ImportMetric merge, `worker.go:402-459`).

    Host numpy tracks the true digest scalars (min/max/rsum) and the
    *local-samples-only* scalar accumulators that back the mixed-scope
    flush duality (`samplers/samplers.go:315-342`:
    LocalWeight/Min/Max/Sum/ReciprocalSum).
    """

    _TRACK_KIND = True  # forwarding needs histogram-vs-timer per row
    # the t-digest family alone splits a skewed flush into tiers (the
    # other histogram families evaluate vectors, not staged depth)
    _TIERED = True
    # per interval: rows pre-reduced, staged points into and weighted
    # points out of the compress, its launches, and the passes' hold of
    # the caller (the aggregator lock: a drain tick's sync, the cut's)
    HOT_STATS = ("keys", "points_in", "points_out", "compress_launches",
                 "compress_held_ns")
    # per flush: 1 where the native pass into kept operands made every
    # operand (0: the numpy builder), and the bytes of operands and
    # row-index arrays the builds allocated anew
    BUILD_STATS = ("onepass", "fresh_bytes")

    family = "digest"
    # a histogram family's window ring (query plane, retention) and the
    # ForwardMetric field its wire vectors travel in (the t-digest
    # forwards centroid lists instead)
    ring = "tdigest"
    wire_field: Optional[str] = None
    # the local-samples-only accumulators, then the true digest scalars
    # (local samples + imports); __init__ says what each holds
    _COLUMNS = (("l_weight", 0), ("l_min", np.inf), ("l_max", -np.inf),
                ("l_sum", 0), ("l_rsum", 0),
                ("d_min", np.inf), ("d_max", -np.inf), ("d_rsum", 0),
                ("d_weight", 0), ("d_sum", 0))

    def __init__(self, capacity: int = _INITIAL_CAPACITY,
                 compression: float = td.DEFAULT_COMPRESSION,
                 mesh=None, n_lanes: Optional[int] = None,
                 eval_dtype=np.float32, bf16_staging: bool = False,
                 presharded_staging: bool = True,
                 resident: bool = False,
                 resident_chunk_points: int = 32768,
                 resident_device_assembly: Optional[bool] = None):
        super().__init__(capacity)
        self.compression = compression
        # pre-sharded staging (put_dense_sharded): per-device block
        # placement of the meshed dense build; off = the single
        # process-wide device_put funnel (kept for A/B and conservation
        # testing)
        self.presharded_staging = presharded_staging
        self.ccap = td.centroid_capacity(compression)
        # float64 evaluation option (digest_float64): staging is ALWAYS
        # host f64; this controls the dense matrices the flush program
        # evaluates.  f64 preserves integer exactness past 2^24 (epoch
        # stamps, byte counters) at the cost of emulated-f64 device math
        # — the reference computes in float64 throughout
        # (tdigest/merging_digest.go:23-40).  Requires jax_enable_x64.
        self.eval_dtype = np.dtype(eval_dtype)
        # bf16 staging option (digest_bf16_staging): the dense VALUE
        # matrix uploads as bfloat16 (half the flush's dominant upload),
        # bounding quantile values to bf16's ~2^-8 relative rounding —
        # within t-digest's own accuracy envelope (merging_digest's
        # median bar is 2%) but NOT exact; weights/minmax stay f32
        if bf16_staging:
            import ml_dtypes
            self.stage_dtype = np.dtype(ml_dtypes.bfloat16)
        else:
            self.stage_dtype = self.eval_dtype
        # compact-key general staging (v3 kernel): with bf16 staging on,
        # the GENERAL (weighted) dense values also upload as bf16 and
        # the flush routes shallow shapes to the packed compact-key sort
        # network (ops/sorted_eval.py usable_compact) — weights, minmax
        # and exported centroids stay f32-exact (serving.digest_export
        # widens before compress).  Unmeshed only: the meshed program
        # stacks dense_v/dense_w into one all_to_all, which requires one
        # dtype
        self.compact_general = bool(bf16_staging) and mesh is None
        self.n_replicas = self._init_mesh_lanes(mesh, "digest")
        if mesh is not None:
            from veneur_tpu.parallel.mesh import SHARD_AXIS
            self.n_shards = mesh.shape[SHARD_AXIS]
        else:
            self.n_shards = 1
        self._dense_shd = serving.dense_sharding(mesh)
        self._minmax_shd = serving.minmax_sharding(mesh)
        # n_lanes is accepted for config compatibility; the stateless
        # design has no ingest lanes (depth shards over 'replica' instead)
        del n_lanes
        # true digest scalars (local samples + imports)
        self.d_min = np.full(capacity, np.inf)
        self.d_max = np.full(capacity, -np.inf)
        self.d_rsum = np.zeros(capacity)
        # exact f64 interval totals (local samples land via sync's l_*
        # adds; imported centroids via merge_digest) — the flush's
        # count/sum emission reads THESE instead of fetching the device
        # f32 totals, trimming two columns off every readback
        self.d_weight = np.zeros(capacity)
        self.d_sum = np.zeros(capacity)
        # local-samples-only accumulators
        self.l_weight = np.zeros(capacity)
        self.l_min = np.full(capacity, np.inf)
        self.l_max = np.full(capacity, -np.inf)
        self.l_sum = np.zeros(capacity)
        self.l_rsum = np.zeros(capacity)
        # raw COO staging (scalars not yet applied)
        self._rows: list[int] = []
        self._vals: list[float] = []
        self._wts: list[float] = []
        self._local: list[bool] = []
        # array-chunk staging: (rows, vals, wts, local) — local samples
        # from the native ingest engine (sample_batch) and a payload's
        # forwarded centroids (merge_digest_batch; local False)
        self._chunks: list[
            tuple[np.ndarray, np.ndarray, np.ndarray, bool]] = []
        # the interval accumulator: the scalar-applied (rows, vals, wts)
        # points of every sync() so far, in arrival order in ONE buffer
        # the flush takes whole (take_staged), + per-row staged depth
        self._acc = _StagedPoints()
        self._depth = np.zeros(capacity, np.int64)
        # True while every staged weight this interval is exactly 1.0
        # (raw unsampled samples) — lets the flush pick the key-only
        # sort network (ops/sorted_eval.py _kernel_uniform, ~1.8x);
        # any sample_rate != 1, forwarded centroid weight != 1, or
        # hot-key pre-reduction flips it off until the next interval
        self._staged_nonuniform = False
        # rows a pre-reduce re-staged this interval (their points carry
        # merged weights): they alone leave the uniform form — the
        # interval's other rows keep it where the flush builds tiers
        # (deep_rows), and a single-operand flush is weighted as a whole
        self._reduced = np.zeros(capacity, bool)
        self._reduced_any = False
        # what the hot-key lane did over the open interval, for the
        # flush timeline's row (take_hot_stats, at the cut)
        self._hot_stats = dict.fromkeys(self.HOT_STATS, 0)
        # the flush operands' host buffers (build_dense), kept from
        # flush to flush: a dict per tier slot — the single operand or
        # the long tail, and the deep rows — of the operands, the int16
        # record of what the last build filled (`depths`) and, in the
        # first, the native call's scratch; what the last build's
        # launches returned, which the next waits for before it writes
        # them again (hold_dense; None = nobody said, so the buffers
        # are not the arena's to rewrite); and what the builds since
        # take_build_stats did
        self._dense_keep: tuple = ({}, {})
        self._dense_readers = None
        self._build_stats = dict.fromkeys(self.BUILD_STATS, 0)
        # device-resident delta mirror (flush_resident_arenas): the host
        # COO above stays AUTHORITATIVE — checkpoints, forwarding
        # exports and the query rings read it unchanged, which is what
        # keeps crash conservation exact — but with `resident` on, the
        # consolidated prefix additionally streams to the device in
        # fixed pow2-size chunks DURING the interval
        # (stream_resident), so the flush assembles its dense matrix
        # on device from already-resident chunks plus the un-streamed
        # tail (assemble_resident / serving.resident_scatter*) instead
        # of re-uploading the whole key space.  Unmeshed only: the
        # meshed dense build is the pre-sharded all_to_all path.
        self.resident = bool(resident) and mesh is None
        # backend gate for the device-assembly half: on PJRT:CPU there
        # is no link to amortize and XLA:CPU's serial scatter makes
        # flush-time assembly strictly slower than the host dense
        # builder, so streaming/assembly auto-degrade to the staged
        # pipeline (serving.resident_link_ok); tests force the device
        # path by passing resident_device_assembly=True
        self._res_device = (serving.resident_link_ok()
                            if resident_device_assembly is None
                            else bool(resident_device_assembly))
        self._res_chunk_points = max(1024, _pow2(
            int(resident_chunk_points)))
        self._res_chunks: list[dict] = []  # streamed device chunks
        self._res_consumed = 0  # consolidated points already streamed
        self._res_bytes = 0     # bytes moved off the flush critical path
        self._res_dirty = False  # mirror invalidated for this interval
        # per-row arrival cursors: the next streamed point of row r
        # takes dense column _res_pos[r] — the same ordinal
        # build_dense's stable argsort assigns, which is what makes the
        # device-assembled dense matrix elementwise identical to the
        # host-staged one (the bit-parity contract)
        self._res_pos = (np.zeros(capacity, np.int32)
                         if self.resident else None)

    def _grow_state(self, old: int) -> None:
        super()._grow_state(old)
        self._depth = self._grown(self._depth, old, 0)
        self._reduced = self._grown(self._reduced, old, False)
        if self._res_pos is not None:
            self._res_pos = self._grown(self._res_pos, old, 0)

    # -- staging ----------------------------------------------------------

    def sample(self, row: int, value: float, sample_rate: float) -> None:
        """A locally-observed sample (Histo.Sample, samplers.go:331-342)."""
        w = 1.0 / sample_rate
        if w != 1.0:
            self._staged_nonuniform = True
        self._rows.append(row)
        self._vals.append(value)
        self._wts.append(w)
        self._local.append(True)

    def merge_digest(self, row: int, means, weights, dmin: float,
                     dmax: float, drsum: float) -> None:
        """Fold a forwarded digest into a row (Histo.Merge,
        samplers.go:539-543): centroids re-staged as weighted points,
        scalars merged exactly from the wire values."""
        self._rows.extend([row] * len(means))
        self._vals.extend(float(m) for m in means)
        self._wts.extend(float(w) for w in weights)
        self._local.extend([False] * len(means))
        if not self._staged_nonuniform and any(
                float(w) != 1.0 for w in weights):
            self._staged_nonuniform = True
        self.d_min[row] = min(self.d_min[row], dmin)
        self.d_max[row] = max(self.d_max[row], dmax)
        self.d_rsum[row] += drsum

    def sample_batch(self, rows: np.ndarray, vals: np.ndarray,
                     wts: np.ndarray) -> None:
        """Stage a columnar batch of locally-observed samples (the native
        ingest drain path)."""
        if not self._staged_nonuniform and not np.all(wts == 1.0):
            self._staged_nonuniform = True
        self._chunks.append((rows, vals, wts, True))

    def merge_digest_batch(self, rows: np.ndarray, counts: np.ndarray,
                           means: np.ndarray, weights: np.ndarray,
                           dmin: np.ndarray, dmax: np.ndarray,
                           drsum: np.ndarray) -> None:
        """merge_digest for a whole payload in one call: digest i folds
        into rows[i] with its counts[i] centroids, which lie one digest
        after the other in the flat `means` / `weights` (float64, the
        wire's values), and its wire scalars dmin[i] / dmax[i] /
        drsum[i].  The centroids stage as ONE columnar chunk, not
        local; the scalars merge in digest order, so a row that occurs
        twice reads what two merge_digest calls leave (fmin / fmax: as
        Python's min / max there, a NaN from the wire does not
        stick)."""
        if len(means):
            if not self._staged_nonuniform and not np.all(weights == 1.0):
                self._staged_nonuniform = True
            self._chunks.append((np.repeat(rows, counts), means, weights,
                                 False))
        np.fmin.at(self.d_min, rows, dmin)
        np.fmax.at(self.d_max, rows, dmax)
        np.add.at(self.d_rsum, rows, drsum)

    def staged_count(self) -> int:
        return len(self._rows) + sum(len(c[0]) for c in self._chunks)

    # -- consolidation / hot-key pre-reduction ----------------------------

    def sync(self) -> None:
        """Move raw staging into the interval accumulator: write the
        staged points behind those already there (the lists first, then
        the chunks, each in arrival order), apply the host scalar
        updates, track per-row depth, and pre-reduce any row whose
        backlog outgrew DENSE_DEPTH_CAP.  Called from the P7 drain
        ticks and at snapshot: every point is written into the
        accumulator once, by the sync() that finds it staged, so the
        snapshot's own call covers the final partial tick and
        take_staged() after it copies nothing."""
        if not self._rows and not self._chunks:
            return
        parts = []
        if self._rows:
            parts.append((np.asarray(self._rows, np.int64),
                          np.asarray(self._vals, np.float64),
                          np.asarray(self._wts, np.float64),
                          np.asarray(self._local, bool)))
            self._rows, self._vals, self._wts, self._local = [], [], [], []
        for r, v, w, is_local in self._chunks:
            parts.append((r, v, w, np.full(len(r), is_local, bool)))
        self._chunks = []
        # the accumulator's new slots: the scalar updates below read
        # the points from where they stay
        rows, vals, wts = self._acc.extend(sum(len(p[0]) for p in parts))
        at = 0
        for r, v, w, _ in parts:
            end = at + len(r)
            rows[at:end], vals[at:end], wts[at:end] = r, v, w
            at = end
        local = (parts[0][3] if len(parts) == 1
                 else np.concatenate([p[3] for p in parts]))

        # host scalar updates (vectorized)
        np.minimum.at(self.d_min, rows, vals)
        np.maximum.at(self.d_max, rows, vals)
        # exact interval totals over ALL staged points (imported
        # centroids stage through _rows too, so one pass covers both)
        np.add.at(self.d_weight, rows, wts)
        np.add.at(self.d_sum, rows, vals * wts)
        with np.errstate(divide="ignore"):
            np.add.at(self.d_rsum, rows[local],
                      wts[local] / vals[local])
        lr, lv, lw = rows[local], vals[local], wts[local]
        np.add.at(self.l_weight, lr, lw)
        np.minimum.at(self.l_min, lr, lv)
        np.maximum.at(self.l_max, lr, lv)
        np.add.at(self.l_sum, lr, lv * lw)
        with np.errstate(divide="ignore"):
            np.add.at(self.l_rsum, lr, lw / lv)
        self._sync_extra(rows, vals, wts, local)

        np.add.at(self._depth, rows, 1)
        # pre-reduce until every row fits the dense cap; each pass
        # collapses a row's samples ~HOT_TILE_WIDTH -> ccap, so this
        # converges in O(log) passes even for absurd backlogs
        while int(self._depth.max()) > DENSE_DEPTH_CAP:
            before = int(self._depth.max())
            # a pre-reduce reorders the consolidated accumulator, which
            # invalidates the resident mirror's streamed (row, pos)
            # coordinates for this interval
            self._mark_resident_dirty()
            self._pre_reduce()
            if int(self._depth.max()) >= before:
                break

    def _sync_extra(self, rows: np.ndarray, vals: np.ndarray,
                    wts: np.ndarray, local: np.ndarray) -> None:
        """Family hook: extra host-scalar accumulation over one sync
        batch (MomentsArena tracks the positive-sample mass here)."""

    def _consolidated(self):
        """The accumulated (rows, vals, wts): views of the live buffer,
        never copies — index or copy before keeping one past the next
        sync()."""
        return self._acc.views()

    @property
    def staged_copied_bytes(self) -> int:
        return self._acc.copied_bytes

    def _pre_reduce(self) -> None:
        """Collapse rows deeper than DENSE_DEPTH_CAP into <= ccap weighted
        points each: split every deep row's points into virtual rows of
        HOT_TILE_WIDTH, compress them HOT_TILE_ROWS to a launch — one
        tile shape, so one program (_hot_compress) — read the slim
        [rows, ccap] results back and re-stage the centroids.  Every
        tile launches before the first result is waited for.  Scalars
        are NOT re-applied (the original samples already updated
        them)."""
        deep = np.nonzero(self._depth > DENSE_DEPTH_CAP)[0]
        if len(deep) == 0:
            return
        t0 = time.perf_counter_ns()
        stats = self._hot_stats
        rows, vals, wts = self._consolidated()
        # re-staged compressed centroids carry merged weights
        stats["keys"] += int(len(deep) - self._reduced[deep].sum())
        self._reduced[deep] = True
        self._reduced_any = True
        is_deep = np.zeros(self.capacity, bool)
        is_deep[deep] = True
        sel = is_deep[rows]
        keep = (rows[~sel], vals[~sel], wts[~sel])
        drows, dvals, dwts = rows[sel], vals[sel], wts[sel]
        order = np.argsort(drows, kind="stable")
        drows, dvals, dwts = drows[order], dvals[order], dwts[order]
        # virtual rows: (row, chunk of HOT_TILE_WIDTH points), so one
        # pathological key never asks for a wider program
        rpos = np.arange(len(drows)) - np.searchsorted(drows, drows)
        vrows = (drows << np.int64(20)) | (rpos // HOT_TILE_WIDTH)
        urows, counts = np.unique(vrows, return_counts=True)
        vidx = np.repeat(np.arange(len(urows)), counts)
        pos = rpos % HOT_TILE_WIDTH
        starts = np.concatenate([[0], np.cumsum(counts)])
        launched = []
        for g0 in range(0, len(urows), HOT_TILE_ROWS):
            g1 = min(g0 + HOT_TILE_ROWS, len(urows))
            sl = slice(int(starts[g0]), int(starts[g1]))
            dv = np.zeros((HOT_TILE_ROWS, HOT_TILE_WIDTH), np.float32)
            dw = np.zeros_like(dv)
            dv[vidx[sl] - g0, pos[sl]] = dvals[sl]
            dw[vidx[sl] - g0, pos[sl]] = dwts[sl]
            launched.append((g0, g1, self._hot_compress(dv, dw)))
        out_r: list[np.ndarray] = []
        out_v: list[np.ndarray] = []
        out_w: list[np.ndarray] = []
        for g0, g1, (pm, pw) in launched:
            pm = np.asarray(pm)[:g1 - g0]
            pw = np.asarray(pw)[:g1 - g0]
            occ = pw > 0
            out_r.append(np.repeat(urows[g0:g1] >> np.int64(20),
                                   occ.sum(axis=1)))
            out_v.append(pm[occ].astype(np.float64))
            out_w.append(pw[occ].astype(np.float64))
        red_r = np.concatenate(out_r)
        self._acc.replace(np.concatenate([keep[0], red_r]),
                          np.concatenate([keep[1]] + out_v),
                          np.concatenate([keep[2]] + out_w))
        self._depth[deep] = 0
        np.add.at(self._depth, red_r, 1)
        stats["points_in"] += len(drows)
        stats["points_out"] += len(red_r)
        stats["compress_launches"] += len(launched)
        stats["compress_held_ns"] += time.perf_counter_ns() - t0

    def _hot_compress(self, dv: np.ndarray, dw: np.ndarray):
        """LAUNCH the hot-key compress on one tile — _pre_reduce's
        launch, and the one prewarm_hot makes at boot."""
        with self._guard(("hot_compress", dv.shape)):
            return serving.partial_digests(
                jnp.asarray(dv), jnp.asarray(dw), self.compression,
                self.ccap)

    def prewarm_hot(self) -> int:
        """Launch the hot-key compress once on an all-zero tile, so that
        the drain tick that first finds a row past DENSE_DEPTH_CAP — a
        pass under the aggregator lock — compiles nothing.  Returns the
        programs launched."""
        tile = np.zeros((HOT_TILE_ROWS, HOT_TILE_WIDTH), np.float32)
        for out in self._hot_compress(tile, tile):
            out.block_until_ready()
        return 1

    @property
    def hot_tile_bytes(self) -> int:
        """Operands + results of one compress launch, from the shape
        launched: two f32 tiles in, two `[HOT_TILE_ROWS, ccap]` out."""
        return 2 * 4 * HOT_TILE_ROWS * (HOT_TILE_WIDTH + self.ccap)

    def take_hot_stats(self) -> dict:
        """The open interval's HOT_STATS, handed over and zeroed (call
        under the aggregator lock, at the cut)."""
        out, self._hot_stats = (self._hot_stats,
                                dict.fromkeys(self.HOT_STATS, 0))
        return out

    def deep_rows(self, rows: np.ndarray) -> Optional[np.ndarray]:
        """Positions in `rows` (a snapshot's touched rows; call before
        take_staged) of the keys the flush should build apart as its
        deep tier, or None where one operand serves: on a mesh, where
        no row is deep or all are, and where the split would not halve
        the padded value matrix (`fleet8.steady`'s 1,250 keys of 256
        centroids each ARE the operand).  Deep = past
        DEEP_TIER_THRESHOLD staged points, or re-staged by a pre-reduce
        (weighted, whatever its depth)."""
        if self.mesh is not None or not self._TIERED or not len(rows):
            return None
        depth = self._depth[rows]
        deep = depth > DEEP_TIER_THRESHOLD
        if self._reduced_any:
            deep |= self._reduced[rows]
        n_deep = int(deep.sum())
        if n_deep == 0 or n_deep == len(rows):
            return None
        single = _pow2(len(rows)) * self.dense_depth(int(depth.max()))
        tiered = (_pow2(len(rows) - n_deep)
                  * self.dense_depth(int(depth[~deep].max()))
                  + _pow2(max(n_deep, DEEP_TIER_MIN_ROWS))
                  * DENSE_DEPTH_CAP)
        if 2 * tiered > single:
            return None
        return np.nonzero(deep)[0]

    # -- flush ------------------------------------------------------------

    @property
    def staged_uniform(self) -> bool:
        """True iff every weight staged this interval equals exactly 1.0
        (capture BEFORE take_staged resets the tracking): no sample
        rate, no forwarded centroid, no pre-reduced row."""
        return not (self._staged_nonuniform or self._reduced_any)

    def take_staged(self):
        """Consume the interval accumulator (call under the aggregator
        lock, after sync()): returns (rows, vals, wts) COO arrays in
        arrival order.  A hand-off, not a copy: the arrays are the
        accumulator's filled prefix, contiguous, and the caller's from
        here on; the arena starts the next interval in a fresh buffer
        sized to this one's fill."""
        self.snapshot_staged_points = self._acc.n
        self.snapshot_staged_regrows = self._acc.regrows
        self._staged_nonuniform = False
        if self._reduced_any:
            self._reduced[:] = False
            self._reduced_any = False
        return self._acc.take()

    def snapshot_part(self) -> dict:
        part = super().snapshot_part()
        # hash(name) mirror for the query plane's vectorized slot
        # lookups (maintained incrementally at registration)
        part["name_hashes"] = self.name_hash_col[part["rows"]]
        # the interval's staged weighted points (consumed); the flush
        # program evaluates them in one dense pass outside the lock
        # (uniform selects the key-only sort network as a static program
        # choice, ops/sorted_eval.py).  uniform is captured BEFORE
        # take_staged resets the tracking, and the resident mirror is
        # consumed right after take_staged with its result (the tail's
        # (row, pos) coordinates come from the same consolidated arrays)
        part["uniform"] = self.staged_uniform
        # a skewed interval's deep tier (deep_rows), and whether the
        # tail beside it is all unit weights; an interval with no deep
        # key carries neither and is built as one operand
        deep = self.deep_rows(part["rows"])
        if deep is not None:
            part["deep"] = deep
            part["shallow_uniform"] = not self._staged_nonuniform
        t0 = time.perf_counter()
        part["staged"] = self.take_staged()
        self.snapshot_staged_s = time.perf_counter() - t0
        part["resident"] = self.take_resident(part["staged"])
        return part

    # -- resident delta mirror (flush_resident_arenas) ---------------------

    def _mark_resident_dirty(self) -> None:
        """Invalidate the interval's device mirror: drop the streamed
        chunks and fall back to the host-staged dense build at the next
        flush.  Every interval of a node with a hot key (a pre-reduce
        past DENSE_DEPTH_CAP reorders the accumulator), else rare
        (corrupt staged row ids); the host COO is authoritative either
        way."""
        if not self.resident:
            return
        self._res_chunks = []
        self._res_consumed = 0
        self._res_bytes = 0
        self._res_dirty = True
        self._res_pos[:] = 0

    def stream_resident(self) -> int:
        """Mirror freshly-consolidated staged points into device-resident
        delta chunks (call under the aggregator lock, after sync()).
        Only FULL chunks stream — the tail rides the flush dispatch —
        so jit shapes are fixed and every chunk amortizes.  The upload
        itself is asynchronous (jnp.asarray returns before the transfer
        completes); the lock hold covers the host-side slice + cast
        only.  Returns bytes moved off the flush critical path."""
        if (not self.resident or not self._res_device
                or self._res_dirty or not self._acc.n):
            return 0
        rows, vals, wts = self._consolidated()
        cp = self._res_chunk_points
        sent = 0
        while len(rows) - self._res_consumed >= cp:
            sl = slice(self._res_consumed, self._res_consumed + cp)
            crows = rows[sl]
            if (int(crows.min()) < 0
                    or int(crows.max()) >= self.capacity):
                # corrupt staged ids: leave them to build_dense's loud
                # drop path (host fallback for this interval)
                self._mark_resident_dirty()
                return sent
            sent += self._stream_chunk(crows, vals[sl], wts[sl], cp)
            self._res_consumed += cp
        return sent

    def _stream_chunk(self, crows, cvals, cwts, pad_to: int) -> int:
        """Upload one full delta chunk: (row, pos, value[, weight])
        arrays, row-sorted (scatter order is irrelevant — (row, pos)
        pairs are unique), positions continuing each row's arrival
        cursor.  Weights upload only once the interval has gone
        nonuniform; chunks streamed before that scatter exact 1.0
        weights materialized on device."""
        n = len(crows)
        order = np.argsort(crows, kind="stable")
        sr = crows[order]
        starts = np.searchsorted(sr, sr)
        pos = self._res_pos[sr] + (np.arange(n) - starts)
        # duplicate fancy assignment: the LAST write per row wins, which
        # is that row's highest position this chunk — the cursor
        # advances past everything just streamed
        self._res_pos[sr] = (pos + 1).astype(np.int32)
        pr = np.full(pad_to, self.capacity, np.int32)  # pad -> sentinel
        pp = np.zeros(pad_to, np.int32)
        # unmeshed dense VALUES are always stage_dtype: the uniform and
        # compact_general builds stage at wire width, and without bf16
        # staging stage_dtype == eval_dtype — so chunks streamed before
        # the flush knows its uniformity still land bit-identical
        pv = np.zeros(pad_to, self.stage_dtype)
        pr[:n] = sr
        pp[:n] = pos
        pv[:n] = cvals[order]  # same numpy cast as the dense build's
        chunk = {"rows": jnp.asarray(pr), "pos": jnp.asarray(pp),
                 "vals": jnp.asarray(pv)}
        nbytes = pr.nbytes + pp.nbytes + pv.nbytes
        if self._staged_nonuniform:
            pw = np.zeros(pad_to, self.eval_dtype)
            pw[:n] = cwts[order]
            chunk["wts"] = jnp.asarray(pw)
            nbytes += pw.nbytes
        self._res_chunks.append(chunk)
        self._res_bytes += nbytes
        return nbytes

    def take_resident(self, staged):
        """Consume the interval's resident mirror (call under the
        aggregator lock, immediately after take_staged, with its
        result): returns the dispatch part for assemble_resident and
        resets the mirror for the next interval.  The TAIL — staged
        points after the last full streamed chunk — gets its (row, pos)
        coordinates here: O(tail) indexing, the only per-flush host
        build work left on the resident path.  Returns None when device
        assembly is off for this backend (serving.resident_link_ok) —
        the flush then takes the staged chunk-pipelined path."""
        if not self.resident or not self._res_device:
            return None
        rows, vals, wts = staged
        part = {"dirty": self._res_dirty,
                "chunks": self._res_chunks,
                "streamed_bytes": self._res_bytes,
                "streamed_points": self._res_consumed}
        if not part["dirty"]:
            tr = rows[self._res_consumed:]
            if len(tr) and (int(tr.min()) < 0
                            or int(tr.max()) >= self.capacity):
                part["dirty"] = True  # host fallback drops them loudly
                part["chunks"] = []
            else:
                n = len(tr)
                order = np.argsort(tr, kind="stable")
                sr = tr[order]
                starts = np.searchsorted(sr, sr)
                pos = self._res_pos[sr] + (np.arange(n) - starts)
                part["tail"] = (sr, pos,
                                vals[self._res_consumed:][order],
                                wts[self._res_consumed:][order])
        self._res_chunks = []
        self._res_consumed = 0
        self._res_bytes = 0
        self._res_dirty = False
        self._res_pos[:] = 0
        return part

    def assemble_resident(self, part, staged, touched: np.ndarray,
                          d_min_t: np.ndarray, d_max_t: np.ndarray,
                          uniform: bool, donate: bool):
        """Assemble the flush's dense build ON DEVICE from the resident
        delta mirror: a zeros [U, D] accumulator born in HBM plus one
        scatter per streamed chunk and one for the tail.  The critical-
        path upload is the dense-id map, the tail chunk and the depth
        vector / minmax scalars — everything else crossed the link
        during the interval.  Same value contract as build_dense +
        put_dense*, but the dense matrices come back as DEVICE arrays;
        the extra return is the critical-path byte count.  Caller must
        have checked part['dirty'].  donate=False keeps the scatter
        chain copying even on donation-safe backends (a local tier
        keeps the final matrices for centroid export)."""
        rows, vals, wts = staged
        nd = len(touched)
        u_pad = self.n_shards * self.dense_block_per_shard(nd)
        # dense-id map with a sentinel slot at index `capacity` (where
        # chunk padding rows point); rows outside this flush map to the
        # OOB marker the scatters drop on device
        dense_id = np.full(self.capacity + 1, serving._RESIDENT_DROP,
                           np.int32)
        dense_id[touched] = np.arange(nd, dtype=np.int32)
        counts = (np.bincount(rows, minlength=self.capacity)[touched]
                  if len(rows) and nd else np.zeros(nd, np.int64))
        depth = max(int(counts.max()) if len(counts) else 1, 1)
        d_pad = self.dense_depth(depth)
        vdt = (self.stage_dtype if (uniform or self.compact_general)
               else self.eval_dtype)
        chunks = list(part["chunks"])
        critical = dense_id.nbytes
        tail = part.get("tail")
        if tail is not None and len(tail[0]):
            tr, tp, tv, tw = tail
            n = len(tr)
            pad_to = max(2, _pow2(n))  # pow2 pad: jit-shape reuse
            pr = np.full(pad_to, self.capacity, np.int32)
            pp = np.zeros(pad_to, np.int32)
            pv = np.zeros(pad_to, vdt)
            pr[:n] = tr
            pp[:n] = tp
            pv[:n] = tv
            tchunk = {"rows": jnp.asarray(pr), "pos": jnp.asarray(pp),
                      "vals": jnp.asarray(pv)}
            critical += pr.nbytes + pp.nbytes + pv.nbytes
            if not uniform:
                pw = np.zeros(pad_to, self.eval_dtype)
                pw[:n] = tw
                tchunk["wts"] = jnp.asarray(pw)
                critical += pw.nbytes
            chunks.append(tchunk)
        did = jnp.asarray(dense_id)
        donate = donate and serving.resident_donation_ok()
        dv = serving.resident_dense_zeros(shape=(u_pad, d_pad),
                                          dtype=vdt)
        if uniform:
            scat = (serving.resident_scatter if donate
                    else serving.resident_scatter_copy)
            for ch in chunks:
                dv = scat(dv, did, ch["rows"], ch["pos"], ch["vals"])
            depths_vec = np.zeros(u_pad, np.int16)
            if nd:
                depths_vec[:nd] = counts
            critical += depths_vec.nbytes
            return dv, serving.put(depths_vec, None), None, critical
        dw = serving.resident_dense_zeros(shape=(u_pad, d_pad),
                                          dtype=self.eval_dtype)
        sw = (serving.resident_scatter_w if donate
              else serving.resident_scatter_w_copy)
        sw1 = (serving.resident_scatter_w1 if donate
               else serving.resident_scatter_w1_copy)
        for ch in chunks:
            if "wts" in ch:
                dv, dw = sw(dv, dw, did, ch["rows"], ch["pos"],
                            ch["vals"], ch["wts"])
            else:
                # streamed while the interval was still uniform: exact
                # 1.0 weights materialize on device, never uploaded
                dv, dw = sw1(dv, dw, did, ch["rows"], ch["pos"],
                             ch["vals"])
        minmax = np.zeros((2, u_pad), self.eval_dtype)
        minmax[0, :nd] = d_min_t
        minmax[1, :nd] = d_max_t
        critical += minmax.nbytes
        return dv, dw, serving.put(minmax, self._minmax_shd), critical

    @staticmethod
    def staged_depth(staged) -> int:
        """Max per-row staged depth of a take_staged() result (cheap; used
        for the multi-controller shape agreement)."""
        rows = staged[0]
        if len(rows) == 0:
            return 0
        return int(np.bincount(rows).max())

    def dense_block_per_shard(self, n_rows: int) -> int:
        """Row-block size each mesh shard owns in the dense build for
        `n_rows` touched keys: each shard's block must split evenly
        over the replicas (the flush body's all_to_all re-partitions a
        shard's rows R ways), so the block is the pow2 ceiling of
        n_rows/S rounded up to a replica multiple.  This IS the
        multi-controller key-ownership contract: dense row r (touched
        order) lives on shard r // block, and devices are process-major
        — a deployment must stage/import key k only on the process
        whose shards cover its dense row (parallel/multihost.py;
        tests/test_multihost.py drives it through this method so the
        test and the build cannot drift)."""
        per_shard = _pow2(-(-max(int(n_rows), 1) // self.n_shards))
        if per_shard % self.n_replicas:
            per_shard = self.n_replicas * _pow2(
                -(-per_shard // self.n_replicas))
        return per_shard

    def dense_depth(self, depth: int) -> int:
        """Padded depth of the dense build for a deepest row of `depth`
        staged points: a power of two to each replica's slice."""
        return max(2, self.n_replicas * _pow2(-(-depth // self.n_replicas)))

    def _operand(self, keep: dict, name: str, shape, dtype, zero: bool):
        """The buffer of that name in `keep` (a tier slot of
        `_dense_keep`), made anew where shape or dtype no longer fit.
        A fresh operand of megabytes is first-touch page faults inside
        the fill (~1 us/KB on the chip's host), and whether glibc
        serves it from mapped heap or from new pages is an accident of
        the process's allocation history: a kept one costs the same in
        every run.  zero: all zeros (a kept one zeroed in place); else
        whatever the buffer holds."""
        buf = keep.get(name)
        if buf is None or buf.shape != tuple(shape) or buf.dtype != dtype:
            buf = keep[name] = (np.zeros if zero else np.empty)(shape, dtype)
            self._build_stats["fresh_bytes"] += buf.nbytes
        elif zero:
            buf.fill(0)
        return buf

    def take_build_stats(self) -> dict:
        """BUILD_STATS of the builds since the last call, zeroed."""
        out, self._build_stats = (self._build_stats,
                                  dict.fromkeys(self.BUILD_STATS, 0))
        return out

    def hold_dense(self, readers) -> None:
        """The rule for the kept operands (safety, not a target): after
        a caller launched on what build_dense returned, it says here
        what the launches returned (results that are ready have consumed
        their inputs) — and hands lend_dense the device operands it
        keeps beyond the launch.  The next build waits for those readers
        before it writes the kept buffers again — a served node's
        flushes are serial, so that returns at once; a build nobody
        called this after makes its own buffers."""
        self._dense_readers = readers

    def lend_dense(self, dev_operands) -> None:
        """Device arrays of the last build's operands that outlive their
        launch (a forwarding tier keeps them for its digest export).
        Where device_put aliased an aligned host buffer instead of
        copying it — the CPU backend's may — the array IS the kept
        buffer: the arena lets go of it and the next build makes its
        own."""
        for arr in dev_operands:
            for shard in arr.addressable_shards:
                if shard.device.platform != "cpu":
                    continue
                at = shard.data.unsafe_buffer_pointer()
                for keep in self._dense_keep:
                    for name in ("dv", "dw", "depths"):
                        buf = keep.get(name)
                        if (buf is not None and buf.ctypes.data <= at
                                < buf.ctypes.data + buf.nbytes):
                            del keep[name]

    def _tier_buffers(self, keep: dict, uniform: bool, u_pad: int,
                      d_pad: int) -> tuple:
        """One tier's (dv, dw, depths, u_pad, d_pad) for the native
        build out of its slot of `_dense_keep`: the kept buffers as
        they are where their shapes still fit — they are what `depths`
        records (each row's first depths[r] cells filled, zeros past
        them) —, else all of them zeroed, made anew where they do not
        fit.  d_pad 0: none (the native call only counts)."""
        if not d_pad:
            return None, None, None, u_pad, 0
        wanted = [("dv", (u_pad, d_pad), np.float32),
                  ("depths", (u_pad,), np.int16)]
        if uniform:
            # (what a weighted interval left would not follow the record)
            keep.pop("dw", None)
        else:
            wanted.append(("dw", (u_pad, d_pad), np.float32))
        stale = any(name not in keep or keep[name].shape != shape
                    or keep[name].dtype != dtype
                    for name, shape, dtype in wanted)
        bufs = {name: self._operand(keep, name, shape, dtype, zero=stale)
                for name, shape, dtype in wanted}
        return bufs["dv"], bufs.get("dw"), bufs["depths"], u_pad, d_pad

    @staticmethod
    def tier_rows(n_touched: int, deep: np.ndarray) -> tuple:
        """build_dense's `sels` for a snapshot's deep tier (`deep_rows`):
        the long tail's and the deep rows' positions among the touched
        rows, each ascending."""
        in_tail = np.ones(n_touched, bool)
        in_tail[deep] = False
        return np.nonzero(in_tail)[0], deep

    def build_dense(self, staged, touched: np.ndarray,
                    d_min_t: np.ndarray, d_max_t: np.ndarray,
                    u_floor: int = 0, d_floor: int = 0,
                    uniform: bool = False, sels=None) -> list:
        """The flush program's host operand(s): the staged COO mapped
        onto touched-row-ordered dense matrices `[U, D]` (U = padded
        touched count, no less than u_floor; D = padded max depth, no
        less than d_floor), plus the stacked [2, U] min/max from the
        SNAPSHOT scalar copies (the live arrays are already reset by
        the time this runs).  Host work only; the caller device_puts
        the result (outside the aggregator lock).  Returns a list of
        one (dv, dw, minmax) triple, or — `sels` given (`tier_rows`:
        the tail's and the deep rows' positions in `touched`) — two:
        the long tail in the form `uniform` allows at its own depth,
        and the deep rows weighted, DENSE_DEPTH_CAP deep, a pow2 bucket
        of at least DEEP_TIER_MIN_ROWS.

        uniform=True (legal only when every staged weight of the
        operand is exactly 1, `staged_uniform`): the middle return is a
        per-row int16 DEPTH VECTOR `[U]` instead of the `[U, D]` weight
        matrix — staged points pack contiguously from column 0, so
        `col < depth[row]` is the occupancy — and no minmax (None).
        Halves both the host build work and the bytes crossing the
        host->device link.

        Built in ONE native call (`_build_kept` -> vn_build_tiers) into
        operands the arena keeps from flush to flush, which are the
        caller's only until the next build and oblige it to
        `hold_dense`; where that call cannot be made — no native
        engine, a staging or eval dtype other than float32
        (digest_float64, digest_bf16_staging), a staged id out of range
        or not in `touched` — by `build_dense_numpy` per tier, into
        fresh operands, which drops corrupt points loudly."""
        if sels is None:
            specs = [(slice(None), bool(uniform), u_floor, d_floor)]
        else:
            specs = [(sels[0], bool(uniform), 0, 0),
                     (sels[1], False, DEEP_TIER_MIN_ROWS, DENSE_DEPTH_CAP)]
        built = self._build_kept(staged, touched, d_min_t, d_max_t, specs)
        if built is not None:
            return built
        rows, vals, wts = self._sound_points(staged)
        mine = [slice(None)]
        if sels is not None:
            is_deep = np.zeros(self.capacity, bool)
            is_deep[touched[sels[1]]] = True
            in_deep = is_deep[rows]
            mine = [~in_deep, in_deep]
        return [self.build_dense_numpy(
            (rows[own], vals[own], wts[own]), touched[sel], d_min_t[sel],
            d_max_t[sel], u_fl, d_fl, form)
            for (sel, form, u_fl, d_fl), own in zip(specs, mine)]

    def _build_kept(self, staged, touched: np.ndarray,
                    d_min_t: np.ndarray, d_max_t: np.ndarray,
                    specs: list) -> Optional[list]:
        """build_dense's native form: ONE call (vn_build_tiers) maps
        rows to (tier, dense row), counts each row's points from the
        first read of them (so the depth is known without a numpy
        gather or bincount) and fills from the second, into the
        buffers `_dense_keep` holds for each tier slot — no page is
        first touched in a steady flush, and no cell zeroed but those
        the last build filled past a row's new count (the kept int16
        `depths`, the uniform form's operand, is that record for either
        form).  specs: each tier's (sel, uniform, u_floor, d_floor).
        Same casts, same arrival order within a row: bit-equal to
        build_dense_numpy over each tier's own points.  None, and no
        cell written, where that cannot be promised."""
        if (self.eval_dtype != np.float32
                or self.stage_dtype != np.float32):
            return None
        try:
            from veneur_tpu import ingest as ingest_mod
            ingest_mod.load_library()
        except Exception:
            return None
        keeps = self._dense_keep[:len(specs)]
        if self._dense_readers is None:
            # handed out, and nobody said who reads them
            for keep in self._dense_keep:
                keep.clear()
        else:
            jax.block_until_ready(self._dense_readers)
            self._dense_readers = []
        rows, vals, wts = staged
        sels, forms, u_floors, floors = zip(*specs)
        rows = np.ascontiguousarray(rows, np.int64)
        vals = np.ascontiguousarray(vals, np.float64)
        wts = (None if all(forms)
               else np.ascontiguousarray(wts, np.float64))
        touched = np.ascontiguousarray(touched, np.int64)
        deep = (np.ascontiguousarray(sels[1], np.int64)
                if len(specs) > 1 else np.empty(0, np.int64))
        u_pads = [
            self.n_shards * self.dense_block_per_shard(max(
                len(touched) if isinstance(sel, slice) else len(sel),
                u_floor))
            for sel, u_floor in zip(sels, u_floors)]
        row_map = self._operand(keeps[0], "row_map", (self.capacity,),
                                np.int32, zero=False)
        cursors = self._operand(
            keeps[0], "cursors",
            ((ingest_mod.BUILD_DENSE_THREADS + 1) * sum(u_pads),),
            np.int32, zero=False)

        def attempt(d_pads):
            """The call at these depths (any of them 0: count only)."""
            if not all(d_pads):
                d_pads = [0] * len(specs)
            tiers = [self._tier_buffers(*tier)
                     for tier in zip(keeps, forms, u_pads, d_pads)]
            status, depths = ingest_mod.build_tiers(
                rows, vals, wts, touched, deep, row_map, cursors, tiers)
            want = [self.dense_depth(max(depth, floor, 1))
                    for depth, floor in zip(depths, floors)]
            return status, want, tiers

        # the kept shapes first (the steady case: one call), then, where
        # a tier's deepest row asks for another depth, that one
        d_pads = [
            keep["dv"].shape[1] if "dv" in keep
            and keep["dv"].shape[0] == u_pad
            else self.dense_depth(floor) if floor else 0
            for keep, u_pad, floor in zip(keeps, u_pads, floors)]
        status, want, tiers = attempt(d_pads)
        if status <= 0 and want != d_pads:
            status, want, tiers = attempt(want)
        if status != 0:
            return None
        self._dense_readers = None
        self._build_stats["onepass"] = 1
        built = []
        for (dv, dw, depths, u_pad, _d), sel, uniform, keep in zip(
                tiers, sels, forms, keeps):
            if uniform:
                built.append((dv, depths, None))
                continue
            lo, hi = d_min_t[sel], d_max_t[sel]
            minmax = self._operand(keep, "minmax", (2, u_pad),
                                   self.eval_dtype, zero=False)
            minmax[0, :len(lo)] = lo
            minmax[1, :len(lo)] = hi
            minmax[:, len(lo):] = 0
            built.append((dv, dw, minmax))
        return built

    def _sound_points(self, staged) -> tuple:
        """The staged COO without points whose row id is outside
        [0, capacity): a negative id would WRAP through numpy negative
        indexing into another key's row — dropped loudly instead."""
        rows, vals, wts = staged
        if not len(rows) or (int(rows.min()) >= 0
                             and int(rows.max()) < self.capacity):
            return staged
        bad = (rows < 0) | (rows >= self.capacity)
        import logging
        logging.getLogger("veneur_tpu.core.arena").error(
            "dropping %d staged digest points with out-of-bounds "
            "row ids (corrupt staging)", int(bad.sum()))
        return rows[~bad], vals[~bad], wts[~bad]

    def build_dense_numpy(self, staged, touched: np.ndarray,
                          d_min_t: np.ndarray, d_max_t: np.ndarray,
                          u_floor: int = 0, d_floor: int = 0,
                          uniform: bool = False) -> tuple:
        """One operand's (dv, dw, minmax) in plain numpy — a stable
        argsort by dense row and one scatter — into fresh arrays: the
        reference build_dense's native call is held to bit for bit, and
        what builds for the dtypes that call would round
        (digest_float64, bf16 staging) and after `_sound_points`
        dropped corrupt staging."""
        rows, vals, wts = staged
        nd = len(touched)
        u_pad = self.n_shards * self.dense_block_per_shard(max(nd, u_floor))
        dense_id = np.full(self.capacity, -1, np.int64)
        dense_id[touched] = np.arange(nd)
        r = dense_id[rows]
        self._build_stats["fresh_bytes"] += dense_id.nbytes + r.nbytes

        def fresh(shape, dtype):
            buf = np.zeros(shape, dtype)
            self._build_stats["fresh_bytes"] += buf.nbytes
            return buf

        order = np.argsort(r, kind="stable")
        r, v = r[order], vals[order]
        first = np.searchsorted(r, np.arange(nd))
        pos = np.arange(len(r)) - first[r]
        depth = max(int(pos.max()) + 1 if len(r) else 1, d_floor)
        d_pad = self.dense_depth(depth)
        if uniform:
            # bf16 staging narrows the VALUE matrix only; weights (0/1,
            # implicit here) and exported centroid weights stay exact
            dv = fresh((u_pad, d_pad), self.stage_dtype)
            dv[r, pos] = v
            # int16 is exact (depths <= DENSE_DEPTH_CAP < 2^15) and
            # halves the vector's bytes on the link
            depths_vec = fresh((u_pad,), np.int16)
            if len(r):
                depths_vec[:nd] = np.bincount(
                    r.astype(np.int64), minlength=nd)[:nd]
            # minmax stays host-side on this path (never uploaded);
            # returned as None so nobody builds it for nothing
            return dv, depths_vec, None
        # compact_general: bf16 VALUES on the general path too (weights
        # and minmax stay eval_dtype — they feed exact accumulations)
        dv = fresh((u_pad, d_pad), self.stage_dtype if self.compact_general
                   else self.eval_dtype)
        dv[r, pos] = v
        minmax = fresh((2, u_pad), self.eval_dtype)
        minmax[0, :nd] = d_min_t
        minmax[1, :nd] = d_max_t
        dw = fresh((u_pad, d_pad), self.eval_dtype)
        dw[r, pos] = wts[order]
        return dv, dw, minmax

    def put_dense(self, dv: np.ndarray, dw: np.ndarray,
                  minmax: np.ndarray):
        """Device-put the dense build with the mesh shardings."""
        return (serving.put(dv, self._dense_shd),
                serving.put(dw, self._dense_shd),
                serving.put(minmax, self._minmax_shd))

    def put_dense_sharded(self, dv: np.ndarray, dw: np.ndarray,
                          minmax: np.ndarray):
        """Pre-sharded staging of the meshed dense build
        (serving.place_dense_blocks: per-device block placement, no
        process-wide re-layout on program entry).  Falls back to
        put_dense when unmeshed, multi-controller (each process only
        holds its own slices — serving.put's make_array_from_callback
        handles that), or when the flag is off."""
        import jax
        if (self.mesh is None or not self.presharded_staging
                or jax.process_count() > 1):
            return self.put_dense(dv, dw, minmax)
        return serving.place_dense_blocks(
            self.mesh, dv, dw, minmax, self._dense_shd, self._minmax_shd)

    def put_dense_uniform(self, dv: np.ndarray, depths: np.ndarray):
        """Device-put the uniform (depth-vector) dense build — no
        weight matrix and no minmax (see digest_eval_uniform)."""
        return (serving.put(dv, self._dense_shd),
                serving.put(depths, None))

    _CKPT_SCALARS = ("d_min", "d_max", "d_rsum", "d_weight", "d_sum",
                     "l_weight", "l_min", "l_max", "l_sum", "l_rsum",
                     "_depth")

    def _checkpoint_arrays(self) -> dict:
        # call after sync(): raw COO staging and native chunks are
        # consolidated into _acc, so the interval's not-yet-flushed
        # samples checkpoint as three aligned arrays and restore
        # BIT-EXACTLY (the mid-interval durability the crash arms prove)
        out = {name: getattr(self, name).copy()
               for name in self._CKPT_SCALARS}
        rows, vals, wts = self._consolidated()
        out["acc_rows"] = rows.copy()
        out["acc_vals"] = vals.copy()
        out["acc_wts"] = wts.copy()
        return out

    def _checkpoint_extra(self, meta: dict) -> None:
        # which rows a pre-reduce re-staged is not kept: a restored
        # interval with any is weighted as a whole
        meta["staged_nonuniform"] = not self.staged_uniform
        meta["compression"] = float(self.compression)
        # resident layout stamp (flush_resident_arenas): the host COO in
        # this checkpoint is authoritative either way — the resident
        # mirror re-streams from it after restore — but the streamed
        # chunks' staging width is part of the bit-replay contract
        # (resident == host-staged twin), so a resident restore prechecks
        # it (restore_precheck)
        meta["resident"] = bool(self.resident)
        meta["resident_stage_dtype"] = str(np.dtype(self.stage_dtype))

    def restore_precheck(self, meta: dict, arrays: dict) -> None:
        if float(meta.get("compression",
                          self.compression)) != self.compression:
            raise CheckpointIncompatible(
                "digest checkpoint compression "
                f"{meta.get('compression')} != configured "
                f"{self.compression}")
        want = str(np.dtype(self.stage_dtype))
        got = str(meta.get("resident_stage_dtype", want))
        if bool(meta.get("resident")) and self.resident and got != want:
            raise CheckpointIncompatible(
                "resident-arena checkpoint streamed delta chunks at "
                f"stage dtype {got} != configured {want}; the "
                "bit-replay contract (resident == host-staged twin) "
                "does not hold across staging widths")

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        for name in self._CKPT_SCALARS:
            self._restore_into(getattr(self, name), arrays[name])
        self._acc.replace(arrays["acc_rows"], arrays["acc_vals"],
                          arrays["acc_wts"])
        self._staged_nonuniform = bool(meta.get("staged_nonuniform",
                                                False))
        if self.resident:
            # drop any pre-restore mirror state: the restored accumulator
            # re-streams from position 0 (readback is never needed — the
            # checkpointed COO is the authoritative copy)
            self._res_chunks = []
            self._res_consumed = 0
            self._res_bytes = 0
            self._res_dirty = False
            self._res_pos[:] = 0

    def reset_rows(self, rows: np.ndarray) -> None:
        super().reset_rows(rows)
        self._depth[rows] = 0


class MomentsArena(DigestArena):
    """The moments sketch family (sketches/moments.py): each row is one
    fixed-size f64 moments vector instead of a centroid set, and the
    flush's merge is a dense segmented SUM (ops/moments_eval.py Pallas
    kernel) followed by the batched maxent solver — no sort network at
    all.  The low-accuracy/high-cardinality counterpart to DigestArena
    (ROADMAP #3); family choice per key is the aggregator's dispatch
    layer (config ``sketch_family_*``).

    Shares DigestArena's whole staging machinery — COO buffers, native
    chunk staging, interval consolidation, the compact dense build with
    its uniform depth-vector variant, and ``dense_block_per_shard`` —
    plus the exact host scalar accumulators (d_min/d_max/d_weight/
    d_sum/d_rsum and the local-only l_* set), and adds:

      d_logn   per-row weight over strictly-positive samples (the mass
               the log-domain power sums cover)
      ivec     ``[capacity, 2(k+1)]`` f64 accumulator of NON-STAGED
               power-sum mass — imported vectors (merge_moments) and
               hot-row pre-reductions — as range-scaled monomial sums
               in the row's own ivec domain (iv_a/iv_b).  Layout:
               [count, U_1..U_k, logn, V_1..V_k].

    The interval's raw staged samples stay in COO staging and reduce
    ON DEVICE at flush; the host converts ivec to Chebyshev
    contributions in the authoritative [d_min, d_max] domain and the
    program adds the two before solving.  Hot rows whose staged depth
    outgrows DENSE_DEPTH_CAP pre-reduce by folding into ivec on host
    (exact f64) instead of a device t-digest compress.

    Unmeshed only: the moments flush is a single-device program (config
    rejects ``sketch_family_*`` with a device mesh)."""

    _TIERED = False
    family = ring = wire_field = "moments"
    _COLUMNS = DigestArena._COLUMNS + (
        ("d_logn", 0), ("ivec", 0), ("iv_a", np.inf), ("iv_b", -np.inf))

    def __init__(self, capacity: int = _INITIAL_CAPACITY,
                 k: int = 0, mesh=None, **kw):
        from veneur_tpu.sketches import moments as mo
        if mesh is not None:
            raise ValueError(
                "the moments sketch family serves unmeshed tiers only "
                "(its flush program is single-device; drop "
                "mesh_devices or the sketch_family_* rules)")
        kw.pop("compression", None)
        kw.pop("bf16_staging", None)
        super().__init__(capacity=capacity, mesh=None, **kw)
        self.k = int(k) if k else mo.DEFAULT_K
        self.d_logn = np.zeros(self.capacity)
        self.ivec = np.zeros((self.capacity, 2 * (self.k + 1)),
                             np.float64)
        self.iv_a = np.full(self.capacity, np.inf)
        self.iv_b = np.full(self.capacity, -np.inf)

    def _sync_extra(self, rows, vals, wts, local) -> None:
        pos = vals > 0
        if pos.any():
            np.add.at(self.d_logn, rows[pos], wts[pos])

    # -- imports (vector merge: the elementwise-add path) ------------------

    def merge_moments(self, row: int, vec) -> None:
        """Fold one wire moments vector into a row: exact scalar
        merges plus a domain-rebased elementwise add of the power-sum
        blocks (sketches/moments.py contract)."""
        from veneur_tpu.sketches import moments as mo
        vec = np.asarray(vec, np.float64)
        if len(vec) != mo.vector_len(self.k):
            raise ValueError(
                f"moments vector length {len(vec)} does not match "
                f"k={self.k} (len {mo.vector_len(self.k)}); mixed-k "
                "fleets are not mergeable")
        self.d_min[row] = min(self.d_min[row], vec[mo.IDX_MIN])
        self.d_max[row] = max(self.d_max[row], vec[mo.IDX_MAX])
        self.d_weight[row] += vec[mo.IDX_COUNT]
        self.d_sum[row] += vec[mo.IDX_SUM]
        self.d_rsum[row] += vec[mo.IDX_RSUM]
        self.d_logn[row] += vec[mo.IDX_LOGN]
        self._ivec_fold(
            row, (vec[mo.IDX_MIN], vec[mo.IDX_MAX]),
            np.concatenate([[vec[mo.IDX_COUNT]],
                            vec[mo.SUMS_OFF:mo.SUMS_OFF + self.k]]),
            np.concatenate([[vec[mo.IDX_LOGN]],
                            vec[mo.SUMS_OFF + self.k:]]))

    def _ivec_fold(self, row: int, src_ab, raw_sums, log_sums) -> None:
        """Rebase-add one (raw, log) monomial power-sum pair (in domain
        ``src_ab``) into the row's ivec accumulator, growing the ivec
        domain to cover both."""
        from veneur_tpu.sketches import moments as mo
        k = self.k
        a0, b0 = self.iv_a[row], self.iv_b[row]
        a1 = min(a0, float(src_ab[0]))
        b1 = max(b0, float(src_ab[1]))
        new_ab = (np.asarray([a1]), np.asarray([b1]))
        new_lab = mo.log_domain(*map(np.asarray, ([a1], [b1])))
        cur_raw = self.ivec[row:row + 1, :k + 1]
        cur_log = self.ivec[row:row + 1, k + 1:]
        src_lab = mo.log_domain(np.asarray([float(src_ab[0])]),
                                np.asarray([float(src_ab[1])]))
        if a1 == a0 and b1 == b0:
            # steady state: the row's domain already covers the
            # incoming vector — rebasing the existing sums would be
            # an exact identity, so skip its two O(k^2) transforms
            raw = cur_raw
            log = cur_log
        else:
            old_lab = mo.log_domain(
                np.asarray([a0 if np.isfinite(a0) else 0.0]),
                np.asarray([b0 if np.isfinite(b0) else 0.0]))
            raw = mo.rebase_sums(cur_raw, ([a0], [b0]), new_ab)
            log = mo.rebase_sums(cur_log, old_lab, new_lab)
        raw = raw + mo.rebase_sums(
            raw_sums[None, :],
            ([float(src_ab[0])], [float(src_ab[1])]), new_ab)
        log = log + mo.rebase_sums(log_sums[None, :], src_lab, new_lab)
        self.ivec[row, :k + 1] = raw[0]
        self.ivec[row, k + 1:] = log[0]
        self.iv_a[row], self.iv_b[row] = a1, b1

    # -- hot-row pre-reduction (host fold, no device compress) -------------

    def _pre_reduce(self) -> None:
        """Collapse rows deeper than DENSE_DEPTH_CAP by folding their
        staged points into the ivec accumulator (exact f64 host fold,
        sketches/moments.fold_values) — a moments "compress" is just
        the merge itself, so no device round-trip and no re-staging.
        Scalars are NOT re-applied (sync already did)."""
        from veneur_tpu.sketches import moments as mo
        rows, vals, wts = self._consolidated()
        deep = np.nonzero(self._depth > DENSE_DEPTH_CAP)[0]
        if len(deep) == 0:
            return
        is_deep = np.zeros(self.capacity, bool)
        is_deep[deep] = True
        sel = is_deep[rows]
        drows, dvals, dwts = rows[sel], vals[sel], wts[sel]
        # compact index space over the deep rows
        ridx = np.searchsorted(deep, drows)
        n = len(deep)
        k = self.k
        sub_a = np.minimum.reduceat(
            *self._reduceat_args(drows, dvals, np.inf))
        sub_b = np.maximum.reduceat(
            *self._reduceat_args(drows, dvals, -np.inf))
        # per-deep-row fold domain: the union of the row's ivec domain
        # and the staged subset's own range
        a1 = np.minimum(np.where(np.isfinite(self.iv_a[deep]),
                                 self.iv_a[deep], np.inf), sub_a)
        b1 = np.maximum(np.where(np.isfinite(self.iv_b[deep]),
                                 self.iv_b[deep], -np.inf), sub_b)
        lab1 = mo.log_domain(a1, b1)
        # rebase the existing ivec rows to the grown domains
        old_lab = mo.log_domain(
            np.where(np.isfinite(self.iv_a[deep]), self.iv_a[deep],
                     0.0),
            np.where(np.isfinite(self.iv_b[deep]), self.iv_b[deep],
                     0.0))
        raw = mo.rebase_sums(self.ivec[deep, :k + 1],
                             (self.iv_a[deep], self.iv_b[deep]),
                             (a1, b1))
        log = mo.rebase_sums(self.ivec[deep, k + 1:], old_lab, lab1)
        mo.fold_values(raw, log, ridx, dvals, dwts, (a1, b1), lab1)
        self.ivec[deep, :k + 1] = raw
        self.ivec[deep, k + 1:] = log
        self.iv_a[deep], self.iv_b[deep] = a1, b1
        keep = ~sel
        self._acc.replace(rows[keep], vals[keep], wts[keep])
        self._depth[deep] = 0

    @staticmethod
    def _reduceat_args(sorted_rows, vals, fill):
        """(values, starts) for np.{minimum,maximum}.reduceat over the
        per-row segments of a row-sorted COO subset."""
        order = np.argsort(sorted_rows, kind="stable")
        sr, sv = sorted_rows[order], vals[order]
        starts = np.searchsorted(sr, np.unique(sr))
        del fill
        return sv, starts

    # -- forwarding export -------------------------------------------------

    def assemble_vectors(self, part: dict, staged, sel: np.ndarray
                         ) -> np.ndarray:
        """Wire vectors ``[F, M]`` for the selected snapshot rows:
        exact scalars from the snapshot copies, power sums = the ivec
        contribution rebased to the authoritative [d_min, d_max] plus
        a host f64 fold of the interval's staged points (subset-sized
        — forwarding cost scales with the forwarded rows).  Call at
        emit time on the SNAPSHOT dict (the live arrays are already
        reset)."""
        from veneur_tpu.sketches import moments as mo
        k = self.k
        f = len(sel)
        a = np.where(np.isfinite(part["d_min"][sel]),
                     part["d_min"][sel], 0.0)
        b = np.where(np.isfinite(part["d_max"][sel]),
                     part["d_max"][sel], 0.0)
        lab = mo.log_domain(a, b)
        old_a, old_b = part["iv_a"][sel], part["iv_b"][sel]
        old_lab = mo.log_domain(
            np.where(np.isfinite(old_a), old_a, 0.0),
            np.where(np.isfinite(old_b), old_b, 0.0))
        raw = mo.rebase_sums(part["ivec"][sel, :k + 1],
                             (old_a, old_b), (a, b))
        log = mo.rebase_sums(part["ivec"][sel, k + 1:], old_lab, lab)
        # fold this interval's staged points of the selected rows
        srows, svals, swts = staged
        if len(srows):
            grows = part["rows"][sel]
            lut = np.full(self.capacity, -1, np.int64)
            lut[grows] = np.arange(f)
            m = lut[srows] >= 0
            if m.any():
                mo.fold_values(raw, log, lut[srows[m]], svals[m],
                               swts[m], (a, b), lab)
        vecs = np.zeros((f, mo.vector_len(k)), np.float64)
        vecs[:, mo.IDX_COUNT] = part["d_weight"][sel]
        vecs[:, mo.IDX_MIN] = part["d_min"][sel]
        vecs[:, mo.IDX_MAX] = part["d_max"][sel]
        vecs[:, mo.IDX_SUM] = part["d_sum"][sel]
        vecs[:, mo.IDX_RSUM] = part["d_rsum"][sel]
        vecs[:, mo.IDX_LOGN] = part["d_logn"][sel]
        vecs[:, mo.SUMS_OFF:mo.SUMS_OFF + k] = raw[:, 1:]
        vecs[:, mo.SUMS_OFF + k:] = log[:, 1:]
        return vecs

    # -- flush conversion --------------------------------------------------

    def import_contrib(self, part: dict, u_pad: int):
        """The flush program's ``imp`` operand: Chebyshev contributions
        of the snapshot rows' ivec accumulators in the authoritative
        domain, f64-converted on host, zero-padded to the dense row
        count.  Returns (imp [u_pad, 2(k+1)] f32, ab [2, u_pad] f32,
        lab [2, u_pad] f32)."""
        from veneur_tpu.ops import moments_eval as me
        from veneur_tpu.sketches import moments as mo
        k = self.k
        n = len(part["rows"])
        a = np.where(np.isfinite(part["d_min"]), part["d_min"], 0.0)
        b = np.where(np.isfinite(part["d_max"]), part["d_max"], 0.0)
        la, lb = mo.log_domain(a, b)
        old_a, old_b = part["iv_a"], part["iv_b"]
        old_lab = mo.log_domain(
            np.where(np.isfinite(old_a), old_a, 0.0),
            np.where(np.isfinite(old_b), old_b, 0.0))
        raw = mo.rebase_sums(part["ivec"][:, :k + 1],
                             (old_a, old_b), (a, b))
        log = mo.rebase_sums(part["ivec"][:, k + 1:], old_lab,
                             (la, lb))
        c = me._mono_to_cheb(k).T
        imp = np.zeros((u_pad, 2 * (k + 1)), np.float32)
        imp[:n, :k + 1] = raw @ c
        imp[:n, k + 1:] = log @ c
        ab = np.zeros((2, u_pad), np.float32)
        ab[0, :n] = a
        ab[1, :n] = b
        lab = np.zeros((2, u_pad), np.float32)
        lab[1, :] = -1.0          # sentinel: lb < la = log invalid
        lab[0, :n] = la
        lab[1, :n] = lb
        return imp, ab, lab

    # -- crash checkpoint --------------------------------------------------

    def _checkpoint_arrays(self) -> dict:
        out = super()._checkpoint_arrays()
        out["d_logn"] = self.d_logn.copy()
        # ivec serializes live rows only (the dense plane is f64 and
        # capacity-sized; live rows are what restores bit-exactly)
        live = np.asarray(sorted(self.kdict.values()), np.int64)
        out["ivec_rows"] = live
        out["ivec"] = self.ivec[live].copy()
        out["iv_a"] = self.iv_a[live].copy()
        out["iv_b"] = self.iv_b[live].copy()
        return out

    def _checkpoint_extra(self, meta: dict) -> None:
        from veneur_tpu.ops import moments_eval as me
        super()._checkpoint_extra(meta)
        meta["moments_k"] = int(self.k)
        meta["solver"] = [int(me.QUAD_POINTS), int(me.NEWTON_ITERS)]

    def restore_precheck(self, meta: dict, arrays: dict) -> None:
        from veneur_tpu.ops import moments_eval as me
        super().restore_precheck(meta, arrays)
        if int(meta.get("moments_k", self.k)) != self.k:
            raise CheckpointIncompatible(
                f"moments checkpoint k {meta.get('moments_k')} != "
                f"configured {self.k}; power-sum blocks are not "
                "mergeable across orders")
        solver = [int(x) for x in (meta.get("solver")
                                   or [me.QUAD_POINTS,
                                       me.NEWTON_ITERS])]
        if solver != [int(me.QUAD_POINTS), int(me.NEWTON_ITERS)]:
            raise CheckpointIncompatible(
                f"moments checkpoint solver config {solver} != "
                f"current [{me.QUAD_POINTS}, {me.NEWTON_ITERS}]; "
                "restored quantiles would not replay bit-identically")

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        super()._restore_arrays(meta, arrays)
        self._restore_into(self.d_logn, arrays["d_logn"])
        rows = arrays.get("ivec_rows")
        if rows is not None and len(rows):
            rows = rows.astype(np.int64, copy=False)
            self.ivec[rows] = arrays["ivec"]
            self.iv_a[rows] = arrays["iv_a"]
            self.iv_b[rows] = arrays["iv_b"]


class CompactorArena(DigestArena):
    """The relative-error compactor family (sketches/compactor.py): each
    row is one fixed ladder of ``levels`` compactor buffers of ``cap``
    slots — the provable-rank-error tier (ROADMAP #4, README "Sketch
    families") operators pick by rule for SLA-grade tails, next to the
    empirical t-digest (DigestArena) and the cheap-merge moments family
    (MomentsArena).

    Shares DigestArena's whole staging machinery — COO buffers, native
    chunk staging, interval consolidation, the exact host scalar
    accumulators — and adds the per-row ladder state:

      cvals   ``[capacity, levels, cap]`` f32 level items (occupied
              prefix per level, zero padding beyond ``ccnt``)
      ccnt    ``[capacity, levels]`` per-level occupancies
      ccomps / cclip   per-row compaction / clip counters (the coin
              schedule position — what makes merges replayable)

    The interval's staged samples fold into the ladders in batched
    ROUNDS of ops/compactor_eval.compact_batch — each round is ONE
    device launch compacting every pending row at once, the host only
    assembles level staging and plans the coin schedule between rounds
    (compactor.plan_pass).  The fold runs mid-interval when a row's
    backlog outgrows DENSE_DEPTH_CAP (_pre_reduce) and at flush on the
    snapshot (fold_flush); values round to f32 on entry so the host
    reference, the XLA twin and the Pallas kernel replay
    bit-identically — the checkpoint/restore parity contract.

    Unmeshed only, like moments: one flush program per device, no
    cross-shard collective in the family's merge algebra yet."""

    _TIERED = False
    family = ring = wire_field = "compactor"
    _COLUMNS = DigestArena._COLUMNS + (
        ("cvals", 0), ("ccnt", 0), ("ccomps", 0), ("cclip", 0))

    def __init__(self, capacity: int = _INITIAL_CAPACITY,
                 cap: int = 0, levels: int = 0, seed: int = 0,
                 mesh=None, **kw):
        from veneur_tpu.sketches import compactor as cs
        if mesh is not None:
            raise ValueError(
                "the compactor sketch family serves unmeshed tiers "
                "only (its fold/flush programs are single-device; "
                "drop mesh_devices or the sketch_family_* rules)")
        kw.pop("compression", None)
        kw.pop("bf16_staging", None)
        # no dense matrix build at flush -> nothing for the resident
        # delta mirror to amortize
        kw.pop("resident", None)
        kw.pop("resident_chunk_points", None)
        kw.pop("resident_device_assembly", None)
        super().__init__(capacity=capacity, mesh=None, **kw)
        self.cc_cap = int(cap) if cap else cs.DEFAULT_CAP
        self.cc_levels = int(levels) if levels else cs.DEFAULT_LEVELS
        self.cc_seed = int(seed) if seed else cs.DEFAULT_SEED
        if (self.cc_cap < 8 or self.cc_cap & (self.cc_cap - 1)
                or self.cc_levels < 2):
            raise ValueError(
                f"bad compactor params cap={self.cc_cap} "
                f"levels={self.cc_levels} (cap must be a power of two "
                ">= 8, levels >= 2)")
        self.cvals = np.zeros(
            (capacity, self.cc_levels, self.cc_cap), np.float32)
        self.ccnt = np.zeros((capacity, self.cc_levels), np.int64)
        self.ccomps = np.zeros(capacity, np.int64)
        self.cclip = np.zeros(capacity, np.int64)

    # -- the batched fold (rounds of ONE compact_batch launch) -------------

    def _fold_state(self, st: dict, srows: np.ndarray,
                    svals: np.ndarray, swts: np.ndarray) -> None:
        """Fold staged weighted points into ladder state arrays
        ``st = {cvals, ccnt, comps, clip}`` (row space = whatever
        ``srows`` indexes — the live capacity-sized arrays or a compact
        snapshot).  Points enter in staged order per row; each round
        feeds every pending row's level staging up to 2*cap and runs
        one compact_batch over all of them, so the device launch count
        is O(max backlog / cap), never O(rows)."""
        from veneur_tpu.ops import compactor_eval as ce
        from veneur_tpu.sketches import compactor as cs
        if len(srows) == 0:
            return
        levels, cap = self.cc_levels, self.cc_cap
        s2 = cs.STAGE_MUL * cap
        # f32 value resolution on entry: the device fold and the host
        # reference then agree bit-for-bit
        v32 = np.clip(svals, -cs._FCLAMP, cs._FCLAMP).astype(
            np.float32).astype(np.float64)
        order = np.argsort(srows, kind="stable")
        r_s, v_s = srows[order], v32[order]
        w_s = np.asarray(swts, np.float64)[order]
        uniq, starts = np.unique(r_s, return_index=True)
        ends = np.append(starts[1:], len(r_s))
        pending = []
        for u0, s0, e0 in zip(uniq, starts, ends):
            q = cs.split_levels(v_s[s0:e0], w_s[s0:e0], levels)
            pending.append((int(u0), q, np.zeros(levels, np.int64)))
        slot = np.arange(cap)[None, :]
        while pending:
            n = len(pending)
            n_pad = max(8, _pow2(n))
            stage_v = np.full((n_pad, levels, s2), np.inf)
            stage_n = np.zeros((n_pad, levels), np.int64)
            comps = np.zeros(n_pad, np.int64)
            clip = np.zeros(n_pad, np.int64)
            for i, (r, q, pos) in enumerate(pending):
                comps[i] = st["comps"][r]
                clip[i] = st["clip"][r]
                for lvl in range(levels):
                    occ = int(st["ccnt"][r, lvl])
                    stage_v[i, lvl, :occ] = st["cvals"][r, lvl, :occ]
                    take = min(s2 - occ, len(q[lvl]) - int(pos[lvl]))
                    if take > 0:
                        stage_v[i, lvl, occ:occ + take] = \
                            q[lvl][pos[lvl]:pos[lvl] + take]
                        pos[lvl] += take
                    stage_n[i, lvl] = occ + take
            off, cnt_out, comps_out, clip_out = cs.plan_pass(
                stage_n, comps, clip, self.cc_seed, cap)
            out = ce.compact_batch(stage_v, stage_n, off)
            # zero the +inf padding back out (live-state convention)
            out = np.where(slot[None, :, :] < cnt_out[:, :, None],
                           out, 0.0).astype(np.float32)
            nxt = []
            for i, (r, q, pos) in enumerate(pending):
                st["cvals"][r] = out[i]
                st["ccnt"][r] = cnt_out[i]
                st["comps"][r] = comps_out[i]
                st["clip"][r] = clip_out[i]
                if any(int(pos[lvl]) < len(q[lvl])
                       for lvl in range(levels)):
                    nxt.append((r, q, pos))
            pending = nxt

    def _live_state(self) -> dict:
        return {"cvals": self.cvals, "ccnt": self.ccnt,
                "comps": self.ccomps, "clip": self.cclip}

    def _pre_reduce(self) -> None:
        """Collapse rows deeper than DENSE_DEPTH_CAP by folding their
        staged points into the ladder state — a compactor "compress"
        is the fold itself, so nothing re-stages.  Scalars are NOT
        re-applied (sync already did)."""
        rows, vals, wts = self._consolidated()
        deep = np.nonzero(self._depth > DENSE_DEPTH_CAP)[0]
        if len(deep) == 0:
            return
        is_deep = np.zeros(self.capacity, bool)
        is_deep[deep] = True
        sel = is_deep[rows]
        self._fold_state(self._live_state(), rows[sel], vals[sel],
                         wts[sel])
        keep = ~sel
        self._acc.replace(rows[keep], vals[keep], wts[keep])
        self._depth[deep] = 0

    # -- imports (ladder merge: concatenate-then-compact) ------------------

    def merge_compactor(self, row: int, vec) -> None:
        """Fold one wire compactor vector into a row: exact scalar
        merges plus a level-wise concatenate and ONE host compaction
        pass (sketches/compactor.py contract — the coin continues from
        the summed counters, so import order cannot change the bits).
        Param (cap/levels/seed) mismatches are refused, never
        coerced."""
        from veneur_tpu.sketches import compactor as cs
        vec = np.asarray(vec, np.float64)
        params = cs.params_from_vector(vec)
        if params != (self.cc_cap, self.cc_levels, self.cc_seed):
            raise ValueError(
                f"compactor vector params {params} do not match "
                f"configured ({self.cc_cap}, {self.cc_levels}, "
                f"{self.cc_seed}); mixed-param fleets are not "
                "mergeable")
        self.d_min[row] = min(self.d_min[row], vec[cs.IDX_MIN])
        self.d_max[row] = max(self.d_max[row], vec[cs.IDX_MAX])
        self.d_weight[row] += vec[cs.IDX_COUNT]
        self.d_sum[row] += vec[cs.IDX_SUM]
        self.d_rsum[row] += vec[cs.IDX_RSUM]
        vb, cb, qb, lb = cs.state_from_vector(vec)
        if not cb.any():
            return
        levels, cap = self.cc_levels, self.cc_cap
        s2 = cs.STAGE_MUL * cap
        stage_v = np.full((1, levels, s2), np.inf)
        ca = self.ccnt[row]
        for lvl in range(levels):
            stage_v[0, lvl, :ca[lvl]] = self.cvals[row, lvl, :ca[lvl]]
            stage_v[0, lvl, ca[lvl]:ca[lvl] + cb[lvl]] = \
                vb[lvl, :cb[lvl]].astype(np.float32)
        stage_n = (ca + cb)[None, :]
        off, cnt_out, comps, clip = cs.plan_pass(
            stage_n, np.asarray([self.ccomps[row] + qb]),
            np.asarray([self.cclip[row] + lb]), self.cc_seed, cap)
        out = cs.apply_pass(stage_v, stage_n, off, cap)[0]
        live = np.arange(cap)[None, :] < cnt_out[0][:, None]
        self.cvals[row] = np.where(live, out, 0.0).astype(np.float32)
        self.ccnt[row] = cnt_out[0]
        self.ccomps[row] = int(comps[0])
        self.cclip[row] = int(clip[0])

    # -- flush (fold-then-evaluate on the snapshot) ------------------------

    def fold_flush(self, part: dict, staged):
        """Fold the interval's staged points into the SNAPSHOT ladder
        states — call at dispatch time, once; the result caches in the
        part dict so the flush eval, the forwarding export and the
        query plane all read the SAME folded state and cannot
        disagree.  Returns ``(cvals [n, levels, cap] f32, ccnt
        [n, levels], comps [n], clip [n])`` in snapshot row order."""
        cached = part.get("cfold")
        if cached is not None:
            return cached
        grows = np.asarray(part["rows"], np.int64)
        n = len(grows)
        st = {"cvals": part["cvals"].copy(), "ccnt": part["ccnt"].copy(),
              "comps": part["ccomps"].copy(),
              "clip": part["cclip"].copy()}
        srows, svals, swts = staged
        if len(srows):
            lut = np.full(self.capacity, -1, np.int64)
            lut[grows] = np.arange(n)
            m = lut[srows] >= 0
            if m.any():
                self._fold_state(st, lut[srows[m]], svals[m], swts[m])
        part["cfold"] = (st["cvals"], st["ccnt"], st["comps"],
                         st["clip"])
        return part["cfold"]

    def flush_operands(self, part: dict, staged, u_pad: int):
        """Operands for ops/compactor_eval.make_compactor_flush from
        the folded snapshot state: ``(cvals [u_pad, levels*cap] f32,
        ccnt [u_pad, levels] i32, cscale [u_pad] f32, mm [2, u_pad]
        f32)``.  ``cscale`` renormalizes the implied item mass to the
        exact header count (identity while counts are integral and the
        ladder never clipped)."""
        cvals, ccnt, comps, clip = self.fold_flush(part, staged)
        n = len(part["rows"])
        levels, cap = self.cc_levels, self.cc_cap
        cv = np.zeros((u_pad, levels * cap), np.float32)
        cv[:n] = cvals.reshape(n, levels * cap)
        cc = np.zeros((u_pad, levels), np.int32)
        cc[:n] = ccnt
        mass = (ccnt * 2.0 ** np.arange(levels)[None, :]).sum(axis=1)
        cnt = np.asarray(part["d_weight"][:n], np.float64)
        cscale = np.ones(u_pad, np.float32)
        nz = (mass > 0) & (cnt > 0)
        cscale[:n][nz] = (cnt[nz] / mass[nz]).astype(np.float32)
        mm = np.zeros((2, u_pad), np.float32)
        mm[0, :n] = np.where(np.isfinite(part["d_min"][:n]),
                             part["d_min"][:n], 0.0)
        mm[1, :n] = np.where(np.isfinite(part["d_max"][:n]),
                             part["d_max"][:n], 0.0)
        return cv, cc, cscale, mm

    # -- forwarding export -------------------------------------------------

    def assemble_vectors(self, part: dict, staged, sel: np.ndarray
                         ) -> np.ndarray:
        """Wire vectors ``[F, M]`` for the selected snapshot rows:
        exact scalars from the snapshot copies, ladder state from the
        flush's folded snapshot (fold_flush — shared, not recomputed).
        Call at emit time on the SNAPSHOT dict."""
        from veneur_tpu.sketches import compactor as cs
        cvals, ccnt, comps, clip = self.fold_flush(part, staged)
        f = len(sel)
        vecs = np.zeros(
            (f, cs.vector_len(self.cc_cap, self.cc_levels)), np.float64)
        for j, i in enumerate(sel):
            vec = cs.empty_vector(self.cc_cap, self.cc_levels,
                                  self.cc_seed)
            vec[cs.IDX_COUNT] = part["d_weight"][i]
            vec[cs.IDX_SUM] = part["d_sum"][i]
            vec[cs.IDX_RSUM] = part["d_rsum"][i]
            vec[cs.IDX_MIN] = part["d_min"][i]
            vec[cs.IDX_MAX] = part["d_max"][i]
            cs._encode(vec, cvals[i].astype(np.float64), ccnt[i],
                       int(comps[i]), int(clip[i]))
            vecs[j] = vec
        return vecs

    # -- lifecycle ---------------------------------------------------------

    def snapshot_part(self) -> dict:
        """The staged points fold into the part's ladder COPIES at
        dispatch (fold_flush, outside the lock); the live ladders reset
        right after the cut, so an overlapping interval can never alias
        the in-flight fold.  No dense build at flush: the part carries
        neither a kernel choice nor a resident mirror."""
        part = super().snapshot_part()
        del part["uniform"], part["resident"]
        return part

    # -- crash checkpoint --------------------------------------------------

    def _checkpoint_arrays(self) -> dict:
        out = super()._checkpoint_arrays()
        # ladder state serializes live rows only (capacity-sized
        # [levels, cap] planes are the family's biggest arrays; live
        # rows are what restores bit-exactly)
        live = np.asarray(sorted(self.kdict.values()), np.int64)
        out["compactor_rows"] = live
        out["cvals"] = self.cvals[live].copy()
        out["ccnt"] = self.ccnt[live].copy()
        out["ccomps"] = self.ccomps[live].copy()
        out["cclip"] = self.cclip[live].copy()
        return out

    def _checkpoint_extra(self, meta: dict) -> None:
        super()._checkpoint_extra(meta)
        meta["compactor_params"] = [int(self.cc_cap),
                                    int(self.cc_levels),
                                    int(self.cc_seed)]

    def restore_precheck(self, meta: dict, arrays: dict) -> None:
        super().restore_precheck(meta, arrays)
        want = [int(self.cc_cap), int(self.cc_levels),
                int(self.cc_seed)]
        got = [int(x) for x in (meta.get("compactor_params") or want)]
        if got != want:
            raise CheckpointIncompatible(
                f"compactor checkpoint params {got} != configured "
                f"{want}; ladder states and coin schedules are not "
                "mergeable across (cap, levels, seed)")

    def _restore_arrays(self, meta: dict, arrays: dict) -> None:
        super()._restore_arrays(meta, arrays)
        rows = arrays.get("compactor_rows")
        if rows is not None and len(rows):
            rows = rows.astype(np.int64, copy=False)
            self.cvals[rows] = arrays["cvals"]
            self.ccnt[rows] = arrays["ccnt"]
            self.ccomps[rows] = arrays["ccomps"]
            self.cclip[rows] = arrays["cclip"]
