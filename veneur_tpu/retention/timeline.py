"""The multi-resolution retention timeline.

Every flush cut already produces an immutable snapshot `part` per
histogram family (the same parts the query WindowRing rotates).  The
timeline compacts those parts upward through a ladder of coarser
tiers:

    cut (seconds)  ->  tier 0 (e.g. minute)  ->  tier 1 (hour)  -> ...

Each tier is a bounded ring of `TierBucket`s.  A cut merges into the
finest tier's open bucket; when a bucket's time span completes it
closes into the tier's ring AND merges into the next tier's open
bucket — so every datum lives at every resolution simultaneously, and
a range query picks the finest tier still holding its window.  The
merges are the families' own merges (digest point-cloud concat with
the serving compress kernel past the payload cap, moments rebase-add,
compactor concat-then-compact), so every bucket stays mergeable and
every tier inherits the family's committed error envelope.

Buckets evicted from the COARSEST tier's ring spill to disk through
the TierSegmentStore (retention/spill.py) — the bounded-footprint
tail of the timeline; evictions from finer tiers are not loss (their
mass already cascaded upward) and are counted, not spilled.

Crash contract: the in-memory tiers checkpoint with the arena cut
(aggregator.checkpoint_state -> "retention" block) and the on-disk
segments re-index on boot — proven by the `timeline-crash-revive`
chaos arm.

The timeline's lock is a leaf: taken from the compaction worker and
from query threads; it never nests inside any aggregator or arena
lock.  The flush hook (compact_cut, called AFTER the aggregator lock
releases) only ENQUEUES the cut's immutable snapshot parts — the
egress-lane pattern: extraction and tier merges run on a daemon
worker, so the flush path pays a handoff, not O(live keys) work.
`drain()` (called by the checkpoint capture) fences the queue.
"""

from __future__ import annotations

import io
import json
import math
import threading
import time
from collections import deque
from typing import Optional

import numpy as np

# fused digest clouds past this many points compress down through the
# serving compress kernel (the same bound the query payload codec uses)
BUCKET_POINT_CAP = 2048


def _jtags(tags) -> str:
    return ",".join(sorted(tags)) if tags is not None and len(tags) \
        else ""


# -- per-cut summaries (snapshot part -> per-key mergeable payloads) ----

def summarize_digest_part(part: dict, point_cap: int = BUCKET_POINT_CAP,
                          compression: float = 100.0) -> dict:
    """Digest-family snapshot part -> {(name, jtags, kind): cloud}.
    The cloud is the key's staged weighted points plus the exact
    scalar accumulators — the same extraction the query fusion does,
    over every key in the part at once."""
    rows = part["rows"]
    n = len(rows)
    if n == 0:
        return {}
    srows, svals, swts = part["staged"]
    order = np.argsort(srows, kind="stable")
    ss = srows[order]
    sv = np.asarray(svals, np.float64)[order]
    sw = np.asarray(swts, np.float64)[order]
    names, tags, kinds = part["names"], part["tags"], part["kinds"]
    # one vectorized pass for every per-key boundary and scalar (the
    # hook runs on the flush path: a per-key searchsorted here showed
    # up as flush degradation at the 5k-key shape)
    rr = np.asarray(rows, np.int64)
    lo_a = np.searchsorted(ss, rr).tolist()
    hi_a = np.searchsorted(ss, rr + 1).tolist()
    cnt_a = np.asarray(part["d_weight"], np.float64).tolist()
    min_a = np.asarray(part["d_min"], np.float64).tolist()
    max_a = np.asarray(part["d_max"], np.float64).tolist()
    sum_a = np.asarray(part["d_sum"], np.float64).tolist()
    rsum_a = np.asarray(part["d_rsum"], np.float64).tolist()
    out: dict = {}
    for i in range(n):
        lo, hi = lo_a[i], hi_a[i]
        cnt = cnt_a[i]
        if cnt <= 0 and hi <= lo:
            continue
        key = (str(names[i]), _jtags(tags[i]), str(kinds[i]))
        ent = {"v": sv[lo:hi].copy(), "w": sw[lo:hi].copy(),
               "min": min_a[i], "max": max_a[i],
               "count": cnt, "sum": sum_a[i], "rsum": rsum_a[i]}
        prev = out.get(key)
        out[key] = ent if prev is None else \
            merge_cloud(prev, ent, point_cap, compression)
    return out


def summarize_vector_part(part: dict, arena, family: str) -> dict:
    """Moments/compactor snapshot part -> {(name, jtags, kind): wire
    vector}, via ONE batched assemble_vectors walk over the part."""
    rows = part["rows"]
    n = len(rows)
    if n == 0:
        return {}
    if family == "moments":
        from veneur_tpu.sketches import moments as fam
    else:
        from veneur_tpu.sketches import compactor as fam
    srows, svals, swts = part["staged"]
    order = np.argsort(srows, kind="stable")
    sub = (srows[order], svals[order], swts[order])
    parr = np.arange(n, dtype=np.int64)
    vecs = arena.assemble_vectors(part, sub, parr)
    names, tags, kinds = part["names"], part["tags"], part["kinds"]
    out: dict = {}
    for i in range(n):
        vec = np.asarray(vecs[i], np.float64)
        if float(vec[fam.IDX_COUNT]) <= 0:
            continue
        key = (str(names[i]), _jtags(tags[i]), str(kinds[i]))
        prev = out.get(key)
        out[key] = vec.copy() if prev is None else \
            fam.merge_vectors(prev[None, :], vec[None, :])[0]
    return out


def merge_cloud(a: dict, b: dict, point_cap: int = BUCKET_POINT_CAP,
                compression: float = 100.0) -> dict:
    """Digest bucket merge: weighted point-cloud concat, compressed
    through the serving kernel only past the cap (below it the merge
    is bit-exact concatenation — the tier-compaction parity tests
    stay under the cap)."""
    v = np.concatenate([a["v"], b["v"]])
    w = np.concatenate([a["w"], b["w"]])
    if len(v) > point_cap:
        from veneur_tpu.query.engine import _compress_payload
        v, w = _compress_payload(v, w, compression)
    return {"v": v, "w": w,
            "min": min(a["min"], b["min"]),
            "max": max(a["max"], b["max"]),
            "count": a["count"] + b["count"],
            "sum": a["sum"] + b["sum"],
            "rsum": a["rsum"] + b["rsum"]}


class TierBucket:
    """One tier bucket: per-key mergeable payloads for all three
    families over [t_start, t_end).  `filled_to` tracks how far the
    bucket's data actually reaches (an open bucket covers only up to
    the last merged cut)."""

    __slots__ = ("t_start", "t_end", "filled_to", "td", "mo", "cc",
                 "cuts")

    def __init__(self, t_start: float, t_end: float):
        self.t_start = float(t_start)
        self.t_end = float(t_end)
        self.filled_to = float(t_start)
        self.td: dict = {}
        self.mo: dict = {}
        self.cc: dict = {}
        self.cuts = 0

    @property
    def points(self) -> float:
        """Total sample count across families (the conservation
        currency of the crash arm)."""
        return (sum(e["count"] for e in self.td.values())
                + sum(float(v[0]) for v in self.mo.values())
                + sum(float(v[0]) for v in self.cc.values()))

    def nbytes(self) -> int:
        n = 0
        for e in self.td.values():
            n += e["v"].nbytes + e["w"].nbytes + 48
        for v in self.mo.values():
            n += v.nbytes
        for v in self.cc.values():
            n += v.nbytes
        return n

    def absorb(self, td: dict, mov: dict, ccv: dict, upto: float,
               point_cap: int, compression: float) -> None:
        """Merge one cut's (or one finer bucket's) per-key summaries
        into this bucket — the tier compaction itself."""
        from veneur_tpu.sketches import compactor as cs
        from veneur_tpu.sketches import moments as mo
        for key, ent in td.items():
            prev = self.td.get(key)
            self.td[key] = (
                {"v": ent["v"], "w": ent["w"], "min": ent["min"],
                 "max": ent["max"], "count": ent["count"],
                 "sum": ent["sum"], "rsum": ent["rsum"]}
                if prev is None
                else merge_cloud(prev, ent, point_cap, compression))
        for key, vec in mov.items():
            prev = self.mo.get(key)
            self.mo[key] = vec.copy() if prev is None else \
                mo.merge_vectors(prev[None, :], vec[None, :])[0]
        for key, vec in ccv.items():
            prev = self.cc.get(key)
            self.cc[key] = vec.copy() if prev is None else \
                cs.merge_vectors(prev[None, :], vec[None, :])[0]
        self.filled_to = max(self.filled_to, min(float(upto),
                                                 self.t_end))
        self.cuts += 1

    def snapshot(self) -> "TierBucket":
        """Shallow copy for lock-free reads: payload dicts copy by
        reference (entries are replaced, never mutated in place)."""
        b = TierBucket(self.t_start, self.t_end)
        b.filled_to = self.filled_to
        b.td = dict(self.td)
        b.mo = dict(self.mo)
        b.cc = dict(self.cc)
        b.cuts = self.cuts
        return b


# -- the bucket codec (checkpoint arrays and the spill body share it) ---

def bucket_to_arrays(b: TierBucket) -> tuple[dict, dict]:
    """TierBucket -> (JSON-able meta, named float64 arrays): the flat
    columnar form both the checkpoint (npz arrays) and the spill body
    serialize.  Floats round-trip bit-exactly."""
    td_keys = sorted(b.td)
    mo_keys = sorted(b.mo)
    cc_keys = sorted(b.cc)
    sizes = [len(b.td[k]["v"]) for k in td_keys]
    off = np.zeros(len(td_keys) + 1, np.int64)
    off[1:] = np.cumsum(sizes)
    meta = {"t_start": b.t_start, "t_end": b.t_end,
            "filled_to": b.filled_to, "cuts": b.cuts,
            "td_keys": [list(k) for k in td_keys],
            "mo_keys": [list(k) for k in mo_keys],
            "cc_keys": [list(k) for k in cc_keys]}
    arrays = {
        "td_off": off,
        "td_vals": (np.concatenate([b.td[k]["v"] for k in td_keys])
                    if td_keys else np.zeros(0, np.float64)),
        "td_wts": (np.concatenate([b.td[k]["w"] for k in td_keys])
                   if td_keys else np.zeros(0, np.float64)),
        "td_scal": np.asarray(
            [[b.td[k]["min"], b.td[k]["max"], b.td[k]["count"],
              b.td[k]["sum"], b.td[k]["rsum"]] for k in td_keys],
            np.float64).reshape(len(td_keys), 5),
        "mo_vecs": (np.stack([b.mo[k] for k in mo_keys])
                    if mo_keys else np.zeros((0, 0), np.float64)),
        "cc_vecs": (np.stack([b.cc[k] for k in cc_keys])
                    if cc_keys else np.zeros((0, 0), np.float64)),
    }
    return meta, arrays


def bucket_from_arrays(meta: dict, arrays: dict) -> TierBucket:
    b = TierBucket(meta["t_start"], meta["t_end"])
    b.filled_to = float(meta["filled_to"])
    b.cuts = int(meta.get("cuts", 0))
    off = np.asarray(arrays["td_off"], np.int64)
    vals = np.asarray(arrays["td_vals"], np.float64)
    wts = np.asarray(arrays["td_wts"], np.float64)
    scal = np.asarray(arrays["td_scal"], np.float64)
    for i, key in enumerate(meta["td_keys"]):
        lo, hi = int(off[i]), int(off[i + 1])
        b.td[tuple(key)] = {
            "v": vals[lo:hi].copy(), "w": wts[lo:hi].copy(),
            "min": float(scal[i, 0]), "max": float(scal[i, 1]),
            "count": float(scal[i, 2]), "sum": float(scal[i, 3]),
            "rsum": float(scal[i, 4])}
    mo_vecs = np.asarray(arrays["mo_vecs"], np.float64)
    for i, key in enumerate(meta["mo_keys"]):
        b.mo[tuple(key)] = mo_vecs[i].copy()
    cc_vecs = np.asarray(arrays["cc_vecs"], np.float64)
    for i, key in enumerate(meta["cc_keys"]):
        b.cc[tuple(key)] = cc_vecs[i].copy()
    return b


def encode_bucket_body(b: TierBucket) -> bytes:
    """Bucket -> spill record body (npz-in-bytes with a JSON
    `__meta__` key table)."""
    meta, arrays = bucket_to_arrays(b)
    bio = io.BytesIO()
    np.savez(bio, __meta__=np.frombuffer(
        json.dumps(meta).encode(), np.uint8), **arrays)
    return bio.getvalue()


def decode_bucket_body(body: bytes) -> TierBucket:
    with np.load(io.BytesIO(body), allow_pickle=False) as z:
        meta = json.loads(bytes(np.asarray(z["__meta__"]).tobytes()))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    return bucket_from_arrays(meta, arrays)


class RetentionTier:
    """One resolution: a bounded ring of closed buckets plus the open
    bucket currently absorbing cuts."""

    def __init__(self, name: str, bucket_seconds: float,
                 capacity: int):
        if bucket_seconds <= 0:
            raise ValueError(f"retention tier {name!r}: bucket "
                             f"seconds must be > 0, got {bucket_seconds}")
        if capacity < 1:
            raise ValueError(f"retention tier {name!r}: capacity "
                             f"must be >= 1, got {capacity}")
        self.name = name
        self.bucket_seconds = float(bucket_seconds)
        self.capacity = int(capacity)
        self.buckets: deque[TierBucket] = deque()
        self.open: Optional[TierBucket] = None
        self.closed_total = 0
        self.evicted = 0

    def stats(self) -> dict:
        held = list(self.buckets)
        if self.open is not None:
            held.append(self.open)
        return {"bucket_seconds": self.bucket_seconds,
                "capacity": self.capacity,
                "buckets": len(self.buckets),
                "open": int(self.open is not None),
                "closed_total": self.closed_total,
                "evicted": self.evicted,
                "points_held": float(sum(b.points for b in held)),
                "bytes_held": int(sum(b.nbytes() for b in held))}


class RetentionTimeline:
    """The tier ladder + the spill store + the checkpoint codec."""

    def __init__(self, tiers: list, store=None,
                 compression: float = 100.0,
                 point_cap: int = BUCKET_POINT_CAP,
                 statsd_fn=None):
        """`tiers` is the config shape: a finest-first list of
        {"seconds": float, "buckets": int[, "name": str]} dicts."""
        if not tiers:
            raise ValueError("retention needs at least one tier")
        self.tiers: list[RetentionTier] = []
        prev = 0.0
        for i, spec in enumerate(tiers):
            secs = float(spec["seconds"])
            if secs <= prev:
                raise ValueError(
                    "retention_tiers must be finest-first with "
                    f"strictly increasing seconds, got {secs} after "
                    f"{prev}")
            prev = secs
            self.tiers.append(RetentionTier(
                str(spec.get("name") or f"t{i}x{int(secs)}s"),
                secs, int(spec.get("buckets", 8))))
        self.store = store
        self.compression = float(compression)
        self.point_cap = int(point_cap)
        self._statsd_fn = statsd_fn or (lambda: None)
        self.lock = threading.Lock()
        self.compactions = 0       # cuts absorbed
        self.points_in = 0.0
        self.last_cut = 0.0
        # coarsest-tier evictions staged under the lock, spilled to
        # disk after it drops (no I/O under the timeline lock)
        self._pending_spill: list = []
        # the flush hook only ENQUEUES (the egress-lane pattern: the
        # flush path hands off, it does not pay O(live keys) part
        # summarization); this worker does extraction + tier merges.
        # Ordering is FIFO so cut positions stay monotone.
        self._cv = threading.Condition()
        self._queued: deque = deque()
        self._compacting = False
        self._stopped = False
        self._worker: Optional[threading.Thread] = None
        self.compact_errors = 0

    # -- the flush-cut hook ---------------------------------------------

    def compact_cut(self, cut: dict, cut_ts: float) -> None:
        """Queue one flush cut: {ring name: (snapshot part, its arena)}
        over the histogram families — the same immutable parts the
        WindowRing slots hold (query threads already read them
        lock-free, so the compaction worker may too).  The flush path
        pays a handoff; `drain()` (and the checkpoint capture) waits
        for the worker to go idle."""
        with self._cv:
            if self._stopped:
                return
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._worker_loop, daemon=True,
                    name="retention-compact")
                self._worker.start()
            self._queued.append((cut, cut_ts))
            self._cv.notify_all()

    def _worker_loop(self) -> None:
        while True:
            with self._cv:
                while not self._queued and not self._stopped:
                    self._cv.wait()
                if not self._queued:
                    return      # stopped and drained (or cleared)
                item = self._queued.popleft()
                self._compacting = True
            try:
                self._compact_one(*item)
            except Exception:
                self.compact_errors += 1
            finally:
                with self._cv:
                    self._compacting = False
                    self._cv.notify_all()

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every queued cut has been compacted (False on
        timeout).  Never call this holding the aggregator lock — the
        worker takes the timeline's own leaf lock only."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._queued or self._compacting:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True

    def close(self, drain: bool = True) -> None:
        """Stop the compaction worker.  `drain=False` is the crash
        path: queued cuts are DISCARDED (exactly what a kill -9 loses
        — they were never checkpointed) so a dying server can't keep
        spilling into a directory its revival reopened."""
        if drain:
            self.drain()
        with self._cv:
            self._queued.clear()
            self._stopped = True
            self._cv.notify_all()
        w = self._worker
        if w is not None and w is not threading.current_thread():
            w.join(timeout=5.0)

    def _compact_one(self, cut: dict, cut_ts: float) -> None:
        td = summarize_digest_part(cut["tdigest"][0], self.point_cap,
                                   self.compression)
        mov = summarize_vector_part(*cut["moments"], "moments")
        ccv = summarize_vector_part(*cut["compactor"], "compactor")
        self.absorb_summaries(td, mov, ccv, cut_ts)

    def absorb_summaries(self, td: dict, mov: dict, ccv: dict,
                         cut_ts: float) -> None:
        """The compact_cut tail: merge one cut's per-key family
        summaries into the tier ladder.  Benchmarks and tests feed
        synthetic summaries (arbitrary cut timestamps) here directly;
        the flush hook arrives via compact_cut's part extraction."""
        pts = (sum(e["count"] for e in td.values())
               + sum(float(v[0]) for v in mov.values())
               + sum(float(v[0]) for v in ccv.values()))
        with self.lock:
            # position the cut by its data window's START (the
            # previous cut), so a cut landing exactly on a bucket
            # boundary files under the bucket its data came from
            pos = self.last_cut if self.last_cut > 0 else cut_ts
            self._feed_locked(0, td, mov, ccv, pos, cut_ts)
            self.last_cut = float(cut_ts)
            self.compactions += 1
            self.points_in += pts
            spills = self._pending_spill
            self._pending_spill = []
        # disk I/O happens OUTSIDE the timeline lock: queries snapshot
        # tier state under it, and a spill stall must not block them
        if self.store is not None:
            for ev in spills:
                self.store.spill(self.tiers[-1].name, ev.t_start,
                                 ev.t_end, int(round(ev.points)),
                                 encode_bucket_body(ev))
            if spills:
                self.store.expire_now()
        from veneur_tpu import scopedstatsd
        statsd = scopedstatsd.ensure(self._statsd_fn())
        statsd.count("retention.compactions_total", 1)
        if pts:
            statsd.count("retention.points_total", pts)

    def _feed_locked(self, ti: int, td: dict, mov: dict, ccv: dict,
                     pos_ts: float, upto: float) -> None:
        tier = self.tiers[ti]
        bs = tier.bucket_seconds
        if tier.open is not None and pos_ts >= tier.open.t_end:
            self._close_locked(ti, tier)
        if tier.open is None:
            start = math.floor(pos_ts / bs) * bs
            tier.open = TierBucket(start, start + bs)
        tier.open.absorb(td, mov, ccv, upto, self.point_cap,
                         self.compression)

    def _close_locked(self, ti: int, tier: RetentionTier) -> None:
        closed = tier.open
        tier.open = None
        tier.buckets.append(closed)
        tier.closed_total += 1
        if ti + 1 < len(self.tiers):
            # cascade: the closed bucket merges into the coarser
            # tier's open bucket, positioned by its OWN start
            self._feed_locked(ti + 1, closed.td, closed.mo, closed.cc,
                              closed.t_start, closed.filled_to)
        while len(tier.buckets) > tier.capacity:
            ev = tier.buckets.popleft()
            tier.evicted += 1
            if ti + 1 < len(self.tiers):
                continue     # its mass lives on in the coarser tier
            # coarsest tier: eviction leaves memory for disk — staged
            # here, written by absorb_summaries AFTER the lock drops
            if self.store is not None:
                self._pending_spill.append(ev)

    # -- the range-query read surface -----------------------------------

    def sources_overlapping(self, t0: float, t1: float) -> list:
        """Finest-first (tier name, bucket_seconds, buckets) triples
        overlapping [t0, t1), open buckets included as snapshots, the
        spill store's on-disk buckets decoded and appended as the
        coarsest source."""
        out = []
        with self.lock:
            for tier in self.tiers:
                bl = [b for b in tier.buckets
                      if b.filled_to > t0 and b.t_start < t1]
                op = tier.open
                if op is not None and op.filled_to > t0 \
                        and op.t_start < t1:
                    bl = bl + [op.snapshot()]
                out.append((tier.name, tier.bucket_seconds, bl))
        if self.store is not None:
            recs = self.store.records_overlapping(t0, t1)
            disk = []
            for rec in recs:
                try:
                    disk.append(decode_bucket_body(
                        self.store.read_body(rec)))
                except Exception:
                    self.store.io_errors += 1
            if disk:
                coarsest = self.tiers[-1]
                out.append((f"{coarsest.name}:disk",
                            coarsest.bucket_seconds, disk))
        return out

    # -- checkpoint (in-memory tiers ride the arena cut) -----------------

    def checkpoint_capture(self) -> tuple[dict, dict]:
        """(meta, arrays) for the aggregator checkpoint: every closed
        AND open bucket of every tier, through the shared codec.
        Drains the compaction queue first so the capture covers every
        cut the flush path has handed off."""
        self.drain()
        meta: dict = {"tiers": [], "compactions": self.compactions,
                      "points_in": self.points_in,
                      "last_cut": self.last_cut}
        arrays: dict = {}
        with self.lock:
            for ti, tier in enumerate(self.tiers):
                held = list(tier.buckets)
                if tier.open is not None:
                    held.append(tier.open)
                tmeta = {"name": tier.name,
                         "bucket_seconds": tier.bucket_seconds,
                         "closed_total": tier.closed_total,
                         "evicted": tier.evicted,
                         "n_buckets": len(held),
                         "open": int(tier.open is not None),
                         "buckets": []}
                for bi, b in enumerate(held):
                    bmeta, barrs = bucket_to_arrays(b)
                    tmeta["buckets"].append(bmeta)
                    for k, v in barrs.items():
                        arrays[f"t{ti}/b{bi}/{k}"] = v
                meta["tiers"].append(tmeta)
        return meta, arrays

    def checkpoint_restore(self, meta: dict, arrays: dict) -> None:
        """Restore the in-memory tiers from a checkpoint capture.
        Tier geometry must match the running config (a geometry change
        cold-starts the timeline instead of mis-filing buckets)."""
        tiers_meta = meta.get("tiers") or []
        if len(tiers_meta) != len(self.tiers) or any(
                float(tm["bucket_seconds"]) != t.bucket_seconds
                for tm, t in zip(tiers_meta, self.tiers)):
            return
        # decode every bucket BEFORE taking the lock (the codec pulls
        # array scalars — a device sync queries must not wait behind)
        decoded: list[list[TierBucket]] = []
        for ti, tm in enumerate(tiers_meta):
            held = []
            for bi, bmeta in enumerate(tm["buckets"]):
                barrs = {k: arrays[f"t{ti}/b{bi}/{k}"]
                         for k in ("td_off", "td_vals", "td_wts",
                                   "td_scal", "mo_vecs",
                                   "cc_vecs")}
                held.append(bucket_from_arrays(bmeta, barrs))
            decoded.append(held)
        with self.lock:
            self.compactions = int(meta.get("compactions", 0))
            self.points_in = float(meta.get("points_in", 0.0))
            self.last_cut = float(meta.get("last_cut", 0.0))
            for tm, tier, held in zip(tiers_meta, self.tiers,
                                      decoded):
                tier.closed_total = int(tm.get("closed_total", 0))
                tier.evicted = int(tm.get("evicted", 0))
                tier.buckets.clear()
                tier.open = None
                if tm.get("open") and held:
                    tier.open = held.pop()
                tier.buckets.extend(held)

    # -- observability ---------------------------------------------------

    def footprint_bytes(self) -> int:
        with self.lock:
            mem = sum(t.stats()["bytes_held"] for t in self.tiers)
        disk = self.store.stats()["pending_bytes"] \
            if self.store is not None else 0
        return int(mem + disk)

    def stats(self) -> dict:
        with self.lock:
            tiers = {t.name: t.stats() for t in self.tiers}
            out = {"tiers": tiers,
                   "compactions": self.compactions,
                   "points_in": self.points_in,
                   "last_cut_unix": self.last_cut,
                   "pending_cuts": len(self._queued),
                   "compact_errors": self.compact_errors,
                   "buckets": int(sum(
                       s["buckets"] + s["open"]
                       for s in tiers.values()))}
        # the spill store's ledger fields flatten to THIS level (zeros
        # when spill is off): the telemetry witness asserts the
        # closure spilled + recovered == expired + dropped + pending
        # directly over /debug/vars -> retention
        store_stats = self.store.stats() if self.store is not None \
            else {k: 0 for k in (
                "pending_buckets", "pending_bytes", "pending_points",
                "spilled_buckets", "spilled_points",
                "recovered_buckets", "recovered_points",
                "expired_buckets", "expired_points",
                "dropped_buckets", "dropped_points", "torn_records",
                "crc_rejected", "io_errors", "reads")}
        out.update(store_stats)
        out["on_disk_bytes"] = store_stats["pending_bytes"]
        out["footprint_bytes"] = self.footprint_bytes()
        return out
