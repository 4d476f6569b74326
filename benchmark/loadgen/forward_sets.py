"""Generator kind `forward_sets`: a fleet's set sketches as load.

`set_keys` set keys (`bench.s.<k>`), whose sizes follow Zipf(1.0) over
rank and sum to `members_total` distinct members; `locals` forwarders
(the repo's own `ForwardClient`s, all in this one child), each of which
saw two thirds of every set: member i of a set goes to local 0 if
i % 3 is 0 or 1 and to local 1 if it is 1 or 2, so a third of every set
is seen by both and the union is not the sum.  `variants` seeded
rank -> key permutations are used in turn, so the hot sets move every
interval.

The MODEL half (`model`) is numpy only and is what
`reference/forward_sets.py` reads: it hashes members with its own seeded
64-bit mixer, builds each local's 2^precision HyperLogLog registers per
key, and says which wire form the sender will choose.  The SENDER half
puts those registers on the wire with the program's `hll.marshal` (the
axiomhq MarshalBinary codec: sparse or dense by size) and protobufs,
which are the system's wire, not its answers.  Protobufs are built once
in set-up and re-sent with a fresh epoch each interval, so the global's
dedup ledger admits them.
"""

from __future__ import annotations

import threading
import time

import numpy as np

PREFIX = "bench"
# keys hashed and marshalled at a time: bounds the model's working set
# (two locals x BLOCK x 2^precision register bytes)
BLOCK = 2048


def key_tags(k: int) -> str:
    """`loadgen/forward.py`'s tags: 4 a key."""
    return f"svc:s{k % 61},region:r{k % 7},az:z{k % 3},shard:{k % 1021}"


def sizes_by_rank(p: dict) -> np.ndarray:
    """floor(C / (r + 1)) members for rank r, with C the largest for
    which they sum to at most `members_total`; what is left over goes to
    rank 0, so the sum is exact."""
    n, total = p["set_keys"], p["members_total"]
    ranks = np.arange(1, n + 1, dtype=np.float64)
    lo, hi = 0.0, float(total)
    for _ in range(100):
        mid = (lo + hi) / 2
        if np.floor(mid / ranks).sum() <= total:
            lo = mid
        else:
            hi = mid
    sizes = np.floor(lo / ranks).astype(np.int64)
    sizes[0] += total - int(sizes.sum())
    return sizes


def key_of_rank(seed: int, p: dict, variant: int) -> np.ndarray:
    """The variant's rank -> key permutation."""
    return np.random.default_rng(
        [int(seed), 5, int(variant)]).permutation(p["set_keys"])


def sizes_by_key(seed: int, p: dict, variant: int) -> np.ndarray:
    out = np.empty(p["set_keys"], np.int64)
    out[key_of_rank(seed, p, variant)] = sizes_by_rank(p)
    return out


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser (Steele, Lea, Flood 2014)."""
    x = x.copy()
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def is_dense_on_wire(occupied: np.ndarray, m: int) -> np.ndarray:
    """The sender's choice of form, by size: sparse (at most 4 bytes an
    occupied register, + 20) while that undercuts the dense m/2 + 9."""
    return occupied * 4 + 20 >= m // 2 + 9


def model(seed: int, p: dict, variant: int, keys) -> dict:
    """For the given keys: `sizes` (true distinct members), `regs`
    [locals, keys, m] u8 (each local's registers, before the wire) and
    `dense` [locals, keys] (the form the local's sketch takes on the
    wire; the dense form clamps ranks to 15)."""
    keys = np.asarray(keys, np.int64)
    prec = int(p["precision"])
    m = 1 << prec
    sizes = sizes_by_key(seed, p, variant)[keys]
    total = int(sizes.sum())
    pos = np.repeat(np.arange(len(keys), dtype=np.int64), sizes)
    i = np.arange(total, dtype=np.int64) - np.repeat(
        np.cumsum(sizes) - sizes, sizes)
    salt = _mix64(np.asarray([int(seed) & 0xFFFFFFFF], np.uint64))[0]
    h = _mix64(((keys[pos].astype(np.uint64) << np.uint64(32))
                | i.astype(np.uint64)) ^ salt)
    idx = (h >> np.uint64(64 - prec)).astype(np.int64)
    # rank = leading zeros of the remaining 64 - p bits, + 1 (a sentinel
    # bit below them bounds it at 64 - p + 1)
    w = (h << np.uint64(prec)) | np.uint64(1 << (prec - 1))
    for s in (1, 2, 4, 8, 16, 32):
        w |= w >> np.uint64(s)
    rank = (65 - np.bitwise_count(w)).astype(np.uint8)
    flat = pos * m + idx
    regs = np.zeros((p["locals"], len(keys) * m), np.uint8)
    # member i: local 0 sees i % 3 in {0, 1}, local 1 sees {1, 2}
    third = i % 3
    for loc, skip in zip(range(p["locals"]), (2, 0)):
        sel = third != skip
        np.maximum.at(regs[loc], flat[sel], rank[sel])
    regs = regs.reshape(p["locals"], len(keys), m)
    return {"sizes": sizes, "regs": regs,
            "dense": is_dense_on_wire((regs != 0).sum(axis=2), m)}


def ledger(p: dict) -> dict:
    """What one interval offers: no statsd lines; locals x set_keys
    sketches, set_keys to a forward (what a late forward fails)."""
    return {"lines": 0, "counted_lines": 0,
            "digests": p["locals"] * p["set_keys"],
            "digests_per_forward": p["set_keys"],
            "label": "import (forwards arriving)"}


# -- the child's side -------------------------------------------------------

def _build_pbs(seed: int, p: dict, variant: int) -> tuple:
    """Per local, one Set protobuf per key; and what went on the wire:
    records by form, (register, rank) pairs in the sparse ones."""
    from veneur_tpu.protocol import metric_pb2
    from veneur_tpu.sketches import hll

    out = [[] for _ in range(p["locals"])]
    dense = pairs = 0
    for k0 in range(0, p["set_keys"], BLOCK):
        keys = np.arange(k0, min(k0 + BLOCK, p["set_keys"]))
        m = model(seed, p, variant, keys)
        for loc in range(p["locals"]):
            for j, k in enumerate(keys.tolist()):
                pb = metric_pb2.Metric(name=f"{PREFIX}.s.{k}",
                                       tags=key_tags(k).split(","),
                                       type=metric_pb2.Set,
                                       scope=metric_pb2.Mixed)
                pb.set.hyper_log_log = hll.marshal(m["regs"][loc, j])
                out[loc].append(pb)
        dense += int(m["dense"].sum())
        pairs += int((m["regs"] != 0).sum(axis=2)[~m["dense"]].sum())
    return out, dense, pairs


def prepare(spec: dict) -> dict:
    from veneur_tpu.forward.client import BATCH_MAX

    p = spec["traffic"]
    payloads, wire = [], []
    for v in range(p["variants"]):
        pbs, dense, pairs = _build_pbs(spec["seed"], p, v)
        payloads.append(pbs)
        wire.append({"dense_records": dense, "sparse_pairs": pairs})
    return {"payloads": payloads, "clients": [], "traffic": p,
            "ready": {"digests": ledger(p)["digests"],
                      "chunk": BATCH_MAX,
                      "chunks_per_local": -(-p["set_keys"] // BATCH_MAX),
                      "wire": wire}}


def connect(state: dict, targets: dict) -> None:
    from veneur_tpu.forward.client import ForwardClient

    p = state["traffic"]
    addr = "%s:%d" % tuple(targets["grpc"])
    state["clients"] = [ForwardClient(addr, timeout_s=p["rpc_timeout_s"],
                                      source=f"bench-local{loc}")
                        for loc in range(p["locals"])]


def send_interval(state: dict, spec: dict, interval: int, due: float) -> dict:
    """All locals start their forward together; each reports when its last
    chunk was acked, relative to `due`.  Open loop: a local whose last
    forward is still in flight starts this one anyway."""
    p = spec["traffic"]
    payload = state["payloads"][interval % p["variants"]]
    acks: list = [None] * p["locals"]
    sent: list = [None] * p["locals"]
    errors: list = []

    def one(loc: int) -> None:
        sent[loc] = time.time() - due
        try:
            state["clients"][loc].send_pbs(payload[loc], epoch=interval + 1)
            acks[loc] = time.time() - due
        except Exception as e:      # noqa: BLE001 - reported to the parent
            errors.append(f"local{loc}: {type(e).__name__}: {e}"[:200])

    threads = [threading.Thread(target=one, args=(loc,))
               for loc in range(p["locals"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"sent_s": sent, "ack_s": acks, "errors": errors,
            "digests": ledger(p)["digests"]}


def close(state: dict) -> None:
    for c in state["clients"]:
        c.close()
