"""Generator kind `forward`: a fleet of local veneurs as load.

Each of `locals` forwarders is one of the repo's own `ForwardClient`s
(real forwardrpc gRPC, batched `SendMetrics` chunks with chunk identities,
the wire a local veneur speaks), all in this one child.  A local's
payload is one digest per key: `samples_per_digest` gamma(2, 10) samples
drawn from the seed, sorted, and averaged in groups of
`samples_per_centroid`, so every digest carries
samples_per_digest / samples_per_centroid WEIGHTED centroids with the
samples' true min / max / sum / reciprocal sum.  Protobufs are built once
in set-up (`variants` seeded payloads used in turn) and re-sent with a
fresh epoch each interval: chunk identities differ, so the global's dedup
ledger admits them.

The MODEL half (`model`) is numpy only and is what `reference/forward.py`
reads; the SENDER half imports the program's client and protobufs, which
are the system's wire, not its answers.
"""

from __future__ import annotations

import threading
import time

import numpy as np

PREFIX = "bench"


def key_tags(k: int) -> str:
    return f"svc:s{k % 61},region:r{k % 7},az:z{k % 3},shard:{k % 1021}"


def model(seed: int, p: dict, variant: int) -> dict:
    """samples [locals, keys, samples_per_digest] sorted along the last
    axis, and the centroids each local forwards."""
    rng = np.random.default_rng([int(seed), 2, int(variant)])
    n, g = p["samples_per_digest"], p["samples_per_centroid"]
    samples = np.sort(rng.gamma(2.0, 10.0, (p["locals"], p["keys_per_local"],
                                            n)), axis=2)
    means = samples.reshape(p["locals"], p["keys_per_local"], n // g,
                            g).mean(axis=3)
    return {"samples": samples, "means": means,
            "weights": np.full(means.shape, float(g))}


def ledger(p: dict) -> dict:
    """What one interval offers: no statsd lines; locals x keys digests,
    keys_per_local to a forward (what a late forward fails)."""
    return {"lines": 0, "counted_lines": 0,
            "digests": p["locals"] * p["keys_per_local"],
            "digests_per_forward": p["keys_per_local"],
            "label": "import (forwards arriving)"}


# -- the child's side -------------------------------------------------------

def _build_pbs(m: dict, local: int, p: dict) -> list:
    from veneur_tpu.protocol import metric_pb2, tdigest_pb2

    samples, means = m["samples"][local], m["means"][local].tolist()
    mins, maxs = samples[:, 0].tolist(), samples[:, -1].tolist()
    rsums = (1.0 / np.maximum(samples, 1e-9)).sum(axis=1).tolist()
    w = float(p["samples_per_centroid"])
    out = []
    for k in range(p["keys_per_local"]):
        td = tdigest_pb2.MergingDigestData(
            compression=100.0, min=mins[k], max=maxs[k],
            reciprocalSum=rsums[k])
        for mean in means[k]:
            td.main_centroids.add(mean=mean, weight=w)
        pb = metric_pb2.Metric(name=f"{PREFIX}.h.{k}",
                               tags=key_tags(k).split(","),
                               type=metric_pb2.Histogram,
                               scope=metric_pb2.Mixed)
        pb.histogram.t_digest.CopyFrom(td)
        out.append(pb)
    return out


def prepare(spec: dict) -> dict:
    from veneur_tpu.forward.client import BATCH_MAX

    p = spec["traffic"]
    payloads = []
    for v in range(p["variants"]):
        m = model(spec["seed"], p, v)
        payloads.append([_build_pbs(m, loc, p) for loc in range(p["locals"])])
    return {"payloads": payloads, "clients": [], "traffic": p,
            "ready": {"digests": ledger(p)["digests"],
                      "chunk": BATCH_MAX,
                      "chunks_per_local": -(-p["keys_per_local"]
                                            // BATCH_MAX)}}


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
