"""Generator kind `udp_zipf`: DogStatsD lines over one UDP socket, the
timer keys' popularity Zipfian and the hot set moving every interval.

The `udp` kind gives every timer key the same number of samples; here the
number of lines a key receives in an interval is a seeded multinomial draw
with p(rank r) ~ r ** -zipf_constant over `timer_keys` ranks (YCSB's
`ZipfianGenerator`, ZIPFIAN_CONSTANT = 0.99), each variant maps rank ->
key by its own seeded permutation, and intervals take the variants in
turn: the key that was hottest in one interval is an average key in the
next.  Timer lines go out in seeded random order — a hot key's samples
are spread over the whole send, not sent as a block — followed by the
`udp` kind's uniform counter, gauge and set lines in its order (gauges
are last-write-wins, and the reference reads the send order).

Two halves, both pure functions of (seed, parameters), as `udp`: the
traffic MODEL (`model`; the reference reads it to know what was sent) and
the SENDER (`prepare` / `send_interval`, in the load-generator child),
which is `udp`'s own — this file imports `udp.py` for its datagram
packing, tags, socket and pacing, and edits none of it.  numpy and
sockets only.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_loadgen_udp_base",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "udp.py"))
udp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(udp)

PREFIX = udp.PREFIX
connect, send_interval, close = udp.connect, udp.send_interval, udp.close


def rank_shares(p: dict) -> np.ndarray:
    """p(rank r), r = 1 .. timer_keys."""
    w = np.arange(1, p["timer_keys"] + 1, dtype=np.float64) \
        ** -float(p["zipf_constant"])
    return w / w.sum()


def model(seed: int, p: dict, variant: int) -> dict:
    """One interval's lines as arrays, in send order within each family.
    `rank_key[r]` is the key at rank r (0 = hottest) of this variant,
    `key_count[k]` the timer lines key k receives, `t_key` / `t_val` the
    timer lines in send order."""
    rng = np.random.default_rng([int(seed), 11, int(variant)])
    keys, n_t = p["timer_keys"], p["timer_lines"]
    rank_count = rng.multinomial(n_t, rank_shares(p))
    rank_key = rng.permutation(keys)
    key_count = np.zeros(keys, np.int64)
    key_count[rank_key] = rank_count
    t_key = np.repeat(rank_key, rank_count)[rng.permutation(n_t)]
    n_c, n_g, n_s = p["counter_lines"], p["gauge_lines"], p["set_lines"]
    return {
        "rank_key": rank_key, "key_count": key_count, "t_key": t_key,
        # rounded to the 3 decimals that cross the wire, so the ledger
        # holds exactly what the text says
        "t_val": np.round(rng.gamma(2.0, 10.0, n_t), 3),
        "c_key": rng.integers(0, p["counter_keys"], n_c),
        "c_val": rng.integers(1, 10, n_c),
        "g_key": rng.integers(0, p["gauge_keys"], n_g),
        "g_val": np.round(rng.uniform(0.0, 1000.0, n_g), 3),
        # skewed: cardinalities from a handful to a few % of the lines
        "s_key": (p["set_keys"] * rng.random(n_s) ** 2).astype(np.int64),
        "s_mem": rng.integers(0, p["set_members"], n_s),
    }


def ledger(p: dict) -> dict:
    """What one interval offers: statsd lines, those of them the sink's
    `.count` aggregates account for one by one, forwarded digests."""
    return {"lines": (p["timer_lines"] + p["counter_lines"]
                      + p["gauge_lines"] + p["set_lines"]),
            "counted_lines": p["timer_lines"], "digests": 0,
            "digests_per_forward": 0,
            "label": "ingest (datagrams arriving)"}


def format_lines(m: dict, p: dict) -> list[bytes]:
    pre = PREFIX.encode()
    tags = [udp.key_tags(k).encode() for k in range(p["timer_keys"])]
    lines = [b"%s.t.%d:%.3f|ms|#%s" % (pre, k, v, tags[k])
             for k, v in zip(m["t_key"].tolist(), m["t_val"].tolist())]
    lines += [b"%s.c.%d:%d|c" % (pre, k, v)
              for k, v in zip(m["c_key"].tolist(), m["c_val"].tolist())]
    lines += [b"%s.g.%d:%.3f|g" % (pre, k, v)
              for k, v in zip(m["g_key"].tolist(), m["g_val"].tolist())]
    lines += [b"%s.s.%d:m%d|s" % (pre, k, v)
              for k, v in zip(m["s_key"].tolist(), m["s_mem"].tolist())]
    return lines


def prepare(spec: dict) -> dict:
    p = spec["traffic"]
    payloads = [udp.pack(format_lines(model(spec["seed"], p, v), p),
                         p["max_datagram_bytes"])
                for v in range(p["variants"])]
    return {"payloads": payloads, "sock": None,
            "ready": {"datagrams": [len(d) for d in payloads],
                      "lines": ledger(p)["lines"]}}
