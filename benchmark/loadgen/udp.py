"""Generator kind `udp`: DogStatsD lines over one UDP socket.

Two halves, both pure functions of (seed, parameters):

  * the traffic MODEL (`model`): what one interval sends — timer values,
    counter / gauge / set lines — as numpy arrays.  The reference
    (`reference/udp.py`) reads the same model to know what was sent;
  * the SENDER (`prepare` / `send_interval`), run in the load-generator
    child: formats the model as multi-line datagrams once, in set-up, and
    sends one interval's datagrams paced in bursts.

An interval's payload is one of `variants` seeded payloads, used in turn
(interval k sends variant k % variants), so formatting 400k lines in
Python never competes with the send schedule and a flush that returned
the previous interval's answers would still be caught.

Copied from chip_smoke.py (`pack`, `_send_paced`, `histo_values`,
`global_traffic`, `histo_tags`), parameterised.  numpy and sockets only.
"""

from __future__ import annotations

import socket
import time

import numpy as np

PREFIX = "bench"


def key_tags(k: int) -> str:
    """4 tags per key (the smoke's `histo_tags`)."""
    return f"svc:s{k % 61},region:r{k % 7},az:z{k % 3},shard:{k % 1021}"


def model(seed: int, p: dict, variant: int) -> dict:
    """One interval's lines as arrays, in send order within each family."""
    rng = np.random.default_rng([int(seed), 1, int(variant)])
    # rounded to the 3 decimals that cross the wire, so the ledger holds
    # exactly what the text says
    t_val = np.round(rng.gamma(2.0, 10.0, (p["timer_keys"],
                                           p["samples_per_key"])), 3)
    n_c, n_g, n_s = p["counter_lines"], p["gauge_lines"], p["set_lines"]
    return {
        "t_val": t_val,
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
    timer_lines = p["timer_keys"] * p["samples_per_key"]
    return {"lines": (timer_lines + p["counter_lines"] + p["gauge_lines"]
                      + p["set_lines"]),
            "counted_lines": timer_lines, "digests": 0,
            "digests_per_forward": 0,
            "label": "ingest (datagrams arriving)"}


def format_lines(m: dict, p: dict) -> list[bytes]:
    keys, s = m["t_val"].shape
    tags = [key_tags(k).encode() for k in range(keys)]
    vals = m["t_val"].tolist()
    # sample-major: consecutive lines are different keys
    lines = [b"%s.t.%d:%.3f|ms|#%s" % (PREFIX.encode(), k, vals[k][j],
                                       tags[k])
             for j in range(s) for k in range(keys)]
    pre = PREFIX.encode()
    lines += [b"%s.c.%d:%d|c" % (pre, k, v)
              for k, v in zip(m["c_key"].tolist(), m["c_val"].tolist())]
    lines += [b"%s.g.%d:%.3f|g" % (pre, k, v)
              for k, v in zip(m["g_key"].tolist(), m["g_val"].tolist())]
    lines += [b"%s.s.%d:m%d|s" % (pre, k, v)
              for k, v in zip(m["s_key"].tolist(), m["s_mem"].tolist())]
    return lines


def pack(lines: list[bytes], max_datagram: int) -> list[bytes]:
    """Newline-joined datagrams of at most `max_datagram` bytes."""
    out, cur, size = [], [], 0
    for ln in lines:
        if cur and size + 1 + len(ln) > max_datagram:
            out.append(b"\n".join(cur))
            cur, size = [], 0
        cur.append(ln)
        size += len(ln) + (1 if size else 0)
    if cur:
        out.append(b"\n".join(cur))
    return out


# -- the child's side -------------------------------------------------------

def prepare(spec: dict) -> dict:
    p = spec["traffic"]
    payloads = [pack(format_lines(model(spec["seed"], p, v), p),
                     p["max_datagram_bytes"])
                for v in range(p["variants"])]
    return {"payloads": payloads, "sock": None,
            "ready": {"datagrams": [len(d) for d in payloads],
                      "lines": ledger(p)["lines"]}}


def connect(state: dict, targets: dict) -> None:
    state["addr"] = tuple(targets["statsd_udp"])
    state["sock"] = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)


def send_interval(state: dict, spec: dict, interval: int, due: float) -> dict:
    """Send one interval's datagrams, paced over `pace_share` of the
    interval in bursts of `burst`.  Open loop: never waits for the server."""
    p = spec["traffic"]
    dgs = state["payloads"][interval % p["variants"]]
    sock, addr = state["sock"], state["addr"]
    burst = p["burst"]
    rate = len(dgs) / (p["pace_share"] * spec["interval_s"])
    t0 = time.perf_counter()
    for i in range(0, len(dgs), burst):
        for d in dgs[i:i + burst]:
            sock.sendto(d, addr)
        ahead = (i + burst) / rate - (time.perf_counter() - t0)
        if ahead > 0:
            time.sleep(ahead)
    return {"datagrams": len(dgs)}


def close(state: dict) -> None:
    if state["sock"] is not None:
        state["sock"].close()
