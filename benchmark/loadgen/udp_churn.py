"""Generator kind `udp_churn`: `udp_zipf`'s lines over one UDP socket while
a rolling deploy renames a share of the timer and counter keys every
interval (YCSB core workload D's insert proportion, laid on the key SPACE).

The timer and counter keys of `udp_zipf` become SLOTS.  A seeded
permutation of each family's slots is cut into `churn_period` blocks;
deploy interval n renames block n % `churn_period`: every slot of it gets
a new identity (its generation, one more), in the metric NAME and in a
`version:<generation>` tag beside `udp.key_tags`' four, and the old name
never receives a line again.  A slot keeps its popularity: the new
version's endpoint takes the old one's traffic.  With `churn_share` x
`churn_period` = 1 each slot is renamed exactly once in a period.  Gauge
and set keys keep their names.  Everything else — Zipf ranks, the two rank
-> slot permutations in turn, values, the other families' lines, order,
datagrams, pacing — is `udp_zipf`'s, read from its model and sent by
`udp`'s sender; this file imports both and edits neither.

A row of the server dies `IDLE_GC_INTERVALS` (10) cuts after its name's
last line, and the harness opens its window after as few as three warm
intervals, so `connect` sends the deploy's first `aged_intervals` itself,
by its own clock on the wall-clock grid of the interval (where the
server's ticks fall), before the child says `ready`: interval k of the run
is interval k + `aged_intervals` of the deploy (`deploy_interval`), and
every measured flush frees what it registers.

Two halves, both pure functions of (seed, parameters, the deploy's
interval number): the MODEL (`model`; the reference reads it) and the
SENDER.  A payload is built by a thread of its own between two sends, at
least one interval before its own, never inside the send.  numpy and
sockets only.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_loadgen_{name}_base",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


zipf = _beside("udp_zipf")
udp = zipf.udp

PREFIX = udp.PREFIX
ledger, rank_shares = zipf.ledger, zipf.rank_shares
FAMILIES = {"t": ("timer_keys", 17), "c": ("counter_keys", 19)}


def deploy_interval(p: dict, run_interval: int) -> int:
    return int(run_interval) + int(p["aged_intervals"])


def slot_block(seed: int, p: dict, family: str) -> np.ndarray:
    """block[slot]: which of the period's intervals renames the slot."""
    key, stream = FAMILIES[family]
    n, period = int(p[key]), int(p["churn_period"])
    if abs(float(p["churn_share"]) * period - 1.0) > 1e-9:
        raise ValueError("churn_share x churn_period must be 1: every "
                         "slot is renamed once a period")
    block = np.empty(n, np.int64)
    block[np.random.default_rng([int(seed), stream]).permutation(n)] = \
        np.arange(n) * period // n
    return block


def generation(block: np.ndarray, p: dict, n: int) -> np.ndarray:
    """gen[slot] in deploy interval n: the renames it has had, interval n's
    included (interval 0 renames block 0)."""
    period = int(p["churn_period"])
    return (int(n) + period - block) // period


def renamed(block: np.ndarray, p: dict, n: int) -> np.ndarray:
    """The slots deploy interval n renames."""
    return np.nonzero(block == int(n) % int(p["churn_period"]))[0]


def timer_name(slot: int, gen: int) -> str:
    return f"{PREFIX}.t.{slot}.v{gen}"


def counter_name(slot: int, gen: int) -> str:
    return f"{PREFIX}.c.{slot}.v{gen}"


def model(seed: int, p: dict, n: int, base: dict = None) -> dict:
    """Deploy interval n's lines as arrays: `udp_zipf`'s model of variant
    n % variants (its keys are the slots), and each timer and counter
    slot's generation.  `base` may hold the variants' models already."""
    v = int(n) % int(p["variants"])
    m = dict(base[v] if base is not None else zipf.model(seed, p, v))
    for fam in FAMILIES:
        block = slot_block(seed, p, fam)
        m[f"{fam}_gen"] = generation(block, p, n)
        m[f"{fam}_renamed"] = renamed(block, p, n)
    return m


def timer_lines(m: dict, at: np.ndarray = None) -> list[bytes]:
    """The timer lines at positions `at` of the send order (all of them
    where it is None)."""
    pre = PREFIX.encode()
    keys, vals = (m["t_key"], m["t_val"]) if at is None else \
        (m["t_key"][at], m["t_val"][at])
    gens = m["t_gen"]
    ends = {k: (b"%s.t.%d.v%d:" % (pre, k, gens[k]),
                b"|ms|#%s,version:%d" % (udp.key_tags(k).encode(), gens[k]))
            for k in np.unique(keys).tolist()}
    return [ends[k][0] + b"%.3f" % v + ends[k][1]
            for k, v in zip(keys.tolist(), vals.tolist())]


def counter_lines(m: dict, at: np.ndarray = None) -> list[bytes]:
    pre = PREFIX.encode()
    keys, vals = (m["c_key"], m["c_val"]) if at is None else \
        (m["c_key"][at], m["c_val"][at])
    gens = m["c_gen"].tolist()
    return [b"%s.c.%d.v%d:%d|c|#version:%d" % (pre, k, gens[k], v, gens[k])
            for k, v in zip(keys.tolist(), vals.tolist())]


def format_lines(m: dict, p: dict) -> list[bytes]:
    pre = PREFIX.encode()
    lines = timer_lines(m) + counter_lines(m)
    lines += [b"%s.g.%d:%.3f|g" % (pre, k, v)
              for k, v in zip(m["g_key"].tolist(), m["g_val"].tolist())]
    lines += [b"%s.s.%d:m%d|s" % (pre, k, v)
              for k, v in zip(m["s_key"].tolist(), m["s_mem"].tolist())]
    return lines


# -- the child's side -------------------------------------------------------

def _build(state: dict, n: int) -> list[bytes]:
    """Deploy interval n's datagrams: `format_lines` of its model, packed.
    A variant's lines are kept from its last build and only those of the
    slots renamed since are written anew (a tenth of them: the build must
    fit between the end of a send and the next tick, where the server's
    flush starts)."""
    spec = state["spec"]
    p = spec["traffic"]
    m = model(spec["seed"], p, n, state["base"])
    kept = state["lines"].get(n % p["variants"])
    if kept is None:
        lines = format_lines(m, p)
    else:
        was, lines = kept
        lines = list(lines)
        n_t = len(m["t_key"])
        at = np.nonzero((m["t_gen"] != was["t_gen"])[m["t_key"]])[0]
        for i, ln in zip(at.tolist(), timer_lines(m, at)):
            lines[i] = ln
        at = np.nonzero((m["c_gen"] != was["c_gen"])[m["c_key"]])[0]
        for i, ln in zip(at.tolist(), counter_lines(m, at)):
            lines[n_t + i] = ln
    state["lines"][n % p["variants"]] = (m, lines)
    return udp.pack(lines, p["max_datagram_bytes"])


def _builder(state: dict) -> None:
    cond = state["cond"]
    while True:
        with cond:
            while not state["asked"] and not state["closed"]:
                cond.wait()
            if state["closed"]:
                return
            n = state["asked"].pop(0)
        dgs = _build(state, n)
        with cond:
            state["payloads"][n] = dgs
            cond.notify_all()


def _ask(state: dict, n: int) -> None:
    with state["cond"]:
        if n not in state["payloads"] and n not in state["asked"]:
            state["asked"].append(n)
            state["cond"].notify_all()


def _take(state: dict, n: int) -> list[bytes]:
    """Deploy interval n's datagrams (built long since, as a rule)."""
    _ask(state, n)
    with state["cond"]:
        while n not in state["payloads"]:
            state["cond"].wait()
        return state["payloads"].pop(n)


def _send(state: dict, n: int, due: float) -> dict:
    spec = state["spec"]
    dgs = _take(state, n)
    # udp's sender, on this interval's datagrams
    rep = udp.send_interval(
        {"payloads": [dgs], "sock": state["sock"], "addr": state["addr"]},
        {"traffic": dict(spec["traffic"], variants=1),
         "interval_s": spec["interval_s"]}, 0, due)
    # the build of the interval after next starts now, between two sends
    _ask(state, n + 2)
    rep["deploy_interval"] = n
    return rep


def prepare(spec: dict) -> dict:
    p = spec["traffic"]
    state = {"spec": spec, "sock": None, "payloads": {}, "asked": [],
             "lines": {}, "closed": False, "cond": threading.Condition(),
             "base": {v: zipf.model(spec["seed"], p, v)
                      for v in range(p["variants"])}}
    for n in (0, 1):
        state["payloads"][n] = _build(state, n)
    state["ready"] = {"datagrams": [len(state["payloads"][n])
                                    for n in (0, 1)],
                      "lines": ledger(p)["lines"],
                      "aged_intervals": int(p["aged_intervals"])}
    threading.Thread(target=_builder, args=(state,), daemon=True).start()
    return state


def connect(state: dict, targets: dict) -> None:
    """Open the socket and send the deploy's first `aged_intervals`, so
    the first interval of the run already frees rows."""
    udp.connect(state, targets)
    spec = state["spec"]
    I = float(spec["interval_s"])
    # the server's ticks are wall-clock multiples of I (plus a phase of
    # milliseconds): the aged intervals lie between them as the run's
    # will, so no name's last lines straddle a cut and the run's first
    # interval follows the last aged one without an empty flush between
    tick = (int(time.time() / I) + 1) * I
    aged = int(spec["traffic"]["aged_intervals"])
    state["aged"] = []
    for n in range(aged):
        due = tick + (n + spec["traffic"]["due_share"]) * I
        while time.time() < due:
            time.sleep(min(0.05, max(due - time.time(), 0.0)))
        t = time.time()
        rep = _send(state, n, due)
        # numbered below 0: interval 0 of the run is the deploy's `aged`
        rep.update(interval=n - aged, start_late_s=max(t - due, 0.0),
                   send_s=time.time() - t)
        state["aged"].append(rep)


def send_interval(state: dict, spec: dict, interval: int, due: float) -> dict:
    # the aged intervals' reports first (the child's first line has to be
    # `ready`): the harness holds the engine's line count against every
    # report it was given
    for rep in state.pop("aged", []):
        sys.stdout.write(json.dumps(rep) + "\n")
    sys.stdout.flush()
    return _send(state, deploy_interval(spec["traffic"], interval), due)


def close(state: dict) -> None:
    with state["cond"]:
        state["closed"] = True
        state["cond"].notify_all()
    udp.close(state)
