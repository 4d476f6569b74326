"""Generator kind `forward_stream`: a fleet of REFERENCE senders as load.

What an upstream (Go) veneur local puts on the wire: per flush ONE
`SendMetricsV2(stream metricpb.Metric)` client stream, one message a
metric, then `CloseAndRecv` (`flusher.go:578-591` `forwardGrpc`;
`forwardrpc/forward.proto:9-13`).  So this kind does not use the repo's
`ForwardClient` (V1 `SendMetrics` batches; its V2 fallback fans out over
parallel streams, which no upstream sender does): each local is a plain
`grpc` channel and `channel.stream_unary("/forwardrpc.Forward/
SendMetricsV2")` with the identity serialiser, over messages serialised
once in set-up.

**One sender process per local** (`sender_processes`; local `l` lives in
process `l % sender_processes`): a Go local sends at wire speed, and one
Python process for all eight would pace the global by its own
interpreter lock.  The senders are children of the load-generator child
(`python forward_stream.py --sender`, a JSON line each way per step, as
`run.py --loadgen` itself is driven); each builds its locals' messages
from the seed, alone, and ends when its standard input closes.

The payload is `loadgen/forward.py`'s — its `model`, tags, protobufs and
`ledger`, imported, not copied: `locals` x `keys_per_local` digests of
samples_per_digest / samples_per_centroid weighted centroids, the same
keys from every local, `variants` seeded payloads in turn.

Open loop on the interval schedule: `send_interval` tells every sender
the interval and when it was due; a sender starts that stream at once,
in a thread of its own, whether or not its last one has ended, and
reports `sent_s` (stream opened) and `ack_s` (response received), both
relative to `due`, or the error that ended it (`rpc_timeout_s`: a sender
never hangs on a global that cannot keep up).
"""

from __future__ import annotations

import importlib.util
import json
import os
import queue
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEND_METRICS_V2 = "/forwardrpc.Forward/SendMetricsV2"
BUILD_TIMEOUT_S = 600.0


def _forward():
    """`loadgen/forward.py`, by path (run.py loads kinds the same way)."""
    spec = importlib.util.spec_from_file_location(
        "bench_loadgen_forward", os.path.join(HERE, "forward.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_fwd = _forward()
PREFIX = _fwd.PREFIX
key_tags = _fwd.key_tags
model = _fwd.model
ledger = _fwd.ledger


# -- the load-generator child's side ---------------------------------------

class _Sender:
    """One sender process and what it has said."""

    def __init__(self, index: int, spec: dict):
        self.index = index
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--sender"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ), cwd=os.path.dirname(os.path.dirname(HERE)))
        self.said: queue.Queue = queue.Queue()      # set-up lines
        self.replies: dict = {}     # interval -> Queue of its reports
        self.lock = threading.Lock()
        threading.Thread(target=self._read, daemon=True).start()
        self.tell(spec=spec, sender=index)

    def _read(self) -> None:
        for ln in self.proc.stdout:
            if not ln.startswith("{"):
                continue
            msg = json.loads(ln)
            if "interval" in msg:
                box = self.replies.get(msg["interval"])
                if box is not None:     # else: given up on, already failed
                    box.put(msg)
            else:
                self.said.put(msg)
        self.said.put({"eof": True})

    def tell(self, **msg) -> None:
        with self.lock:
            self.proc.stdin.write(json.dumps(msg) + "\n")
            self.proc.stdin.flush()

    def expect(self, key: str, timeout: float) -> dict:
        try:
            msg = self.said.get(timeout=timeout)
        except queue.Empty:
            msg = {}
        if key not in msg:
            raise RuntimeError(f"sender {self.index}: wanted {key!r}, "
                               f"got {msg!r}")
        return msg


def _locals_of(sender: int, p: dict) -> list:
    return [loc for loc in range(p["locals"])
            if loc % p["sender_processes"] == sender]


def prepare(spec: dict) -> dict:
    p = spec["traffic"]
    senders = [_Sender(i, spec) for i in range(p["sender_processes"])]
    state = {"senders": senders, "traffic": p}
    try:
        built = [s.expect("built", BUILD_TIMEOUT_S) for s in senders]
    except BaseException:
        close(state)
        raise
    state["ready"] = {
        "digests": ledger(p)["digests"],
        "sender_processes": len(senders),
        "messages_per_stream": p["keys_per_local"],
        "message_bytes_mean": round(
            sum(b["bytes"] for b in built)
            / max(sum(b["built"] for b in built), 1), 1),
        "sender_build_s_max": max(b["build_s"] for b in built)}
    return state


def connect(state: dict, targets: dict) -> None:
    addr = "%s:%d" % tuple(targets["grpc"])
    for s in state["senders"]:
        s.tell(target=addr)
    for s in state["senders"]:
        s.expect("connected", 60.0)


def send_interval(state: dict, spec: dict, interval: int, due: float) -> dict:
    """Every local opens its stream for `interval` now; what each
    reports, in local order.  A sender that does not answer within the
    RPC's deadline (and a margin) counts as failed."""
    p = spec["traffic"]
    n = p["locals"]
    sent: list = [None] * n
    acks: list = [None] * n
    errors: list = []
    for s in state["senders"]:
        s.replies[interval] = queue.Queue()
    for s in state["senders"]:
        s.tell(interval=interval, due=due)
    give_up = time.time() + p["rpc_timeout_s"] + 15.0
    for s in state["senders"]:
        for _ in _locals_of(s.index, p):
            try:
                rep = s.replies[interval].get(
                    timeout=max(give_up - time.time(), 0.1))
            except queue.Empty:
                errors.append(f"sender {s.index}: no report")
                break
            sent[rep["local"]] = rep["sent_s"]
            acks[rep["local"]] = rep["ack_s"]
            if rep.get("error"):
                errors.append(rep["error"])
        s.replies.pop(interval, None)
    return {"sent_s": sent, "ack_s": acks, "errors": errors,
            "digests": ledger(p)["digests"]}


def close(state: dict) -> None:
    for s in state["senders"]:
        try:
            s.proc.stdin.close()        # the sender ends by itself
        except OSError:
            pass
    for s in state["senders"]:
        try:
            s.proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            s.proc.kill()
            s.proc.wait(timeout=10)


# -- a sender process: `forward_stream.py --sender` -------------------------

def _say(**kw) -> None:
    sys.stdout.write(json.dumps(kw) + "\n")
    sys.stdout.flush()


def sender_main() -> int:
    import grpc

    first = json.loads(sys.stdin.readline())
    spec, me = first["spec"], first["sender"]
    p = spec["traffic"]
    mine = _locals_of(me, p)
    t0 = time.time()
    # messages[variant][local]: serialised once, sent as they are
    messages = []
    for v in range(p["variants"]):
        m = model(spec["seed"], p, v)
        messages.append({loc: [pb.SerializeToString()
                               for pb in _fwd._build_pbs(m, loc, p)]
                         for loc in mine})
        del m
    _say(built=sum(len(ms) for by in messages for ms in by.values()),
         bytes=sum(len(b) for by in messages for ms in by.values()
                   for b in ms),
         build_s=round(time.time() - t0, 3))
    line = sys.stdin.readline()
    if not line:                    # closed before it connected
        return 0
    target = json.loads(line)["target"]
    channels = {loc: grpc.insecure_channel(target) for loc in mine}
    streams = {loc: ch.stream_unary(SEND_METRICS_V2)
               for loc, ch in channels.items()}
    for ch in channels.values():
        grpc.channel_ready_future(ch).result(timeout=30)
    _say(connected=len(channels))
    out_lock = threading.Lock()
    threads = []

    def one(loc: int, interval: int, due: float) -> None:
        rep = {"interval": interval, "local": loc,
               "sent_s": time.time() - due, "ack_s": None}
        try:
            streams[loc](iter(messages[interval % p["variants"]][loc]),
                         timeout=p["rpc_timeout_s"])
            rep["ack_s"] = time.time() - due
        except Exception as e:      # noqa: BLE001 - reported to the parent
            rep["error"] = f"local{loc}: {type(e).__name__}: {e}"[:200]
        with out_lock:
            _say(**rep)

    for ln in sys.stdin:            # ends when the parent closes it
        cmd = json.loads(ln)
        for loc in mine:
            t = threading.Thread(target=one,
                                 args=(loc, cmd["interval"], cmd["due"]))
            t.start()
            threads.append(t)
    for t in threads:
        t.join(timeout=p["rpc_timeout_s"] + 5.0)
    for ch in channels.values():
        ch.close()
    return 0


if __name__ == "__main__":
    if sys.argv[1:] != ["--sender"]:
        sys.exit("forward_stream.py is a generator kind of benchmark/run.py;"
                 " run alone it is a sender process (--sender)")
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    rc = sender_main()
    sys.stdout.flush()
    os._exit(rc)
