#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served global flush still
runs on the chip.

    python chip_smoke.py              one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    the meshed global on four chips,
                                      and nothing else (the builder runs it)
    python chip_smoke.py --rehearse   CPU rehearsal of the control flow at a
                                      tiny size; can never pass as a chip run

One process holds the chip: this one.  It IS the global veneur.  Default
run, through the entry points a user would call:

  * the global `Server` boots from a YAML read by `read_config` (gRPC
    import, one UDP statsd listener, native ingest, percentiles
    0.5/0.9/0.99, interval 20s) with a `ChannelMetricSink`, is started with
    `.start()` and flushed by its own interval ticker (`.serve()`);
  * 2 local veneurs start as children through the CLI
    (`python -m veneur_tpu.cli.veneur -f <yaml>`), forwarding to the
    global's bound gRPC port; every port is 0 and read back from the
    `port_file` each child writes;
  * a sender child (numpy + sockets, no JAX) drives 3 intervals from
    `--seed`: to each local over UDP 65,536 histogram keys x 16 gamma
    samples (4 tags per key), to the global's own UDP listener 200,000
    lines over 10,000 counter, 1,000 gauge and 1,000 set keys (sets draw
    from 100,000 members), all in multi-line datagrams.  The global so
    imports 131,072 forwarded digests per interval over real forwardrpc
    gRPC and evaluates a dense flush of 65,536 keys at depth 32
    (2 digests x 16 singleton centroids per key) as `[65536, 32]`
    programs — see "the shape that runs" below;
  * every check below is fatal and printed as its own JSON line; the last
    line is `{"ok": true, "device": {...}}` with the device as JAX
    reports it, and nothing else.

The plain reference for percentiles is `numpy.percentile(...,
method="hazen")` over the samples SENT (not `tdigest_cpu`, which shares
code with the system), held to the repo's committed t-digest envelope
(`analysis/tdigest_accuracy.csv` via `testbed.verify`).

Cuts from a real deployment: 2 locals instead of a fleet, 3 intervals,
loopback sockets.  Every server runs its default configuration but for:

  * `synchronize_with_interval: true`: the sender starts each interval
    one second after the shared wall-clock tick, so every global interval
    holds exactly one forward from each local (two from one local would
    stage depth 64 and compile a second program);
  * `interval: 20s`, not the reference's 10s default.  One local's flush
    and forward of 65,537 digests is host Python (digest export, protobuf,
    and the global's per-digest import under its aggregator lock) and took
    8-11 s at this size on an 8-core CPU host, which leaves no margin
    inside 10 s on a machine whose cores are shared; a forward that
    straddles the global's tick splits an interval in two.  What the run
    itself took is printed as `local_forward_single_sample`.

The shape that runs, asserted from the aggregator's `_compiled_shapes`:
the global's own `flush.total_duration_ns` timer (span-extracted
self-telemetry, as in the reference) is the 65,537th digest row, the
dense builder pads rows to a power of two (131,072), and the global
tier's default `flush_upload_chunks: 2` evaluates that as two
`[65536, 32]` programs whose uploads and readbacks overlap.  16 samples
per key per local stage depth 32 with no adjustment.

The forward client speaks batched `SendMetrics` (V1) to this
framework's globals by itself; `SendMetricsV2` is its fallback for
reference globals.  Nothing here forces either.

`--chips 4` runs only the meshed path and what it is compared with: the
same seeded 131,072 digests imported through the forward client into a
global with `mesh_devices: 4` (shard 2 x replica 2) and into an unmeshed
global on device 0 (`flush_upload_chunks: 1`, so both evaluate one
`[65536, 32]` dense matrix: a first flush has no self-telemetry row
yet), one interval each, in this one process — sink outputs equal metric for metric (the tolerance `tests/test_parallel.py`
demands of meshed vs plain), all four devices hold a shard of the dense
input, the compiled program has the kernel and an all-to-all over
replica pairs.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

PERCENTILES = [0.5, 0.9, 0.99]
DEPTH = 32                    # 2 locals x 16 singleton centroids
SAMPLES_PER_KEY = 16
N_LOCALS = 2
MAX_DATAGRAM = 1400


class Sizes:
    """Traffic sizes of one run (full, or the tiny rehearsal)."""

    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.histo_keys = 256 if rehearse else 65536
        self.intervals = 1 if rehearse else 3
        self.interval_s = 5.0 if rehearse else 20.0
        self.counter_keys = 100 if rehearse else 10000
        self.gauge_keys = 10 if rehearse else 1000
        self.set_keys = 10 if rehearse else 1000
        self.set_members = 1000 if rehearse else 100000
        self.global_lines = 2000 if rehearse else 200000
        self.sampled_keys = min(1024, self.histo_keys)
        # rows of the digest program that runs (module docstring)
        self.flush_rows = 512 if rehearse else 65536
        # datagrams per second and per burst, per target: a third of the
        # interval to send, and bursts a small socket buffer can hold
        # while a reader thread waits for its turn on a shared core
        self.rate = 6000.0
        self.burst = 16

    @property
    def counter_lines(self):
        return self.global_lines * 3 // 4

    @property
    def gauge_lines(self):
        return self.global_lines // 20

    @property
    def set_lines(self):
        return self.global_lines - self.counter_lines - self.gauge_lines


# ---------------------------------------------------------------------------
# The traffic model: pure functions of (seed, interval), shared by the
# sender child (which formats and sends) and the parent (its ledger)
# ---------------------------------------------------------------------------

def histo_values(seed: int, interval: int, local: int,
                 sz: Sizes) -> np.ndarray:
    """[keys, samples] gamma values, rounded to the 3 decimals that cross
    the wire, so the ledger holds exactly what the text says."""
    rng = np.random.default_rng([seed, 1, interval, local])
    return np.round(rng.gamma(2.0, 10.0,
                              (sz.histo_keys, SAMPLES_PER_KEY)), 3)


def global_traffic(seed: int, interval: int, sz: Sizes) -> dict:
    """The global's own UDP lines for one interval, in send order."""
    rng = np.random.default_rng([seed, 2, interval])
    return {
        "c_key": rng.integers(0, sz.counter_keys, sz.counter_lines),
        "c_val": rng.integers(1, 10, sz.counter_lines),
        "g_key": rng.integers(0, sz.gauge_keys, sz.gauge_lines),
        "g_val": np.round(rng.uniform(0.0, 1000.0, sz.gauge_lines), 3),
        # skewed: cardinalities from a handful to ~3% of the lines
        "s_key": (sz.set_keys * rng.random(sz.set_lines) ** 2).astype(
            np.int64),
        "s_mem": rng.integers(0, sz.set_members, sz.set_lines),
    }


def histo_tags(k: int) -> str:
    return f"svc:s{k % 61},region:r{k % 7},az:z{k % 3},shard:{k % 1021}"


def pack(lines: list[bytes]) -> list[bytes]:
    """Newline-joined datagrams of at most MAX_DATAGRAM bytes."""
    out, cur, size = [], [], 0
    for ln in lines:
        if cur and size + 1 + len(ln) > MAX_DATAGRAM:
            out.append(b"\n".join(cur))
            cur, size = [], 0
        cur.append(ln)
        size += len(ln) + (1 if size else 0)
    if cur:
        out.append(b"\n".join(cur))
    return out


# ---------------------------------------------------------------------------
# Sender child: numpy + sockets only; never imports JAX
# ---------------------------------------------------------------------------

def _send_paced(addr, datagrams: list[bytes], rate: float,
                burst: int) -> None:
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        t0 = time.perf_counter()
        for i in range(0, len(datagrams), burst):
            for d in datagrams[i:i + burst]:
                sock.sendto(d, addr)
            ahead = (i + burst) / rate - (time.perf_counter() - t0)
            if ahead > 0:
                time.sleep(ahead)
    finally:
        sock.close()


def sender_main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    sz = Sizes(spec["rehearse"])
    seed = spec["seed"]
    tags = [histo_tags(k) for k in range(sz.histo_keys)]
    for iv in range(sz.intervals):
        per_target = []
        for local, addr in enumerate(spec["locals"]):
            vals = histo_values(seed, iv, local, sz)
            # sample-major: consecutive lines are different keys
            lines = [b"smoke.h.%d:%.3f|h|#%s" % (k, vals[k, s],
                                                   tags[k].encode())
                     for s in range(SAMPLES_PER_KEY)
                     for k in range(sz.histo_keys)]
            per_target.append((tuple(addr), pack(lines)))
        g = global_traffic(seed, iv, sz)
        lines = [b"smoke.c.%d:%d|c" % (k, v)
                 for k, v in zip(g["c_key"].tolist(), g["c_val"].tolist())]
        lines += [b"smoke.g.%d:%.3f|g" % (k, v)
                  for k, v in zip(g["g_key"].tolist(), g["g_val"].tolist())]
        lines += [b"smoke.s.%d:m%d|s" % (k, m)
                  for k, m in zip(g["s_key"].tolist(), g["s_mem"].tolist())]
        per_target.append((tuple(spec["global"]), pack(lines)))
        # one second after the shared tick: the tick's flush has taken
        # its snapshot, and the whole interval is still ahead
        start = spec["first_tick"] + iv * sz.interval_s + 1.0
        late = time.time() - start
        if late < 0:
            time.sleep(-late)
        t0 = time.time()
        threads = [threading.Thread(target=_send_paced,
                                    args=(addr, dgs, sz.rate, sz.burst))
                   for addr, dgs in per_target]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        print(json.dumps({
            "sender_interval": iv, "start_late_s": round(max(late, 0.0), 3),
            "send_s": round(time.time() - t0, 3),
            "datagrams": [len(d) for _a, d in per_target]}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Parent helpers
# ---------------------------------------------------------------------------

def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


class SmokeFailure(Exception):
    pass


def check(name: str, ok: bool, **detail) -> None:
    say(check=name, ok=bool(ok), **detail)
    if not ok:
        raise SmokeFailure(name)


def http_json(addr, path: str) -> dict:
    with urllib.request.urlopen(
            f"http://{addr[0]}:{addr[1]}{path}", timeout=10) as r:
        return json.loads(r.read())


def write_yaml(path: str, cfg: dict) -> str:
    import yaml

    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


def terminate(procs: list) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=10)


def device_report(rehearse: bool) -> dict:
    """Assert the accelerator (no CPU continuation) and say what runs."""
    import jax
    import jaxlib

    from veneur_tpu.util import compile_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = None
    cache_dir = compile_cache.enable(min_compile_secs=0.0)
    say(device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        compile_cache_from_env=bool(
            os.environ.get(compile_cache.ENV_VAR)))
    if rehearse:
        say(rehearsal=True)
    elif dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0] is {dev.platform}")
    for var in ("VENEUR_TPU_DISABLE_PALLAS_EVAL",
                "VENEUR_TPU_DISABLE_SEGMENTED_REDUCE"):
        check("env_unset", not os.environ.get(var), var=var)
    return device


class CacheCounter:
    """Persistent-compile-cache hits and misses, from JAX's own events."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def flush_program_text(agg, shape_key) -> str:
    """Lower + compile the aggregator's flush program for a recorded
    `_compiled_shapes` key and return the optimized HLO."""
    import jax

    (u, d), uniform, donate = shape_key
    dt = agg.digests.eval_dtype
    if uniform:
        fn = (agg.flush_fn.depth_variant_donated if donate
              else agg.flush_fn.depth_variant)
        lowered = fn.lower(
            jax.ShapeDtypeStruct((u, d), agg.digests.stage_dtype),
            jax.ShapeDtypeStruct((u,), np.int16), agg._pct_arr)
    else:
        lower = (agg.flush_fn.lower_donated if donate
                 else agg.flush_fn.lower)
        lowered = lower(jax.ShapeDtypeStruct((u, d), dt),
                        jax.ShapeDtypeStruct((u, d), dt),
                        jax.ShapeDtypeStruct((2, u), dt), agg._pct_arr,
                        uniform=False)
    return lowered.compile().as_text()


def digest_shape_keys(agg) -> list:
    return sorted(k for k in agg._compiled_shapes
                  if isinstance(k[0], tuple))


def percentile_check(by_name: dict, samples: np.ndarray, keys,
                     label: str) -> None:
    """`.50/.90/.99percentile` of the sampled keys against numpy's hazen
    percentile of the samples sent, span-normalised and held to the
    committed t-digest envelope, as testbed.verify does."""
    from veneur_tpu.testbed import verify

    env = verify.load_envelope()
    worst = {}
    for q in PERCENTILES:
        want = np.percentile(samples[keys], q * 100.0, axis=1,
                             method="hazen")
        got = np.array([by_name.get(
            f"smoke.h.{k}.{int(q * 100)}percentile", np.nan) for k in keys])
        span = samples[keys].max(axis=1) - samples[keys].min(axis=1)
        err = float(np.max(np.abs(got - want) / np.where(span > 0, span,
                                                         1.0)))
        bound = verify.envelope_for(q, env)
        worst[str(q)] = {"max_span_err": err, "bound": bound}
        if not (np.all(np.isfinite(got)) and err <= bound):
            check(label, False, worst=worst)
    check(label, True, keys=len(keys), worst=worst)


# ---------------------------------------------------------------------------
# One chip: global in this process, 2 CLI locals, sender child
# ---------------------------------------------------------------------------

def run_one_chip(args, sz: Sizes) -> dict:
    device = device_report(sz.rehearse)
    cache = CacheCounter()

    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import ChannelMetricSink
    from veneur_tpu.testbed.proccluster import child_env

    run_dir = tempfile.mkdtemp(prefix="chip-smoke-")
    procs: list = []
    srv = None
    try:
        interval = f"{sz.interval_s:g}s"
        cfg = config_mod.read_config(write_yaml(
            os.path.join(run_dir, "global.yaml"), {
                "hostname": "smoke-global",
                "interval": interval,
                "synchronize_with_interval": True,
                "percentiles": PERCENTILES,
                "statsd_listen_addresses": ["udp://127.0.0.1:0"],
                "grpc_address": "127.0.0.1:0",
                "native_ingest": True,
            }))
        sink = ChannelMetricSink()
        srv = Server(cfg, extra_metric_sinks=[sink])
        srv.start()
        threading.Thread(target=srv.serve, daemon=True,
                         name="global-ticker").start()
        agg = srv.aggregator
        check("global_native_engine", srv.native is not None,
              reader_backends=[srv.native.engine.reader_backend(t)
                               for t in srv.native.engine._reader_tids],
              simd=srv.native.engine.simd_mode())
        g_udp = srv.statsd_addrs[0][1]
        g_grpc = srv.grpc_import.port

        # -- the locals, through the normal CLI.  Locals are per-host
        # agents and run on CPU hosts by design, and the chip belongs to
        # this process: no child may initialise a TPU backend.  They are
        # pinned to the CPU backend by their environment
        # (proccluster.child_env: JAX_PLATFORMS=cpu, XLA_FLAGS dropped) —
        # a pin, not a fallback — and must say so in their log.
        env = child_env()
        locals_ = []
        for i in range(N_LOCALS):
            d = os.path.join(run_dir, f"local{i}")
            os.makedirs(d)
            path = write_yaml(os.path.join(d, "config.yaml"), {
                "hostname": f"smoke-local{i}",
                "interval": interval,
                "synchronize_with_interval": True,
                "percentiles": PERCENTILES,
                "statsd_listen_addresses": ["udp://127.0.0.1:0"],
                "http_address": "127.0.0.1:0",
                "forward_address": f"127.0.0.1:{g_grpc}",
                "native_ingest": True,
                "port_file": os.path.join(d, "ports.json"),
                "metric_sinks": [{"kind": "jsonl", "name": "emit",
                                  "config": {"path": os.path.join(
                                      d, "emit.jsonl")}}],
            })
            log = open(os.path.join(d, "log.txt"), "ab")
            try:
                p = subprocess.Popen(
                    [sys.executable, "-m", "veneur_tpu.cli.veneur",
                     "-f", path], stdout=log, stderr=subprocess.STDOUT,
                    cwd=REPO, env=env)
            finally:
                log.close()
            procs.append(p)
            locals_.append({"dir": d, "proc": p})
        deadline = time.time() + 240
        for loc in locals_:
            pf = os.path.join(loc["dir"], "ports.json")
            while not os.path.exists(pf):
                if loc["proc"].poll() is not None or time.time() > deadline:
                    with open(os.path.join(loc["dir"], "log.txt")) as f:
                        sys.stderr.write(f.read()[-4000:])
                    raise SmokeFailure("local did not boot")
                time.sleep(0.2)
            with open(pf) as f:
                ports = json.load(f)
            loc["udp"] = tuple(ports["statsd"][0][1])
            loc["http"] = tuple(ports["http"])
            dv = http_json(loc["http"], "/debug/vars")
            with open(os.path.join(loc["dir"], "log.txt")) as f:
                backend_lines = [ln for ln in f if "jax backend" in ln]
            backend = [ln.split("jax backend ")[-1].strip()
                       for ln in backend_lines]
            check("local_boot", "native_ingest" in dv
                  and "ingest_stages" in dv and len(backend) == 1
                  and backend[0].startswith("cpu"),
                  host=ports["hostname"],
                  native_engine="native_ingest" in dv, jax_backend=backend)

        # -- traffic: the sender child, aligned to the shared tick
        first_tick = (int(time.time() / sz.interval_s) + 1) * sz.interval_s
        if first_tick - time.time() < (1.0 if sz.rehearse else 7.0):
            first_tick += sz.interval_s     # time to build interval 0
        spec = os.path.join(run_dir, "sender.json")
        with open(spec, "w") as f:
            json.dump({"seed": args.seed, "rehearse": sz.rehearse,
                       "first_tick": first_tick,
                       "locals": [list(loc["udp"]) for loc in locals_],
                       "global": list(g_udp)}, f)
        sender = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--sender", spec],
            stdout=subprocess.PIPE, text=True, env=env)
        procs.append(sender)

        # -- collect the sink until every interval arrived (or time out).
        # The loop stays light (this process is the global, and its
        # import handlers need the interpreter): batches are only
        # classified here and read after the run.
        histo_batches, direct_batches, flush_spans = [], [], {}
        events_after_first = None
        give_up = first_tick + (sz.intervals + 4) * sz.interval_s + 120
        while (len(histo_batches) < sz.intervals
               or len(direct_batches) < sz.intervals):
            if time.time() > give_up:
                raise SmokeFailure(
                    f"sink saw {len(histo_batches)} histogram and "
                    f"{len(direct_batches)} direct intervals of "
                    f"{sz.intervals}")
            try:
                batch = sink.queue.get(timeout=1.0)
            except queue.Empty:
                continue
            kinds = {m.name[:8] for m in batch}
            if "smoke.h." in kinds:
                histo_batches.append(batch)
                if events_after_first is None:
                    events_after_first = agg.compile_events
            if "smoke.c." in kinds:
                direct_batches.append(batch)
            for rec in srv.flight_recorder.snapshot():
                if rec["name"].startswith("flush"):
                    flush_spans.setdefault(rec["trace_id"], {})[
                        rec["name"]] = rec["duration_ms"]
        histo_batches = [{m.name: m.value for m in b
                          if m.name.startswith("smoke.h.")}
                         for b in histo_batches]
        direct_batches = [{m.name: m.value for m in b
                           if m.name.startswith("smoke.")
                           and not m.name.startswith("smoke.h.")}
                          for b in direct_batches]
        sender_out, _ = sender.communicate(timeout=60)
        for ln in sender_out.splitlines():
            say(info="sender", **json.loads(ln))
        check("sender_exit", sender.returncode == 0, rc=sender.returncode)
        check("intervals_at_sink", True, histogram=len(histo_batches),
              direct=len(direct_batches), want=sz.intervals)

        # -- stop the locals (final state of their ledgers), then check
        local_vars = [http_json(loc["http"], "/debug/vars")
                      for loc in locals_]
        for loc in locals_:
            spans = http_json(loc["http"], "/debug/trace")["spans"]
            say(info="local_forward_single_sample",
                host=os.path.basename(loc["dir"]),
                flush_ms=[s["duration_ms"] for s in spans
                          if s["name"] == "flush"],
                forward_ms=[s["duration_ms"] for s in spans
                            if s["name"] == "flush.forward"])
        terminate([loc["proc"] for loc in locals_])
        timeline = srv.flush_timeline.snapshot()
        data_flushes = [r for r in timeline if r.get("imported", 0)]

        # imports: 2 x keys digests per interval (plus each local's own
        # forwarded self-telemetry timer), nothing failed
        want_import = N_LOCALS * sz.histo_keys
        extra = [r["imported"] - want_import for r in data_flushes]
        check("imported_digests_per_interval",
              len(extra) == sz.intervals
              and all(0 <= e <= 4 * N_LOCALS for e in extra)
              and srv.grpc_import.import_errors == 0,
              per_interval=[r["imported"] for r in data_flushes],
              smoke_digests=want_import, self_telemetry_digests=extra,
              import_errors=srv.grpc_import.import_errors)
        # the dense shape that ran, from the aggregator's own record
        keys_ran = digest_shape_keys(agg)
        data_keys = [k for k in keys_ran if k[0][1] == DEPTH]
        check("dense_flush_shape",
              [k[0] for k in data_keys] == [(sz.flush_rows, DEPTH)]
              and len(agg.digests.kdict) > sz.histo_keys,
              compiled_shapes=[[list(k[0]), k[1], k[2]] for k in keys_ran],
              digest_rows_live=len(agg.digests.kdict),
              chunks_per_flush=[r.get("device_chunks", 1)
                                for r in data_flushes],
              want=[sz.flush_rows, DEPTH])
        hlo = flush_program_text(agg, data_keys[0])
        has_kernel = "tpu_custom_call" in hlo
        check("flush_program_has_kernel", has_kernel or sz.rehearse,
              shape=list(data_keys[0][0]), uniform=data_keys[0][1],
              donated=data_keys[0][2], tpu_custom_call=has_kernel)
        check("no_compile_after_first_interval",
              agg.compile_events == events_after_first,
              compile_events=agg.compile_events,
              after_first_interval=events_after_first)

        # counters exact, gauges last-write, sets within the HLL bound
        # the HLL bound: 3 standard errors at the configured precision,
        # no tighter than the 3% tests/test_hll.py holds the estimator to,
        # with an absolute floor where cardinalities are a few dozen
        hll_rel = max(3.0 * 1.04 / np.sqrt(2.0 ** cfg.set_precision), 0.03)
        for iv, by_name in enumerate(direct_batches):
            g = global_traffic(args.seed, iv, sz)
            c_want = np.bincount(g["c_key"], weights=g["c_val"],
                                 minlength=sz.counter_keys)
            c_bad = [k for k in np.nonzero(c_want)[0].tolist()
                     if by_name.get(f"smoke.c.{k}") != c_want[k]]
            g_last = dict(zip(g["g_key"].tolist(), g["g_val"].tolist()))
            g_bad = [k for k, v in g_last.items()
                     if not abs(by_name.get(f"smoke.g.{k}", np.nan) - v)
                     <= 1e-3]
            s_bad, s_worst = [], 0.0
            for k in np.unique(g["s_key"]).tolist():
                true = len(np.unique(g["s_mem"][g["s_key"] == k]))
                got = by_name.get(f"smoke.s.{k}", np.nan)
                bound = max(5.0, hll_rel * true)
                s_worst = max(s_worst, abs(got - true) / bound)
                if not abs(got - true) <= bound:
                    s_bad.append(k)
            check("direct_interval", not (c_bad or g_bad or s_bad),
                  interval=iv, counters_exact=not c_bad,
                  counter_keys=int(np.count_nonzero(c_want)),
                  counter_sum=float(c_want.sum()),
                  gauges_last_write=not g_bad, gauge_keys=len(g_last),
                  sets_within_hll_bound=not s_bad,
                  set_precision=cfg.set_precision,
                  worst_set_err_over_bound=round(s_worst, 4),
                  bad=(c_bad + g_bad + s_bad)[:8])

        # percentiles of a seeded sample of keys vs numpy hazen
        keys = np.sort(np.random.default_rng([args.seed, 3]).choice(
            sz.histo_keys, sz.sampled_keys, replace=False))
        for iv, by_name in enumerate(histo_batches):
            samples = np.concatenate(
                [histo_values(args.seed, iv, loc, sz)
                 for loc in range(N_LOCALS)], axis=1)
            check("percentile_metrics_emitted",
                  sum(n.startswith("smoke.h.") for n in by_name)
                  == sz.histo_keys * len(PERCENTILES), interval=iv,
                  emitted=sum(n.startswith("smoke.h.") for n in by_name))
            percentile_check(by_name, samples, keys,
                             f"percentiles_interval_{iv}")

        # no sample lost: what the locals counted is what was sent
        sent_h = sz.histo_keys * SAMPLES_PER_KEY * sz.intervals
        for loc, dv in zip(locals_, local_vars):
            count_sum = flushes = 0
            with open(os.path.join(loc["dir"], "emit.jsonl")) as f:
                for ln in f:
                    if '.count"' in ln and '"smoke.h.' in ln:
                        count_sum += json.loads(ln)["value"]
                    elif ln.startswith('{"flush"'):
                        flushes += 1
            ni, fw = dv["native_ingest"], dv.get("forward", {})
            check("local_no_loss", count_sum == sent_h
                  and ni["lines"] == sent_h and ni["malformed"] == 0
                  and ni["too_long"] == 0 and fw.get("dropped") == 0
                  and fw.get("spilled") == 0 and "spool" not in dv
                  and dv["forward_slots_dropped"] == 0,
                  host=os.path.basename(loc["dir"]),
                  histogram_count_sum=count_sum, sent=sent_h,
                  native=ni, forward=fw,
                  forward_slots_dropped=dv["forward_slots_dropped"])
        srv._drain_native()
        lines, malformed, _pk, too_long = srv.native.engine.totals()
        check("global_no_loss", lines == sz.global_lines * sz.intervals
              and malformed == 0 and too_long == 0, lines=lines,
              sent=sz.global_lines * sz.intervals, malformed=malformed,
              too_long=too_long)

        # information only: SINGLE SAMPLES, not metrics
        for r in data_flushes:
            spans = flush_spans.get(int(r["trace_id"], 16), {})
            say(info="flush_single_sample", interval=r["interval"],
                flush_wall_ms=r["total_ms"], imported=r["imported"],
                metrics_emitted=r.get("metrics_emitted"),
                segments_ms={n: v for n, v in sorted(spans.items())
                             if n.startswith("flush.seg.")})
        say(info="compile_single_sample",
            compile_events=agg.compile_events,
            compile_seconds=round(agg.compile_seconds_total, 3),
            cache_hits=cache.hits, cache_misses=cache.misses)
        return device
    finally:
        terminate(procs)
        if srv is not None:
            srv.shutdown()
        out = os.path.join(REPO, "chiprun_out", "chip_smoke")
        os.makedirs(out, exist_ok=True)
        for i in range(N_LOCALS):
            src = os.path.join(run_dir, f"local{i}", "log.txt")
            if os.path.exists(src):
                shutil.copy(src, os.path.join(out, f"local{i}.log"))
        shutil.rmtree(run_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Four chips: meshed global vs unmeshed global, one process, no locals
# ---------------------------------------------------------------------------

def forward_metrics(seed: int, sz: Sizes) -> list:
    """What the two locals would forward for interval 0: one digest of 16
    singleton centroids per key per local."""
    from veneur_tpu.samplers import samplers as sm
    from veneur_tpu.samplers.metric_key import MetricScope

    out = []
    for local in range(N_LOCALS):
        vals = np.sort(histo_values(seed, 0, local, sz), axis=1)
        for k in range(sz.histo_keys):
            v = vals[k]
            out.append(sm.ForwardMetric(
                name=f"smoke.h.{k}", tags=histo_tags(k).split(","),
                kind=sm.TYPE_HISTOGRAM, scope=MetricScope.MIXED,
                digest_means=v.tolist(),
                digest_weights=[1.0] * SAMPLES_PER_KEY,
                digest_min=float(v[0]), digest_max=float(v[-1]),
                digest_sum=float(v.sum()),
                digest_rsum=float((1.0 / np.maximum(v, 1e-9)).sum()),
                digest_compression=100.0))
    return out


def run_four_chips(args, sz: Sizes) -> dict:
    import jax

    device = device_report(sz.rehearse)
    check("four_devices", device["count"] == 4, count=device["count"])

    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server
    from veneur_tpu.forward.client import ForwardClient
    from veneur_tpu.parallel import serving
    from veneur_tpu.sinks.simple import ChannelMetricSink

    run_dir = tempfile.mkdtemp(prefix="chip-smoke-")
    servers = []
    try:
        metrics = forward_metrics(args.seed, sz)
        results = {}
        for label, extra in (("meshed", {"mesh_devices": 4,
                                         "mesh_replicas": 2}),
                             ("unmeshed", {"flush_upload_chunks": 1})):
            cfg = config_mod.read_config(write_yaml(
                os.path.join(run_dir, f"{label}.yaml"), {
                    "hostname": f"smoke-{label}", "interval": "10s",
                    "percentiles": PERCENTILES,
                    "grpc_address": "127.0.0.1:0", **extra}))
            sink = ChannelMetricSink()
            srv = Server(cfg, extra_metric_sinks=[sink])
            servers.append(srv)
            srv.start()
            agg = srv.aggregator
            seen = {}
            if srv.mesh is not None:
                check("mesh_shape", dict(srv.mesh.shape)
                      == {"shard": 2, "replica": 2},
                      mesh=dict(srv.mesh.shape))
                inner = agg.flush_fn

                def spy(inputs, pct, _inner=inner, _seen=seen, **kw):
                    # observe where the dense input lives, then run
                    _seen["inputs"] = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=x.sharding), inputs)
                    _seen["uniform"] = kw.get("uniform", False)
                    _seen["shards"] = sorted(
                        (s.device.id, tuple(s.data.shape))
                        for s in inputs.dense_v.addressable_shards)
                    return _inner(inputs, pct, **kw)

                spy.lower = inner.lower
                agg.flush_fn = spy
            client = ForwardClient(f"127.0.0.1:{srv.grpc_import.port}",
                                   timeout_s=120.0)
            try:
                half = len(metrics) // N_LOCALS
                for i in range(N_LOCALS):      # one forward per local
                    client.send(metrics[i * half:(i + 1) * half])
            finally:
                client.close()
            deadline = time.time() + 120
            while srv.grpc_import.imported_count < len(metrics):
                if time.time() > deadline:
                    raise SmokeFailure(f"{label}: import incomplete")
                time.sleep(0.05)
            check("imported", srv.grpc_import.import_errors == 0,
                  label=label, imported=srv.grpc_import.imported_count)
            t0 = time.perf_counter()
            srv.flush()
            srv.egress.settle(timeout_s=300)
            batch = sink.queue.get(timeout=300)
            say(info="flush_single_sample", label=label,
                flush_wall_ms=round((time.perf_counter() - t0) * 1e3, 1),
                note="first flush of its shape: includes the compile")
            results[label] = {m.name: m.value for m in batch
                              if m.name.startswith("smoke.h.")}
            if srv.mesh is not None:
                devs = sorted({d for d, _s in seen["shards"]})
                check("dense_input_on_every_device",
                      devs == sorted(d.id for d in jax.devices())
                      and seen["inputs"].dense_v.shape
                      == (sz.histo_keys, DEPTH),
                      dense_shape=list(seen["inputs"].dense_v.shape),
                      shards=[[d, list(s)] for d, s in seen["shards"]])
                hlo = agg.flush_fn.lower(
                    seen["inputs"], agg._pct_arr,
                    uniform=seen["uniform"]).compile().as_text()
                sizes = serving.collective_group_sizes(hlo, "all-to-all")
                has_kernel = "tpu_custom_call" in hlo
                check("meshed_program", bool(sizes) and set(sizes) == {2}
                      and (has_kernel or sz.rehearse),
                      all_to_all_group_sizes=sorted(set(sizes)),
                      tpu_custom_call=has_kernel)
            else:
                keys_ran = digest_shape_keys(agg)
                check("unmeshed_dense_flush_shape",
                      [k[0] for k in keys_ran] == [(sz.histo_keys, DEPTH)],
                      compiled_shapes=[list(k[0]) for k in keys_ran])

        a, b = results["meshed"], results["unmeshed"]
        names = sorted(a)
        va = np.array([a[n] for n in names])
        vb = np.array([b.get(n, np.nan) for n in names])
        same = (set(a) == set(b)
                and len(a) == sz.histo_keys * len(PERCENTILES)
                and bool(np.allclose(va, vb, rtol=1e-4, atol=1e-4)))
        check("meshed_equals_unmeshed", same, metrics=len(a),
              bit_equal=bool(np.array_equal(va, vb)),
              max_abs_diff=float(np.nanmax(np.abs(va - vb))))
        samples = np.concatenate(
            [histo_values(args.seed, 0, loc, sz)
             for loc in range(N_LOCALS)], axis=1)
        keys = np.sort(np.random.default_rng([args.seed, 3]).choice(
            sz.histo_keys, sz.sampled_keys, replace=False))
        percentile_check(a, samples, keys, "meshed_percentiles")
        return device
    finally:
        for srv in servers:
            srv.shutdown()
        shutil.rmtree(run_dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal of the control flow at a tiny "
                         "size; the last line says ok: false")
    ap.add_argument("--sender", metavar="SPEC", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.sender:
        return sender_main(args.sender)
    sz = Sizes(args.rehearse)
    try:
        device = (run_four_chips if args.chips == 4
                  else run_one_chip)(args, sz)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    # a rehearsal can never be mistaken for a chip run
    print(json.dumps({"ok": not sz.rehearse, "device": device}),
          flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception:
        import traceback
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # server threads of a failed phase must not hold the exit
    os._exit(rc)
