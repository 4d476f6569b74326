#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip and IS the server under test: it reads the
cell's deployment file (`configs/<config>.json`) and traffic mix
(`traffic/<mix>.json`), writes a YAML, boots `Server` through
`read_config` with a `ChannelMetricSink`, and lets the server's own
interval ticker flush (`synchronize_with_interval: true`, so ticks fall on
wall-clock multiples of the interval I).  A load-generator child
(`loadgen/<kind>.py`; numpy, sockets, grpc; pinned to the CPU backend)
offers an open loop on the interval schedule: interval k's traffic is due
at tick_k + due_share * I, is sent whether or not the server kept up, and
is flushed at tick_{k+1}.

Set-up = JAX + native engine, server boot, the seed's payloads, 3 warm
intervals.  Then `--seconds` of measured intervals.  Then the comparison
with the plain reference (`reference/<kind>.py`), outside the window.
The last line of standard output is the result.  README.md has the rest.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import queue             # noqa: E402
import signal            # noqa: E402
import statistics        # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARM_INTERVALS = 3
MAX_EXTRA_WARM_S = 150.0
MAX_VOID_WINDOWS = 2
HOST_STALL_S = 0.25
TRACE_FLUSHES = 6


def load_module(folder: str, name: str):
    """A module of the benchmark, found by its name in a data file."""
    path = os.path.join(HERE, folder, f"{name}.py") if folder else \
        os.path.join(HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


class RunFailure(Exception):
    """The run cannot produce a result line (no chip, a dead child...)."""


# ---------------------------------------------------------------------------
# The load-generator child: `run.py --loadgen <spec.json>`.  Never imports
# JAX.  Protocol on stdin/stdout: build the payloads; read {"targets"};
# print {"ready"}; read {"first_tick"}; send interval after interval, one
# report line each, until {"last_interval"} says which is the last.
# ---------------------------------------------------------------------------

def loadgen_main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    gen = load_module("loadgen", spec["kind"])
    t0 = time.time()
    state = gen.prepare(spec)       # while the server boots
    try:
        gen.connect(state, json.loads(sys.stdin.readline())["targets"])
        say(ready=state["ready"], build_s=time.time() - t0)
        first_tick = json.loads(sys.stdin.readline())["first_tick"]
        I = spec["interval_s"]
        last = [None]       # the last interval to send, once the parent says

        def listen() -> None:
            line = sys.stdin.readline()
            # a parent that went away stops the generator
            last[0] = json.loads(line)["last_interval"] if line else -1

        threading.Thread(target=listen, daemon=True).start()
        lock = threading.Lock()
        threads = []

        def one(iv: int, due: float) -> None:
            t_send = time.time()
            rep = gen.send_interval(state, spec, iv, due)
            rep.update(interval=iv, start_late_s=max(t_send - due, 0.0),
                       send_s=time.time() - t_send)
            with lock:
                say(**rep)

        iv = 0
        while last[0] is None or iv <= last[0]:
            due = first_tick + (iv + spec["traffic"]["due_share"]) * I
            while time.time() < due and (last[0] is None or iv <= last[0]):
                time.sleep(min(0.05, max(due - time.time(), 0.0)))
            if last[0] is not None and iv > last[0]:
                break
            # open loop: an interval still sending does not hold the next
            t = threading.Thread(target=one, args=(iv, due))
            t.start()
            threads.append(t)
            iv += 1
        for t in threads:
            t.join()
        return 0
    finally:
        gen.close(state)


def hostwatch_main() -> int:
    """`run.py --hostwatch`: a process with nothing to do but sleep 10 ms at
    a time and say when a sleep overran by more than HOST_STALL_S: the
    machine (a sandbox whose cores are shared) stood still, not the
    server.  Ends when its standard input closes."""
    threading.Thread(target=lambda: (sys.stdin.read(), os._exit(0)),
                     daemon=True).start()
    while True:
        t = time.monotonic()
        time.sleep(0.01)
        over = time.monotonic() - t - 0.01
        if over > HOST_STALL_S:
            say(at=time.time(), stall_s=over)


class HostWatch:
    """The hostwatch child and what it has said so far."""

    def __init__(self, env: dict):
        self.stalls: list = []
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--hostwatch"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=ROOT)
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for ln in self.proc.stdout:
            if ln.startswith("{"):
                self.stalls.append(json.loads(ln))

    def close(self) -> None:
        self.proc.stdin.close()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# The parent: the server under test and the measurement
# ---------------------------------------------------------------------------

def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no cell {name!r} in BENCHMARK.json")


def metric_cells(m: dict, bench: dict) -> list:
    return m.get("workloads") or [w["name"] for w in bench["workloads"]]


def device_report(chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse and (device["platform"] != "tpu" or len(devs) < chips):
        raise RunFailure(f"needs {chips} TPU chip(s); JAX reports "
                         f"{device['count']} x {device['platform']}")
    return device


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


class Collector(threading.Thread):
    """Takes each flush's batch off the ChannelMetricSink's queue, notes
    when, and keeps from it only what the reference asked for: the batch
    itself (hundreds of thousands of InterMetrics) is dropped at once, as
    a real sink would, so the server's heap stays a deployment's."""

    def __init__(self, sink, plan: dict, recorder=None):
        super().__init__(daemon=True, name="bench-collector")
        self.sink, self.plan, self.recorder = sink, plan, recorder
        self.taken: list[float] = []       # dequeue time of batch b
        self.reduced: list[dict] = []      # reduction of batch b
        self.spans: dict = {}              # trace_id -> {span name: rec}
        self.reduce_from = float("inf")    # wall time: reduce batches after
        self.stop = threading.Event()

    def run(self) -> None:
        wanted = self.plan["wanted"]
        prefix = self.plan["count_prefix"]
        csuf = self.plan["count_suffix"]
        while not self.stop.is_set():
            try:
                batch = self.sink.queue.get(timeout=0.2)
            except queue.Empty:
                continue
            now = time.time()
            self.taken.append(now)
            red = {"n": len(batch)}
            if now >= self.reduce_from:
                got, count_sum, pm = {}, 0.0, 0
                for m in batch:
                    n = m.name
                    if n in wanted:
                        got[n] = m.value
                    if n.startswith(prefix):
                        if n.endswith("percentile"):
                            pm += 1
                        elif csuf is not None and n.endswith(csuf):
                            count_sum += m.value
                red.update(got=got, count_sum=count_sum,
                           percentile_metrics=pm)
            del batch
            self.reduced.append(red)
            if self.recorder is not None and now >= self.reduce_from:
                for rec in self.recorder.snapshot():
                    if rec["name"].startswith("flush.seg."):
                        self.spans.setdefault(rec["trace_id"], {})[
                            rec["name"]] = rec


def write_yaml(path: str, cfg: dict) -> str:
    import yaml

    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


def run_cell(args, sink_factory=None) -> dict:
    """One run.  Returns the result object (the last line).  `sink_factory`
    lets a test put a broken sink in the timed path's place."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = find_cell(bench, args.workload)
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    cfg = json.load(open(os.path.join(ROOT, config_entry["file"])))
    traffic_name = args.traffic_file or cell["traffic"]
    p = load_json("traffic", f"{traffic_name}.json")
    if args.rehearse:
        p.update(p.get("rehearse", {}))
    p.pop("rehearse", None)
    for kv in args.traffic_override or []:
        k, v = kv.split("=", 1)
        p[k] = json.loads(v)
    kind = p["kind"]
    I = float(p["interval_s"])
    gen = load_module("loadgen", kind)
    ref = load_module("reference", kind)
    metrics_mod = load_module("", "metrics")

    # the program first: a directory without it prints nothing at all
    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import ChannelMetricSink
    from veneur_tpu.testbed.proccluster import child_env
    from veneur_tpu.util import compile_cache

    device = device_report(cell["chips"], args.rehearse)
    say(info="device", device=device, rehearse=args.rehearse)
    cache_dir = compile_cache.enable(min_compile_secs=0.0)

    run_dir = (os.path.join(ROOT, "chiprun_out", "bench",
                            f"run_{args.workload}_{args.seed}")
               if args.keep_run_dir
               else os.path.join(ROOT, ".bench_run", f"{os.getpid()}"))
    os.makedirs(run_dir, exist_ok=True)
    child = srv = collector = watch = None
    tracing = False
    try:
        watch = HostWatch(child_env())
        # -- the load generator starts building its payloads at once
        spec = {"kind": kind, "seed": args.seed, "traffic": p,
                "interval_s": I}
        spec_path = os.path.join(run_dir, "loadgen.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--loadgen",
             spec_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=child_env(), cwd=ROOT)

        # -- the server under test
        server_yaml = dict(cfg["server"])
        server_yaml.update(interval=f"{I:g}s",
                           synchronize_with_interval=True)
        for kv in args.server_override or []:
            k, v = kv.split("=", 1)
            server_yaml[k] = json.loads(v)
        conf = config_mod.read_config(write_yaml(
            os.path.join(run_dir, "server.yaml"), server_yaml))
        sink = (sink_factory or ChannelMetricSink)()
        srv = Server(conf, extra_metric_sinks=[sink])
        srv.start()
        threading.Thread(target=srv.serve, daemon=True,
                         name="bench-ticker").start()
        t_started = time.time()
        agg = srv.aggregator
        targets = {}
        if "statsd_udp" in cfg["listeners"]:
            targets["statsd_udp"] = list(srv.statsd_addrs[0][1])
        if "grpc" in cfg["listeners"]:
            targets["grpc"] = ["127.0.0.1", srv.grpc_import.port]
        plan = ref.plan(gen, args.seed, p, cfg)
        collector = Collector(sink, plan,
                              srv.flight_recorder if args.trace else None)
        collector.start()
        child.stdin.write(json.dumps({"targets": targets}) + "\n")
        child.stdin.flush()
        ready = json.loads(child.stdout.readline() or "{}")
        if "ready" not in ready:
            raise RunFailure("load generator did not get ready")
        say(info="loadgen_ready", **ready)

        # -- the schedule.  The server's ticks are wall-clock multiples
        # of I plus a phase that serve() fixes once, when it returns from
        # its first sleep (a fraction of a millisecond as a rule; 105 ms
        # in one run of sixty, on a machine whose cores are shared).  The
        # generator follows the server's ticks, so the first flush is
        # awaited to learn the phase.
        t_ready = time.time()
        n_meas = max(1, int(args.seconds // I))
        give_up = t_ready + 3 * I + 10.0
        while not srv.flush_timeline.snapshot(last=1):
            if time.time() > give_up:
                raise RunFailure("the server's ticker made no flush")
            time.sleep(0.005)
        ts = srv.flush_timeline.snapshot(last=1)[0]["unix_ts"]
        phase = ts - round(ts / I) * I
        now = time.time()
        first_tick = (int((now - phase) / I) + 1) * I + phase
        if first_tick + p["due_share"] * I - now < 0.3:
            first_tick += I
        child.stdin.write(json.dumps({"first_tick": first_tick}) + "\n")
        child.stdin.flush()
        reports: list = []

        def read_reports() -> None:
            for ln in child.stdout:
                ln = ln.strip()
                if ln.startswith("{"):
                    reports.append(json.loads(ln))

        reader = threading.Thread(target=read_reports, daemon=True)
        reader.start()
        native = srv.native

        def sleep_until(t: float) -> None:
            d = t - time.time()
            if d > 0:
                time.sleep(d)

        def flush_done(tick: float, give_up: float):
            """The timeline's row of the flush at `tick`, once its batch
            has left the sink's queue; None after `give_up`."""
            while time.time() < give_up:
                for row in reversed(srv.flush_timeline.snapshot(last=4)):
                    if (abs(row["unix_ts"] - tick) < 0.45 * I
                            and len(collector.taken) >= row["interval"]):
                        return row
                time.sleep(0.02)
            return None

        # -- warm intervals: at least WARM_INTERVALS, and on until a flush
        # came on time and compiled nothing (a first run in a checkout
        # compiles for seconds, and what piles up behind it compiles
        # again).  The window opens at the tick after such a flush.
        grpc = srv.grpc_import if "grpc" in cfg["listeners"] else None

        def import_faults() -> int:
            if grpc is None:
                return 0
            return grpc.import_errors + (
                grpc.dedup.duplicates if grpc.dedup is not None else 0)

        k, attempt = 1, 0
        while True:
            events_prev, w = None, None
            while w is None:
                tick_k = first_tick + k * I
                row = flush_done(tick_k, tick_k + 0.7 * I)
                events_k = agg.compile_events
                rep = [r for r in reports if r["interval"] == k - 1]
                steady = (row is not None and k >= WARM_INTERVALS - 1
                          and row["unix_ts"] - tick_k < 0.1 * I
                          and events_k == events_prev
                          and bool(rep) and not rep[0].get("errors")
                          and time.time() < tick_k + 0.7 * I)
                if steady or (k - WARM_INTERVALS) * I > MAX_EXTRA_WARM_S:
                    w = k + 1
                events_prev = events_k
                k += 1
            n_all = w + n_meas
            tick_w = first_tick + w * I
            tick_end = tick_w + n_meas * I
            collector.reduce_from = tick_w + 0.5 * I

            marks: list = []
            trace_dir = os.path.join(run_dir, f"trace{attempt}")
            n_trace = min(n_meas, TRACE_FLUSHES)
            trace_w0 = tick_w - 0.2 * I
            trace_w1 = tick_w + n_trace * I - 0.2 * I
            if args.trace:
                import jax.profiler as jp

                sleep_until(trace_w0 - 0.05 * I)
                opts = jp.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jp.start_trace(trace_dir, profiler_options=opts)
                tracing = True

                def mark() -> None:
                    marks.append(time.time_ns())
                    with jp.TraceAnnotation("bench.mark"):
                        pass

                mark()
            sleep_until(tick_w)
            window_late_s = time.time() - tick_w
            setup_s = time.time() - T0
            cpu0 = time.process_time()
            stage0 = native.engine.stage_stats()["totals"] if native else None
            events0 = agg.compile_events
            faults0 = import_faults()
            if args.trace:
                sleep_until(trace_w1)
                mark()
                jp.stop_trace()
                tracing = False
            sleep_until(tick_end)
            cpu1 = time.process_time()
            stage1 = native.engine.stage_stats()["totals"] if native else None
            # a window in which the HOST stood still (seen by a process
            # that has nothing to do with the server) measures the host:
            # it is void, and the run warms up and measures again
            stalls = [st for st in watch.stalls
                      if tick_w - 0.5 * I <= st["at"] <= tick_end + 0.5 * I]
            if not stalls or attempt >= MAX_VOID_WINDOWS:
                break
            say(info="void_window", attempt=attempt, host_stalls=stalls[:5])
            attempt += 1
            k = max(k, int((time.time() - first_tick) / I) + 1)
        child.stdin.write(json.dumps({"last_interval": n_all - 1}) + "\n")
        child.stdin.flush()

        # -- the window is over: the last measured interval's flush is
        # running now; wait for its batch, the child and the lanes
        deadline = time.time() + max(10.0, 5 * I)
        while time.time() < deadline:
            tl = srv.flush_timeline.snapshot()
            if (tl and tl[-1]["unix_ts"] >= tick_end - 0.01
                    and len(collector.taken) >= tl[-1]["interval"]):
                break
            time.sleep(0.05)
        try:
            child.wait(timeout=max(30.0, 5 * I))
        except subprocess.TimeoutExpired:
            pass
        reader.join(timeout=10)
        events1 = agg.compile_events
        timeline = srv.flush_timeline.snapshot()
        collector.stop.set()
        collector.join(timeout=5)
        peak = memory_peak_bytes()
        shapes = sorted((k for k in agg._compiled_shapes
                         if isinstance(k, tuple)), key=repr)
        import_faults_in_window = import_faults() - faults0
        engine_totals = None
        if native is not None:
            srv._drain_native()
            engine_totals = native.engine.totals()
        child_rc = child.poll()
    finally:
        if tracing:
            import jax.profiler as jp
            jp.stop_trace()
        if child is not None and child.poll() is None:
            child.send_signal(signal.SIGTERM)
            try:
                child.wait(timeout=10)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait(timeout=10)
        if collector is not None:
            collector.stop.set()
        if watch is not None:
            watch.close()
        if srv is not None:
            srv.shutdown()

    # ------------------------------------------------------------------
    # After the window: match flushes to ticks, reduce, compare
    # ------------------------------------------------------------------
    problems: list = []
    if window_late_s > 0.1 * I:
        problems.append(f"the window's counters were read {window_late_s:.3f}"
                        "s after its first tick")
    if child_rc != 0:
        problems.append(f"load generator exit code {child_rc}")
    # flushes to ticks: by the timeline's own start times, on the
    # schedule's grid.  The scheduled tick a latency is taken from is the
    # server's own: the grid moved by the least offset any flush started at
    by_index, offsets = {}, []
    for row in timeline:
        idx = round((row["unix_ts"] - first_tick) / I)
        if idx >= 1:
            by_index[idx] = row
            offsets.append(row["unix_ts"] - (first_tick + idx * I))
    # (the 10th percentile, not the least: a flush more than half an
    # interval late lands on the next index with a negative offset)
    tick_offset = sorted(offsets)[len(offsets) // 10] if offsets else 0.0
    window = []          # one entry per measured interval
    for k in range(w, n_all):
        tick = first_tick + (k + 1) * I + tick_offset
        row = by_index.get(k + 1)
        if row is not None and not -0.002 <= row["unix_ts"] - tick \
                < 0.4 * I:
            problems.append(f"flush {row['interval']} started "
                            f"{row['unix_ts'] - tick:.3f}s from its tick")
        b = None if row is None else row["interval"] - 1
        if row is None or b >= len(collector.taken):
            problems.append(f"interval {k}: no flush at its tick reached "
                            "the sink")
            continue
        window.append({"interval": k, "tick": tick, "row": row,
                       "taken": collector.taken[b],
                       **collector.reduced[b]})
    rows = [w["row"]["interval"] for w in window]
    if rows and rows != list(range(rows[0], rows[0] + len(rows))):
        problems.append("flushes of the window are not consecutive")
    win_reports = sorted((r for r in reports if w <= r["interval"] < n_all),
                         key=lambda r: r["interval"])
    if len(win_reports) != n_meas:
        problems.append(f"{len(win_reports)} generator reports for "
                        f"{n_meas} intervals")
    for r in sorted(reports, key=lambda r: r["interval"]):
        say(info="loadgen_interval", **r)
        if r.get("errors") and r["interval"] >= w:
            problems.append(f"interval {r['interval']}: {r['errors'][:2]}")
    late = [r["start_late_s"] for r in win_reports]
    sends = [r["send_s"] for r in win_reports]

    # compile events inside the window: the run is invalid, not slow
    compiles_in_window = events1 - events0
    comparisons = []
    if window and all("got" in w for w in window):
        comparisons = ref.compare(gen, args.seed, p, cfg, plan, window)
    else:
        problems.append("no measured interval to compare")
    comparisons.append({"name": "compile_events_in_window",
                        "value": compiles_in_window, "limit": 0})

    # operations: offered in the window, and not accounted for
    led = gen.ledger(p)
    lines_iv, digests_iv = led["lines"], led["digests"]
    attempted = (lines_iv + digests_iv) * n_meas
    failed = 0
    lines_accounted = 0
    if lines_iv:
        counted = sum(w.get("count_sum", 0.0) for w in window)
        lost_timer = max(0, led["counted_lines"] * n_meas
                         - int(round(counted)))
        sent_run = lines_iv * len(reports)
        lost_engine = max(0, sent_run - engine_totals[0])
        failed += max(lost_timer, lost_engine)
        lines_accounted = lines_iv * n_meas - max(lost_timer, lost_engine)
        comparisons.append({"name": "lines_lost_at_engine",
                            "value": lost_engine, "limit": 0})
        comparisons.append({"name": "malformed_or_too_long",
                            "value": engine_totals[1] + engine_totals[3],
                            "limit": 0})
    fwd_lat, late_forwards = [], 0
    if digests_iv:
        imported = sum(w["row"].get("imported", 0) for w in window)
        per_iv = [w["row"].get("imported", 0) for w in window]
        failed += max(0, digests_iv * n_meas - imported)
        fwd_lat, late_forwards = metrics_mod.forward_latencies_ms(
            win_reports, I, p["due_share"])
        failed += late_forwards * led["digests_per_forward"]
        comparisons.append({"name": "intervals_with_wrong_import_count",
                            "value": sum(1 for x in per_iv
                                         if x != digests_iv), "limit": 0})
        comparisons.append({"name": "import_errors_or_duplicates",
                            "value": import_faults_in_window, "limit": 0})
        comparisons.append({"name": "late_or_failed_forwards",
                            "value": late_forwards, "limit": 0})

    # the observations every metric reader sees
    ctx = {
        "metrics_mod": metrics_mod, "interval_s": I, "traffic": p,
        "window": window, "loadgen_reports": win_reports,
        "flush_ms": metrics_mod.flush_latencies_ms(
            [w["tick"] for w in window], [w["taken"] for w in window]),
        "forward_ms": fwd_lat,
        "cpu_seconds": cpu1 - cpu0, "lines_accounted": lines_accounted,
        "setup_s": setup_s, "stage_before": stage0, "stage_after": stage1,
        "device": device, "trace": None,
    }
    breakdown = None
    if args.trace:
        ctx.update(trace_context(
            args, collector, window, timeline, win_reports, marks,
            (trace_w0, trace_w1), trace_dir, first_tick, I, p,
            led["label"]))
        breakdown = ctx.get("breakdown")

    which = "per_layer" if args.trace else "end_to_end"
    out_metrics = {}
    for m in bench[which]:
        if args.workload not in metric_cells(m, bench):
            continue
        folder = "layer_metrics" if args.trace else "end_to_end"
        spec_m = load_json(folder, f"{m['name']}.json")
        reader_mod = load_module("readers", spec_m["reader"])
        value = reader_mod.read(ctx, **spec_m.get("args", {}))
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    say(info="window", flushes=len(window), interval_s=I,
        warm_intervals=w, void_windows=attempt,
        host_stalls=watch.stalls[-5:], setup_phases_s={
            "to_server_started": round(t_started - T0, 3),
            "to_generator_ready": round(t_ready - T0, 3),
            "to_first_tick": round(first_tick - T0, 3),
            "tick_phase_ms": round(phase * 1e3, 3),
            "tick_offset_ms": round(tick_offset * 1e3, 3),
            "to_window": round(setup_s, 3)},
        flush_samples=len(ctx["flush_ms"]),
        flush_samples_beyond_p95=metrics_mod.samples_beyond(
            len(ctx["flush_ms"]), 95),
        forward_samples=len(fwd_lat),
        generator_start_late_s_max=max(late) if late else None,
        generator_send_s_max=max(sends) if sends else None,
        cpu_seconds=cpu1 - cpu0, compile_events=events1,
        compile_seconds=round(agg.compile_seconds_total, 3),
        compiled_shapes=[repr(k) for k in shapes],
        cache_dir=cache_dir, first_tick=first_tick,
        flush_total_ms_median=(statistics.median(
            w["row"]["total_ms"] for w in window) if window else None))
    for c in comparisons:
        c["ok"] = bool(c["value"] <= c["limit"])
        say(compared=c["name"], value=c["value"], limit=c["limit"],
            ok=c["ok"])
    for pr in problems:
        say(problem=pr)
    # a rehearsal can never be mistaken for a chip run
    comparisons_ok = not problems and all(c["ok"] for c in comparisons)
    say(info="verdict", comparisons_ok=comparisons_ok,
        rehearse=args.rehearse)
    correct = comparisons_ok and not args.rehearse
    dev = dict(device, memory_peak_bytes=peak)
    if args.trace and ctx.get("trace"):
        dev.update(busy_s=ctx["trace"]["busy_s"],
                   window_s=ctx["trace"]["window_s"])
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": dev}
    if breakdown:
        result["breakdown"] = breakdown
    if args.server_override or args.traffic_override or args.traffic_file:
        result["probe"] = True
    if args.keep_run_dir:
        result["run_dir"] = run_dir
    else:
        import shutil
        shutil.rmtree(run_dir, ignore_errors=True)
    return result


def trace_context(args, collector, window, timeline, win_reports, marks,
                  trace_wall, trace_dir, first_tick, I, p, gen_label):
    """What the `--trace 1` readers see: the flush spans of the window's
    flushes, the reduced profiler trace, the flush programs' bytes."""
    import glob

    tr = load_module("", "trace_reduce")
    kb = load_module("", "kernel_bytes")
    by_tid = collector.spans
    flush_spans = []
    for w in window:
        recs = by_tid.get(int(w["row"]["trace_id"], 16), {})
        flush_spans.append({n: r["duration_ms"] for n, r in recs.items()})
    out = {"flush_spans": flush_spans, "kernel_bytes_mod": kb,
           "peaks": load_json("peaks.json")}
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return out
    planes = tr.load(paths[-1])
    if args.keep_run_dir:
        with open(os.path.join(os.path.dirname(trace_dir),
                               "trace_lines.txt"), "w") as f:
            f.write("\n".join(tr.describe(planes)))
    try:
        off = tr.clock_offset_ns(planes, marks)
    except ValueError as e:
        say(problem=f"trace clock: {e}")
        return out
    to_trace = lambda wall_s: wall_s * 1e9 - off      # noqa: E731
    host_spans = []
    flush_windows = []
    order = ["flush.seg.snapshot", "flush.seg.build", "flush.seg.layout",
             "flush.seg.dispatch", "flush.seg.device", "flush.seg.emit",
             "flush.seg.fanout"]
    for row in timeline:
        recs = by_tid.get(int(row["trace_id"], 16), {})
        for name in order:
            r = recs.get(name)
            if r is not None:
                s = r["start_ns"] - off
                host_spans.append((name.replace("flush.seg.", "flush: "),
                                   s, s + r["duration_ms"] * 1e6))
        f0 = to_trace(row["unix_ts"])
        flush_windows.append((f0, f0 + row["total_ms"] * 1e6 + 5e6))
    for r in win_reports:
        due = first_tick + (r["interval"] + p["due_share"]) * I
        host_spans.append((gen_label, to_trace(due + r["start_late_s"]),
                           to_trace(due + r["start_late_s"] + r["send_s"])))
    w0, w1 = to_trace(trace_wall[0]), to_trace(trace_wall[1])
    in_win = [fw for fw in flush_windows if fw[0] >= w0 and fw[1] <= w1]
    red = tr.reduce(planes, (w0, w1), host_spans, in_win)
    say(info="trace", file=os.path.basename(paths[-1]),
        bytes=os.path.getsize(paths[-1]), devices=red["devices"],
        flushes_traced=len(in_win), programs=red["programs"],
        kernel_ms_per_flush=red["kernel_ms_per_flush"],
        kernel_bytes_per_flush=[sum(kb.op_bytes(n) for n in ops
                                    if kb.is_kernel(n))
                                for ops in red["ops_per_flush"]])
    out["trace"] = red
    if red["devices"]:
        out["breakdown"] = {
            "device_ops": [[kb.short_name(n), t]
                           for n, t in red["device_ops"]],
            "idle_gaps": red["idle_gaps"]}
    return out


def arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; always correct: false")
    ap.add_argument("--server-override", action="append", metavar="K=JSON",
                    help="probe/control only: a server YAML key")
    ap.add_argument("--traffic-override", action="append", metavar="K=JSON",
                    help="probe only: a traffic parameter")
    ap.add_argument("--traffic-file", help="probe only: another mix")
    ap.add_argument("--keep-run-dir", action="store_true")
    ap.add_argument("--loadgen", metavar="SPEC", help=argparse.SUPPRESS)
    ap.add_argument("--hostwatch", action="store_true",
                    help=argparse.SUPPRESS)
    return ap


def main() -> int:
    ap = arg_parser()
    args = ap.parse_args()
    if args.loadgen:
        return loadgen_main(args.loadgen)
    if args.hostwatch:
        return hostwatch_main()
    if not args.workload:
        ap.error("--workload is required")
    if args.seconds is None:
        args.seconds = float(json.load(open(os.path.join(
            ROOT, "BENCHMARK.json")))["run_seconds"])
    sys.path.insert(0, ROOT)
    try:
        result = run_cell(args)
    except RunFailure as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:       # noqa: BLE001 - report, then leave
        import traceback
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # server threads of a failed run must not hold the exit
    os._exit(rc)
