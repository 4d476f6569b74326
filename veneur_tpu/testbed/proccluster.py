"""Process-separated 3-tier cluster: real OS processes, real signals.

The in-process testbed (testbed/cluster.py) proves the 3-tier topology
with every tier as the real component — but "kill -9" is a method call
and "the network" is a function boundary.  This harness makes the
"distributed" in the title load-bearing: each tier (N locals -> proxy
-> M globals, globals optionally MESHED over real multi-process gloo
collectives via `multihost.init_multihost`) runs as its own OS process
booted from its own config YAML with its own spool/checkpoint dirs and
ports, supervised by this parent, which does

  * port-0-everywhere + readback: every listener binds port 0 and the
    child writes its RESOLVED ports to `ports.json` (config.port_file,
    atomic rename — the file's appearance is the boot marker), so
    parallel CI runs cannot flake on EADDRINUSE;
  * health-probe readiness: poll the port file, then `/debug/vars`,
    under a bounded startup timeout;
  * graceful SIGTERM teardown with post-mortem log capture — and, for
    the chaos arms, REAL faults: host loss is an actual SIGKILL (no
    atexit, no final flush), stragglers are SIGSTOP/SIGCONT freezes,
    and crash/revive boots a NEW process over the same dirs (a real
    boot-nonce change at the dedup ledger).

Cross-process verification is all HTTP scrape + file tail: intervals
are driven through `POST /flush` (config.http_flush_endpoint), the
conservation oracle reads each tier's `jsonl` sink file with per-flush
framing, ledgers come from `/debug/vars`, the trace assembler drains
`/debug/spans?drain=1`, and the telemetry witness captures each node's
real statsd self-metrics on a parent UDP socket — so `run_dryrun` /
`run_chaos_arm` work against either cluster flavor behind one
interface.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field

import yaml

from veneur_tpu.testbed.cluster import pack_datagrams
from veneur_tpu.util import compile_cache

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# bounded startup: jax import alone costs seconds per process, a meshed
# global group additionally blocks in jax.distributed until every
# member joins
STARTUP_TIMEOUT_S = 120.0
# per-scrape HTTP deadline — a SIGSTOP'd node must time a probe out,
# never wedge the harness
SCRAPE_TIMEOUT_S = 5.0
# lockstep flushes on a meshed global group run real collectives (and
# may pay an XLA compile on the first interval)
FLUSH_TIMEOUT_S = 240.0
POLL_S = 0.05
# SIGTERM grace before the supervisor escalates to SIGKILL
TERM_GRACE_S = 30.0
# reaping a SIGKILLed child is kernel-bounded; this only guards a
# wedged harness
REAP_TIMEOUT_S = 10.0
STATS_JOIN_TIMEOUT_S = 5.0
EMIT_WAIT_S = 30.0
INGEST_WAIT_S = 30.0


def child_env(n_local_devices: int = 0) -> dict:
    """Environment of a spawned tier process: pinned to the CPU backend
    (one process owns a chip — the parent's; a child that initialised a
    TPU backend would fail or hang), the parent's XLA_FLAGS dropped, the
    checkout importable, the compile cache shared."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["GRPC_VERBOSITY"] = "ERROR"
    env["PYTHONPATH"] = (_REPO_ROOT + os.pathsep
                         + env.get("PYTHONPATH", ""))
    # persistent XLA cache: later boots (revivals!) replay flush
    # compiles from disk instead of paying them inside the arm
    compile_cache.child_env_dir(env)
    if n_local_devices > 0:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_"
                            f"count={n_local_devices}")
    return env


@dataclass
class ProcClusterSpec:
    n_locals: int = 1
    n_globals: int = 1
    percentiles: tuple = (0.5, 0.9, 0.99)
    aggregates: tuple = ("min", "max", "count")
    # direct mode: no proxy tier — every local forwards straight to
    # global[0] (the shape where a global fault hits the local's
    # spool; the proxy cannot sit in front of a dedup ledger)
    direct: bool = False
    # durable nodes get per-node spool + checkpoint dirs (kept across
    # SIGKILL; a revived process recovers from them)
    durable: bool = False
    # meshed globals: all M global processes join ONE jax.distributed
    # group over gloo CPU collectives (parallel/multihost.py) and run
    # lockstep SPMD flushes over a mesh_devices-wide device mesh
    meshed: bool = False
    mesh_devices: int = 8
    mesh_replicas: int = 2
    # forward edge (local tier)
    forward_timeout: float = 5.0
    forward_max_retries: int = 2
    forward_retry_backoff: float = 0.05
    forward_deadline_retry_safe: bool = False
    # proxy knobs
    proxy_send_timeout: float = 5.0
    proxy_dial_timeout: float = 2.0
    breaker_failure_threshold: int = 2
    breaker_reset_timeout: float = 0.5
    discovery_interval_s: float = 0.25
    # durable-spool knobs (durable=True)
    spool_max_age_s: float = 60.0
    spool_max_bytes: int = 8 << 20
    spool_replay_interval_s: float = 0.1
    checkpoint_interval_s: float = 0.0
    # the server-side flush ticker must NEVER fire on its own: the
    # parent drives every interval through POST /flush, which is what
    # makes per-interval conservation (and meshed lockstep) assertable
    interval_s: float = 3600.0
    # telemetry witness: True = fresh TelemetryWitness, or an instance
    # shared across cells; nodes' stats_address points at the parent's
    # capture socket and /debug/vars snapshots are scraped at teardown
    telemetry: object = None
    # keep the root dir (configs, logs, dirs) after stop() for
    # post-mortem debugging
    keep_root: bool = False


@dataclass
class ProcNode:
    name: str
    role: str                      # "local" | "global" | "proxy"
    proc: subprocess.Popen = None
    dir: str = ""
    config_path: str = ""
    log_path: str = ""
    ports: dict = field(default_factory=dict)
    emit_path: str = ""
    emit_offset: int = 0
    ckpt_dir: str = ""
    spool_dir: str = ""
    ingest_base: int = 0
    alive: bool = True
    # SIGSTOP'd: scrapes would hang until their timeout — quiescence
    # polls skip frozen nodes (the straggler arm waits on the proxy's
    # breaker/ring state instead)
    frozen: bool = False

    @property
    def http_base(self) -> str:
        hp = self.ports.get("http")
        if not hp:
            return ""
        if isinstance(hp, int):      # proxy port file: bare port
            return f"http://127.0.0.1:{hp}"
        return f"http://{hp[0]}:{hp[1]}"

    @property
    def grpc_port(self) -> int:
        return int(self.ports.get("grpc", 0))

    @property
    def statsd_addr(self):
        entries = self.ports.get("statsd") or []
        for scheme, addr in entries:
            if scheme == "udp":
                return (addr[0], int(addr[1]))
        return None


class ScrapedMetric:
    """One emitted metric parsed back from a node's jsonl sink — the
    cross-process stand-in for InterMetric that verify.py's checks
    duck-type on (name/type/value/tags)."""

    __slots__ = ("name", "type", "value", "tags", "timestamp",
                 "hostname")

    def __init__(self, d: dict):
        self.name = d["name"]
        self.type = d["type"]
        self.value = d["value"]
        self.tags = list(d.get("tags") or [])
        self.timestamp = d.get("timestamp", 0)
        self.hostname = d.get("hostname", "")

    def __repr__(self) -> str:
        return (f"ScrapedMetric({self.name!r}, {self.type!r}, "
                f"{self.value!r})")


class ProcCluster:
    """Duck-types the slice of testbed.Cluster the dryrun/chaos runners
    use — run_interval / drain_local_sinks / accounting /
    collect_trace_spans / stop — over real process boundaries."""

    def __init__(self, spec: ProcClusterSpec):
        self.spec = spec
        self.root = tempfile.mkdtemp(prefix="tb-proc-")
        self.locals: list[ProcNode] = []
        self.globals: list[ProcNode] = []
        self.proxy: ProcNode = None
        self._retired: list[ProcNode] = []
        self._tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self._spans: list[dict] = []
        self._started = False
        # telemetry witness capture socket: every node's statsd
        # self-metrics arrive HERE over real UDP
        self.telemetry = None
        self._stats_sock = None
        self._stats_thread = None
        self._stats_stop = threading.Event()
        if spec.telemetry:
            from veneur_tpu.analysis import telemetry as telemetry_mod
            self.telemetry = (spec.telemetry
                              if isinstance(spec.telemetry,
                                            telemetry_mod
                                            .TelemetryWitness)
                              else telemetry_mod.TelemetryWitness())
            self._stats_sock = socket.socket(socket.AF_INET,
                                             socket.SOCK_DGRAM)
            self._stats_sock.bind(("127.0.0.1", 0))
            self._stats_sock.settimeout(0.2)

    # -- config synthesis --------------------------------------------------

    def _node_dirs(self, name: str) -> tuple[str, str, str]:
        base = os.path.join(self.root, name)
        os.makedirs(base, exist_ok=True)
        ckpt = spool = ""
        if self.spec.durable:
            ckpt = os.path.join(base, "ckpt")
            spool = os.path.join(base, "spool")
            os.makedirs(ckpt, exist_ok=True)
            os.makedirs(spool, exist_ok=True)
        return base, ckpt, spool

    def _common_cfg(self, node_dir: str, hostname: str) -> dict:
        spec = self.spec
        cfg = {
            "hostname": hostname,
            "interval": spec.interval_s,
            "percentiles": list(spec.percentiles),
            "aggregates": list(spec.aggregates),
            "http_address": "127.0.0.1:0",
            "http_flush_endpoint": True,
            "port_file": os.path.join(node_dir, "ports.json"),
            # the harness drives the Python packet path: the native
            # engine's first-boot g++ compile would race across N
            # concurrently-spawned processes, and the engine itself is
            # covered by the in-process testbed and the bench
            "native_ingest": False,
            "metric_sinks": [{
                "kind": "jsonl", "name": "emit",
                "config": {"path": os.path.join(node_dir,
                                                "emit.jsonl")}}],
        }
        if self._stats_sock is not None:
            port = self._stats_sock.getsockname()[1]
            cfg["stats_address"] = f"127.0.0.1:{port}"
        return cfg

    def _global_cfg(self, node_dir: str, hostname: str, idx: int,
                    coordinator_port: int, grpc_port: int = 0) -> dict:
        spec = self.spec
        cfg = self._common_cfg(node_dir, hostname)
        cfg["grpc_address"] = f"127.0.0.1:{grpc_port}"
        if spec.meshed and idx > 0:
            # meshed group: ingest is fanned out to every member in
            # identical order (proxy mesh_fanout) and all members
            # compute the same global flush over their own shard
            # slices — so exactly-once emission is leader-only sink
            # config, the deployment-side half of the contract
            cfg["metric_sinks"] = []
        if spec.durable:
            cfg["checkpoint_dir"] = os.path.join(node_dir, "ckpt")
            cfg["checkpoint_interval"] = spec.checkpoint_interval_s
        if spec.meshed:
            cfg.update({
                "distributed_coordinator":
                    f"127.0.0.1:{coordinator_port}",
                "distributed_num_processes": spec.n_globals,
                "distributed_process_id": idx,
                "mesh_devices": spec.mesh_devices,
                "mesh_replicas": spec.mesh_replicas,
            })
        return cfg

    def _local_cfg(self, node_dir: str, hostname: str,
                   forward_address: str) -> dict:
        spec = self.spec
        cfg = self._common_cfg(node_dir, hostname)
        cfg.update({
            "statsd_listen_addresses": ["udp://127.0.0.1:0"],
            "forward_address": forward_address,
            "forward_timeout": spec.forward_timeout,
            "forward_max_retries": spec.forward_max_retries,
            "forward_retry_backoff": spec.forward_retry_backoff,
            "forward_deadline_retry_safe":
                spec.forward_deadline_retry_safe,
        })
        if spec.durable:
            cfg.update({
                "checkpoint_dir": os.path.join(node_dir, "ckpt"),
                "checkpoint_interval": spec.checkpoint_interval_s,
                "spool_dir": os.path.join(node_dir, "spool"),
                "spool_max_age": spec.spool_max_age_s,
                "spool_max_bytes": spec.spool_max_bytes,
                "spool_replay_interval": spec.spool_replay_interval_s,
            })
        return cfg

    def _proxy_cfg(self, node_dir: str) -> dict:
        spec = self.spec
        return {
            "grpc_address": "127.0.0.1:0",
            "http_address": "127.0.0.1:0",
            "port_file": os.path.join(node_dir, "ports.json"),
            "static_destinations": [
                f"127.0.0.1:{g.grpc_port}" for g in self.globals],
            "discovery_interval": spec.discovery_interval_s,
            "proxy_send_timeout": spec.proxy_send_timeout,
            "proxy_dial_timeout": spec.proxy_dial_timeout,
            "breaker_failure_threshold": spec.breaker_failure_threshold,
            "breaker_reset_timeout": spec.breaker_reset_timeout,
            # meshed global group: every batch to every member, in
            # identical order — the consistent-registration half of
            # the multihost lockstep contract
            "mesh_fanout": spec.meshed,
            # the scraped verification surface (/debug/vars)
            "http_enable_profiling": True,
        }

    # -- node lifecycle (vnlint resource-pairing: every spawn_node ends
    #    in terminate_node or harvest_node on all paths) -------------------

    def _child_env(self, n_local_devices: int = 0) -> dict:
        return child_env(n_local_devices)

    def spawn_node(self, name: str, role: str, cfg: dict,
                   module: str, n_local_devices: int = 0) -> ProcNode:
        """Boot one tier process from its own YAML.  The caller owns
        the node (stored on a tier list) and must terminate_node or
        harvest_node it on every path."""
        node_dir, ckpt, spool = self._node_dirs(name)
        config_path = os.path.join(node_dir, "config.yaml")
        with open(config_path, "w") as f:
            yaml.safe_dump(cfg, f)
        port_file = cfg["port_file"]
        if os.path.exists(port_file):
            os.unlink(port_file)    # a revival must re-prove boot
        log_path = os.path.join(node_dir, "log.txt")
        log_f = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", module, "-f", config_path],
                stdout=log_f, stderr=subprocess.STDOUT,
                cwd=_REPO_ROOT, env=self._child_env(n_local_devices))
        finally:
            log_f.close()           # the child holds its own fd now
        return ProcNode(name=name, role=role, proc=proc, dir=node_dir,
                        config_path=config_path, log_path=log_path,
                        emit_path=os.path.join(node_dir, "emit.jsonl"),
                        ckpt_dir=ckpt, spool_dir=spool)

    def terminate_node(self, node: ProcNode,
                       grace_s: float = TERM_GRACE_S) -> int:
        """Graceful SIGTERM teardown (escalating to SIGKILL after the
        grace); returns the exit code.  Idempotent on dead nodes."""
        node.alive = False
        if node.proc.poll() is None:
            try:
                if node.frozen:
                    # a SIGSTOP'd child cannot act on SIGTERM — thaw it
                    node.proc.send_signal(signal.SIGCONT)
                    node.frozen = False
                node.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
            try:
                node.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                node.proc.kill()
                node.proc.wait(timeout=REAP_TIMEOUT_S)
        return node.proc.returncode

    def harvest_node(self, node: ProcNode) -> int:
        """Reap an already-dead (or deliberately SIGKILLed) child so it
        never lingers as a zombie; SIGKILLs a still-running one (the
        host-loss arm's entry point)."""
        node.alive = False
        if node.proc.poll() is None:
            node.proc.kill()
        node.proc.wait(timeout=REAP_TIMEOUT_S)
        return node.proc.returncode

    def node_log(self, node: ProcNode, tail: int = 4000) -> str:
        """Post-mortem log capture."""
        try:
            with open(node.log_path, "rb") as f:
                data = f.read()
            return data[-tail:].decode(errors="replace")
        except OSError:
            return ""

    def _wait_ready(self, node: ProcNode,
                    timeout_s: float = STARTUP_TIMEOUT_S) -> None:
        """Port-file readback, then /debug/vars health probe."""
        port_file = os.path.join(node.dir, "ports.json")
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if node.proc.poll() is not None:
                raise RuntimeError(
                    f"{node.name} died during boot "
                    f"(rc={node.proc.returncode}):\n"
                    f"{self.node_log(node)}")
            if os.path.exists(port_file):
                try:
                    with open(port_file) as f:
                        node.ports = json.load(f)
                    break
                except (OSError, ValueError):
                    pass        # mid-rename; retry
            time.sleep(POLL_S)
        else:
            raise TimeoutError(
                f"{node.name}: no port file within {timeout_s}s:\n"
                f"{self.node_log(node)}")
        while time.time() < deadline:
            if self._scrape_json(node, "/debug/vars") is not None:
                return
            time.sleep(POLL_S)
        raise TimeoutError(
            f"{node.name}: /debug/vars never became healthy:\n"
            f"{self.node_log(node)}")

    # -- HTTP scrape plumbing ----------------------------------------------

    def _scrape_json(self, node: ProcNode, path: str,
                     timeout_s: float = SCRAPE_TIMEOUT_S):
        """GET a JSON endpoint; None on any failure (a frozen or dead
        node must never wedge the harness — callers treat None as
        'no new observation')."""
        if not node.http_base:
            return None
        try:
            with urllib.request.urlopen(node.http_base + path,
                                        timeout=timeout_s) as resp:
                return json.loads(resp.read())
        except (urllib.error.URLError, OSError, ValueError):
            return None

    def _post(self, node: ProcNode, path: str,
              timeout_s: float = FLUSH_TIMEOUT_S):
        req = urllib.request.Request(node.http_base + path, data=b"",
                                     method="POST")
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return json.loads(resp.read())

    def scrape_vars(self, node: ProcNode):
        return self._scrape_json(node, "/debug/vars")

    # -- start / stop ------------------------------------------------------

    def _free_port(self) -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def start(self) -> "ProcCluster":
        spec = self.spec
        if self._stats_sock is not None:
            self._stats_thread = threading.Thread(
                target=self._stats_capture_loop, daemon=True,
                name="proc-stats-witness")
            self._stats_thread.start()
        coordinator_port = (self._free_port() if spec.meshed else 0)
        devs_per_proc = (spec.mesh_devices // max(1, spec.n_globals)
                         if spec.meshed else 0)
        try:
            for i in range(spec.n_globals):
                name = f"pg{i}"
                node_dir, _, _ = self._node_dirs(name)
                self.globals.append(self.spawn_node(
                    name, "global",
                    self._global_cfg(node_dir, f"tb-{name}", i,
                                     coordinator_port),
                    "veneur_tpu.cli.veneur",
                    n_local_devices=devs_per_proc))
            # meshed members block in jax.distributed until every peer
            # joins, so readiness is polled only after all are spawned
            for g in self.globals:
                self._wait_ready(g)
            if not spec.direct:
                name = "pproxy"
                node_dir, _, _ = self._node_dirs(name)
                self.proxy = self.spawn_node(
                    name, "proxy", self._proxy_cfg(node_dir),
                    "veneur_tpu.cli.veneur_proxy")
                self._wait_ready(self.proxy)
            fwd = (f"127.0.0.1:{self.globals[0].grpc_port}"
                   if spec.direct
                   else f"127.0.0.1:{self.proxy.grpc_port}")
            for i in range(spec.n_locals):
                name = f"pl{i}"
                node_dir, _, _ = self._node_dirs(name)
                self.locals.append(self.spawn_node(
                    name, "local",
                    self._local_cfg(node_dir, f"tb-{name}", fwd),
                    "veneur_tpu.cli.veneur"))
            for n in self.locals:
                self._wait_ready(n)
        except BaseException:
            self.stop()
            raise
        self._started = True
        return self

    def _stats_capture_loop(self) -> None:
        while not self._stats_stop.is_set():
            try:
                data, _ = self._stats_sock.recvfrom(65536)
            except socket.timeout:
                continue
            except OSError:
                return
            self.telemetry.record_statsd_payload(data)

    def collect_telemetry_vars(self) -> None:
        """Scrape every live tier's /debug/vars into the witness (the
        HTTP equivalent of TelemetryWitness.collect)."""
        if self.telemetry is None:
            return
        for node in self._all_nodes():
            if not node.alive or node.frozen:
                continue
            snap = self.scrape_vars(node)
            if snap is not None:
                tier = "proxy" if node.role == "proxy" else "server"
                self.telemetry.add_vars_snapshot(tier, snap)

    def _all_nodes(self) -> list[ProcNode]:
        out = list(self.locals)
        if self.proxy is not None:
            out.append(self.proxy)
        out.extend(self.globals)
        return out

    def stop(self) -> None:
        self.collect_telemetry_vars()
        # locals first (their shutdown flushes forward into the still-
        # running upper tiers), then proxy, then globals — CONCURRENTLY
        # within the global tier: a meshed member's graceful exit must
        # not wait on a peer the parent has not signalled yet
        for n in self.locals:
            self.terminate_node(n)
        if self.proxy is not None:
            self.terminate_node(self.proxy)
        threads = [threading.Thread(target=self.terminate_node,
                                    args=(g,)) for g in self.globals]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for n in self._retired:
            self.harvest_node(n)
        self._stats_stop.set()
        if self._stats_thread is not None:
            self._stats_thread.join(timeout=STATS_JOIN_TIMEOUT_S)
        if self._stats_sock is not None:
            self._stats_sock.close()
        try:
            self._tx.close()
        except OSError:
            pass
        if not self.spec.keep_root:
            shutil.rmtree(self.root, ignore_errors=True)

    def __enter__(self) -> "ProcCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- chaos primitives: REAL signals ------------------------------------

    def sigkill_global(self, idx: int) -> ProcNode:
        """Actual host loss: SIGKILL — no atexit, no final flush, no
        spool drain.  The node's dirs are kept for a revival."""
        node = self.globals[idx]
        self.harvest_node(node)
        self._retired.append(node)
        return node

    def sigkill_local(self, idx: int) -> ProcNode:
        node = self.locals[idx]
        self.harvest_node(node)
        self._retired.append(node)
        return node

    def sigstop_global(self, idx: int) -> None:
        """Real straggler: the process freezes mid-whatever — its RPCs
        neither refuse nor reset, they just hang."""
        self.globals[idx].frozen = True
        self.globals[idx].proc.send_signal(signal.SIGSTOP)

    def sigcont_global(self, idx: int) -> None:
        self.globals[idx].frozen = False
        self.globals[idx].proc.send_signal(signal.SIGCONT)

    def revive_global(self, idx: int) -> None:
        """Boot a NEW process over the crashed instance's dirs: same
        hostname (=> same checkpoint/spool state), same gRPC port (the
        locals'/proxy's channels re-reach it), fresh boot nonce."""
        if self.spec.meshed:
            # a gloo group cannot admit a late joiner: the revived
            # child would hang on a dead coordinator until the boot
            # timeout. Re-meshing the survivors + the replacement is
            # the ROADMAP #5(b) story; fail crisply until it exists.
            raise NotImplementedError(
                "revive_global on a MESHED spec needs a re-mesh "
                "story (ROADMAP #5b); only unmeshed specs revive")
        old = self.globals[idx]
        node_dir, _, _ = self._node_dirs(old.name)
        node = self.spawn_node(
            old.name, "global",
            self._global_cfg(node_dir, f"tb-{old.name}", idx,
                             0, grpc_port=old.grpc_port),
            "veneur_tpu.cli.veneur")
        # same emit file: the reader's offset must survive the swap so
        # the revived instance's rows attribute to the right interval
        node.emit_offset = old.emit_offset
        self.globals[idx] = node
        self._wait_ready(node)

    def revive_local(self, idx: int) -> None:
        old = self.locals[idx]
        node_dir, _, _ = self._node_dirs(old.name)
        fwd = (f"127.0.0.1:{self.globals[0].grpc_port}"
               if self.spec.direct
               else f"127.0.0.1:{self.proxy.grpc_port}")
        node = self.spawn_node(
            old.name, "local",
            self._local_cfg(node_dir, f"tb-{old.name}", fwd),
            "veneur_tpu.cli.veneur")
        node.emit_offset = old.emit_offset
        node.ingest_base = 0    # a fresh process counts from zero
        self.locals[idx] = node
        self._wait_ready(node)

    def checkpoint_global(self, idx: int) -> bool:
        return bool(self._post(self.globals[idx],
                               "/checkpoint").get("ok"))

    def checkpoint_local(self, idx: int) -> bool:
        return bool(self._post(self.locals[idx],
                               "/checkpoint").get("ok"))

    # -- traffic + interval driving ----------------------------------------

    def send_lines(self, local_idx: int, lines: list[bytes]) -> int:
        node = self.locals[local_idx]
        # capture the ingest baseline BEFORE the first datagram leaves:
        # `processed` RESETS at every flush (it is an interval counter),
        # so a baseline carried across intervals would be garbage —
        # wait_ingested waits for baseline + values
        v = self.scrape_vars(node)
        if v is None:
            raise RuntimeError(
                f"{node.name}: /debug/vars unreachable before send:\n"
                f"{self.node_log(node)}")
        node.ingest_base = int(v["processed"])
        dgrams, values = pack_datagrams(lines)
        addr = node.statsd_addr
        for dgram in dgrams:
            self._tx.sendto(dgram, addr)
        return values

    def wait_ingested(self, local_idx: int, n_values: int,
                      timeout_s: float = INGEST_WAIT_S) -> None:
        """Scrape-based ingest wait: the local's `processed` counter
        (baselined by send_lines just before the datagrams left) must
        reach base + n AND hold still for a few polls — the span-
        extraction path also ticks `processed`, so the threshold alone
        could be reached while tb. lines are still in flight."""
        node = self.locals[local_idx]
        want = node.ingest_base + n_values
        deadline = time.time() + timeout_s
        stable = 0
        last = -1
        while time.time() < deadline:
            v = self.scrape_vars(node)
            got = int(v["processed"]) if v else -1
            if got >= want and got == last:
                stable += 1
                if stable >= 2:
                    return
            else:
                stable = 0
            last = got
            time.sleep(POLL_S)
        raise TimeoutError(
            f"{node.name}: ingested {last - node.ingest_base}"
            f"/{n_values} values in {timeout_s}s")

    def flush_locals(self) -> None:
        for n in self.locals:
            self._post(n, "/flush")

    def _flush_one_global(self, node: ProcNode,
                          errs: list) -> None:
        try:
            self._post(node, "/flush")
        except Exception as e:  # noqa: BLE001 - surfaced by caller
            errs.append((node.name, e))

    def flush_globals(self) -> list[list]:
        """Flush every global — CONCURRENTLY, because a meshed group's
        flushes are lockstep SPMD programs whose collectives block
        until every member enters — then wait out the async egress and
        read each node's new jsonl emissions."""
        errs: list = []
        threads = [threading.Thread(target=self._flush_one_global,
                                    args=(g, errs))
                   for g in self.globals]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=FLUSH_TIMEOUT_S + 30.0)
        wedged = [g.name for g, t in zip(self.globals, threads)
                  if t.is_alive()]
        if wedged:
            # name the real fault here — falling through would die
            # later in _read_emissions with a misleading "no flush
            # frame appeared" pointing at the sink file
            raise RuntimeError(f"global flush wedged: {wedged}")
        if errs:
            raise RuntimeError(f"global flush failed: {errs}")
        if self.spec.meshed:
            # every member computed the identical global result over
            # its own shard slices; only the leader carries sinks
            return [self._read_emissions(self.globals[0])]
        return [self._read_emissions(g) for g in self.globals]

    def drain_local_sinks(self) -> list[list]:
        return [self._read_emissions(n) for n in self.locals]

    def _read_emissions(self, node: ProcNode,
                        timeout_s: float = EMIT_WAIT_S) -> list:
        """Tail the node's jsonl sink from its last offset: wait for at
        least one NEW flush frame (the egress lanes deliver async), then
        parse every complete row up to the last frame."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            try:
                with open(node.emit_path, "rb") as f:
                    f.seek(node.emit_offset)
                    chunk = f.read()
            except OSError:
                chunk = b""
            frame_end = chunk.rfind(b'{"flush"')
            if frame_end >= 0:
                nl = chunk.find(b"\n", frame_end)
                if nl >= 0:
                    body = chunk[:nl + 1]
                    node.emit_offset += len(body)
                    out = []
                    for line in body.splitlines():
                        if not line.strip():
                            continue
                        try:
                            row = json.loads(line)
                        except ValueError:
                            # a SIGKILL mid-write leaves a torn,
                            # newline-less fragment that the revived
                            # process appends its next frame after
                            # (sinks/simple.py torn-tail contract);
                            # skip it — the conservation oracle still
                            # accounts any points it carried as loss
                            continue
                        if "flush" not in row:
                            out.append(ScrapedMetric(row))
                    return out
            time.sleep(POLL_S)
        raise TimeoutError(
            f"{node.name}: no flush frame appeared in emit.jsonl "
            f"within {timeout_s}s")

    # -- settle: scrape-based quiescence -----------------------------------

    def _pipe_counters(self) -> tuple:
        parts = []
        for n in self.locals:
            v = self.scrape_vars(n) or {}
            fw = v.get("forward") or {}
            sp = v.get("spool") or {}
            parts.append((
                tuple(sorted(fw.items())),
                sp.get("spilled", 0), sp.get("replayed", 0),
                sp.get("expired", 0), sp.get("dropped", 0),
                v.get("forward_slots_dropped", 0)))
        if self.proxy is not None:
            v = self.scrape_vars(self.proxy) or {}
            parts.append((
                v.get("received", 0), v.get("routed", 0),
                v.get("dropped", 0), v.get("no_destination", 0),
                v.get("rerouted", 0),
                tuple(sorted((v.get("destination_totals")
                              or {}).items()))))
        for g in self.globals:
            if not g.alive or g.frozen:
                continue
            v = self.scrape_vars(g) or {}
            parts.append((v.get("imported_total", 0),
                          v.get("imported", 0)))
        return tuple(parts)

    def settle(self, timeout_s: float = 60.0, quiet_polls: int = 3,
               poll_s: float = 0.1) -> None:
        """Scraped quiescence: every forward/route/import counter
        stable for `quiet_polls` consecutive polls.  (No in-process
        semaphores to peek at across a process boundary — counter
        stability IS the interface.)"""
        deadline = time.time() + timeout_s
        last = None
        stable = 0
        while time.time() < deadline:
            cur = self._pipe_counters()
            if cur == last:
                stable += 1
                if stable >= quiet_polls:
                    return
            else:
                stable = 0
            last = cur
            time.sleep(poll_s)
        raise TimeoutError(f"proc cluster did not settle within "
                           f"{timeout_s}s")

    def wait_spool_drained(self, timeout_s: float = 60.0) -> None:
        deadline = time.time() + timeout_s
        pend = None
        while time.time() < deadline:
            pend = []
            for n in self.locals:
                v = self.scrape_vars(n) or {}
                sp = v.get("spool")
                if sp is not None:
                    pend.append(sp.get("pending_records", 0))
            if pend and all(p == 0 for p in pend):
                return
            time.sleep(POLL_S)
        raise TimeoutError(
            f"spool did not drain within {timeout_s}s: {pend}")

    def wait_local(self, local_idx: int, cond, what: str = "",
                   timeout_s: float = 60.0) -> dict:
        """Poll one local's scraped /debug/vars until cond(vars) is
        true; returns the satisfying snapshot."""
        deadline = time.time() + timeout_s
        v = None
        while time.time() < deadline:
            v = self.scrape_vars(self.locals[local_idx])
            if v is not None and cond(v):
                return v
            time.sleep(POLL_S)
        raise TimeoutError(f"{what or 'condition'} not reached "
                           f"within {timeout_s}s: {v}")

    def run_interval(self, per_local_lines: list[list[bytes]],
                     settle_timeout_s: float = 60.0) -> list[list]:
        counts = [self.send_lines(i, lines)
                  for i, lines in enumerate(per_local_lines)]
        for i, c in enumerate(counts):
            if c:
                self.wait_ingested(i, c)
        self.flush_locals()
        self.settle(timeout_s=settle_timeout_s)
        return self.flush_globals()

    # -- scraped accounting (the in-process Cluster.accounting shape) ------

    def accounting(self) -> dict:
        fw = {"sent": 0, "retries": 0, "dropped": 0, "spilled": 0}
        spool = {"spilled": 0, "replayed": 0, "expired": 0,
                 "dropped": 0, "pending": 0, "spilled_points": 0,
                 "replayed_points": 0, "expired_points": 0,
                 "dropped_points": 0}
        ckpt = {"writes": 0, "restores": 0, "errors": 0, "age_ms": 0.0}
        dedup = {"recorded": 0, "duplicates": 0}
        egress = {"flushed": 0, "retried": 0, "spilled": 0,
                  "replayed": 0, "expired": 0, "dropped": 0,
                  "pending": 0}
        fsd = 0
        local_flushes = global_flushes = imported = 0
        for n in self.locals:
            v = self.scrape_vars(n) or {}
            for k, val in (v.get("forward") or {}).items():
                fw[k] = fw.get(k, 0) + val
            sp = v.get("spool")
            if sp:
                for k in ("spilled", "replayed", "expired", "dropped",
                          "spilled_points", "replayed_points",
                          "expired_points", "dropped_points"):
                    spool[k] += sp.get(k, 0)
                spool["pending"] += sp.get("pending_records", 0)
            fsd += v.get("forward_slots_dropped", 0)
            local_flushes += v.get("flush_count", 0)
            self._fold_common(v, ckpt, egress)
        for g in self.globals:
            v = ((self.scrape_vars(g) or {})
                 if g.alive and not g.frozen else {})
            dd = v.get("dedup")
            if dd:
                dedup["recorded"] += dd.get("recorded", 0)
                dedup["duplicates"] += dd.get("duplicates", 0)
            imported += v.get("imported_total", 0)
            global_flushes += v.get("flush_count", 0)
            self._fold_common(v, ckpt, egress)
        pstats = {"received": 0, "routed": 0, "dropped": 0,
                  "no_destination": 0, "rerouted": 0}
        dest_totals = {"sent": 0, "dropped": 0}
        breakers = {}
        reshard = {"epochs": 0, "moved_total": 0, "handoff_total": 0,
                   "last": None}
        if self.proxy is not None:
            v = self.scrape_vars(self.proxy) or {}
            for k in pstats:
                pstats[k] = v.get(k, 0)
            dest_totals = v.get("destination_totals", dest_totals)
            breakers = v.get("breakers", {})
            reshard = v.get("reshard", reshard)
        return {
            "forward": fw,
            "cardinality": {"keys_evicted": 0,
                            "tenants_over_budget": 0,
                            "rollup_points": 0},
            "egress": egress,
            "spool": spool,
            "checkpoint": ckpt,
            "dedup": dedup,
            "reshard": reshard,
            "forward_slots_dropped": fsd,
            "proxy": pstats,
            "destination_totals": dest_totals,
            "breakers": breakers,
            "imported": imported,
            "local_flushes": local_flushes,
            "global_flushes": global_flushes,
            "dropped_total": (fw["dropped"] + fsd
                              + pstats["dropped"]
                              + pstats["no_destination"]
                              + dest_totals.get("dropped", 0)
                              + spool["expired_points"]
                              + spool["dropped_points"]
                              + egress["dropped"]
                              + egress["expired"]),
        }

    @staticmethod
    def _fold_common(v: dict, ckpt: dict, egress: dict) -> None:
        cs = v.get("checkpoint")
        if cs:
            ckpt["writes"] += cs.get("writes", 0)
            ckpt["restores"] += cs.get("restores", 0)
            ckpt["errors"] += cs.get("errors", 0)
            ckpt["age_ms"] = max(ckpt["age_ms"], cs.get("age_ms", 0.0))
        es = v.get("egress")
        if es:
            egress["flushed"] += es.get("flushed", 0)
            egress["retried"] += es.get("retried", 0)
            egress["spilled"] += es.get("spilled", 0)
            egress["replayed"] += es.get("replayed", 0)
            egress["expired"] += es.get("expired", 0)
            egress["dropped"] += (es.get("dropped", 0)
                                  + es.get("queue_dropped", 0)
                                  + es.get("spool_dropped", 0))
            egress["pending"] += es.get("pending", 0)

    # -- trace scrape (the cross-process assembler's raw material) ---------

    def collect_trace_spans(self) -> list[dict]:
        """Drain /debug/spans?drain=1 on every live tier; batches
        accumulate across calls so a mid-run drain never loses spans to
        ring eviction.  A SIGKILLed node's un-scraped spans died with
        its process — the honest cross-process semantics."""
        for i, n in enumerate(self.locals):
            self._drain_spans(n, f"local-{i}")
        if self.proxy is not None:
            self._drain_spans(self.proxy, "proxy")
        for i, g in enumerate(self.globals):
            self._drain_spans(g, f"global-{i}")
        return list(self._spans)

    def _drain_spans(self, node: ProcNode, tier: str) -> None:
        if not node.alive or node.frozen:
            return
        body = self._scrape_json(node, "/debug/spans?drain=1")
        if body:
            self._spans.extend(dict(s, tier=tier)
                               for s in body.get("spans", []))
