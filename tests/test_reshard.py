"""Elastic ring resharding (ISSUE 7): bounded key movement, the
two-phase set_members reshard record, drain-and-forward handoff of a
retiring destination's buffer, and the breaker-retention fix (a reshard
can never resurrect a tripped destination without a successful
probe)."""

import json
import math
import time
import urllib.request

import pytest

from veneur_tpu import config as config_mod
from veneur_tpu import failpoints
from veneur_tpu.core.server import Server
from veneur_tpu.forward import convert
from veneur_tpu.proxy import consistent
from veneur_tpu.proxy.consistent import ConsistentHash
from veneur_tpu.proxy.destinations import Destinations
from veneur_tpu.proxy.proxy import Proxy, ProxyConfig
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricScope
from veneur_tpu.sinks import simple as simple_sinks


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.clear()
    yield
    failpoints.clear()


def boot_global():
    cfg = config_mod.Config(
        grpc_address="127.0.0.1:0", interval=0.05,
        percentiles=[0.5], aggregates=["count"], hostname="g")
    sink = simple_sinks.ChannelMetricSink()
    srv = Server(cfg, extra_metric_sinks=[sink])
    srv.start()
    return srv, sink


def fm_counter(name, value):
    return sm.ForwardMetric(name=name, tags=[], kind="counter",
                            scope=MetricScope.GLOBAL_ONLY,
                            counter_value=value)


# ---------------------------------------------------------------------------
# bounded movement (satellite 3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 3, 5, 8])
def test_ring_growth_moves_bounded_key_fraction(n):
    """N -> N+1 moves <= ceil(1.5 * K / N) keys on seeded workloads:
    only keys the joiner now owns remap; everyone else's assignment is
    untouched (the whole point of consistent hashing vs mod-N)."""
    K = 4000
    members = [f"node-{i}:8128" for i in range(n)]
    old = ConsistentHash(members)
    new = ConsistentHash(members + [f"node-{n}:8128"])
    keys = [f"tb.metric.{i}" for i in range(K)]
    moved = sum(1 for k in keys if old.get(k) != new.get(k))
    assert 0 < moved <= math.ceil(1.5 * K / n), (n, moved)
    # every moved key moved TO the joiner (nothing reshuffled laterally)
    for k in keys:
        if old.get(k) != new.get(k):
            assert new.get(k) == f"node-{n}:8128"


def test_moved_keys_helper_is_deterministic_and_sane():
    a = consistent.moved_keys(["a", "b"], ["a", "b", "c"], 4096)
    b = consistent.moved_keys(["a", "b"], ["a", "b", "c"], 4096)
    assert a == b
    moved, sampled = a
    assert sampled == 4096 and 0 < moved <= 1.5 * sampled / 2
    assert consistent.moved_keys([], ["a"], 100) == (0, 0)
    # identical memberships move nothing
    assert consistent.moved_keys(["a", "b"], ["a", "b"], 100) == (0, 100)


# ---------------------------------------------------------------------------
# two-phase reshard + record
# ---------------------------------------------------------------------------

def test_set_members_two_phase_record_and_failpoint():
    g1, _ = boot_global()
    g2, _ = boot_global()
    g3, _ = boot_global()
    a1 = f"127.0.0.1:{g1.grpc_import.port}"
    a2 = f"127.0.0.1:{g2.grpc_import.port}"
    a3 = f"127.0.0.1:{g3.grpc_import.port}"
    d = Destinations(reshard_sample_keys=512)
    try:
        d.set_members([a1, a2])
        rs = d.reshard_stats()
        assert rs["epochs"] == 1 and rs["last"]["committed"]
        assert rs["last"]["added"] == sorted([a1, a2])

        # scale-up: the reshard failpoint fires inside the window
        fp = failpoints.configure("destinations.reshard", "delay",
                                  delay_s=0.0)
        try:
            d.set_members([a1, a2, a3])
        finally:
            failpoints.disarm("destinations.reshard")
        assert fp.fired == 1
        rs = d.reshard_stats()
        last = rs["last"]
        assert rs["epochs"] == 2
        assert last["added"] == [a3] and last["removed"] == []
        assert last["members_after"] == sorted([a1, a2, a3])
        # bounded movement, measured: one joiner on a 2-ring
        assert 0 < last["keys_moved"] <= 1.5 * last["sample_keys"] / 2
        assert last["duration_s"] >= 0.0

        # scale-down: the leaver lands in `removed`
        d.set_members([a1, a2])
        last = d.reshard_stats()["last"]
        assert last["removed"] == [a3] and d.size() == 2

        # steady state: no new reshard epoch per idle poll
        epochs = d.reshard_stats()["epochs"]
        d.set_members([a1, a2])
        assert d.reshard_stats()["epochs"] == epochs
    finally:
        d.clear()
        for srv in (g1, g2, g3):
            srv.shutdown()


def test_two_callers_offered_one_membership_publish_one_reshard():
    """The discovery poll and an operator's direct call can both be
    offered the same new membership.  Each takes its diff before the
    reshard window; the one admitted second finds the joiner already
    on the ring and must not publish an empty record over the real one
    (what `ring-scale-up` read as `added == []` on a loaded host)."""
    import threading
    g1, _ = boot_global()
    g2, _ = boot_global()
    a1 = f"127.0.0.1:{g1.grpc_import.port}"
    a2 = f"127.0.0.1:{g2.grpc_import.port}"
    d = Destinations(reshard_sample_keys=512)
    try:
        d.set_members([a1])
        # hold the first caller inside its window while the second
        # takes its diff (joiner not connected yet) and queues behind it
        failpoints.configure("destinations.reshard", "delay", delay_s=0.4)
        first = threading.Thread(target=d.set_members, args=([a1, a2],))
        first.start()
        time.sleep(0.1)
        d.set_members([a1, a2])
        first.join(timeout=10.0)
        assert not first.is_alive()
        rs = d.reshard_stats()
        assert rs["epochs"] == 2
        assert rs["last"]["added"] == [a2] and rs["last"]["committed"]
        # the window is not left open: a real change still reshards
        failpoints.disarm("destinations.reshard")
        d.set_members([a1])
        assert d.reshard_stats()["last"]["removed"] == [a2]
    finally:
        d.clear()
        for srv in (g1, g2):
            srv.shutdown()


def test_reshard_drop_failpoint_aborts_but_commits_record():
    """A fault injected at the top of the reshard window aborts the
    membership change; the window still commits (no wedged serial lock,
    the record shows the non-change) and the next poll retries."""
    g1, _ = boot_global()
    a1 = f"127.0.0.1:{g1.grpc_import.port}"
    d = Destinations()
    try:
        with failpoints.active("destinations.reshard", "drop", times=1):
            with pytest.raises(failpoints.FailpointDrop):
                d.set_members([a1])
        rs = d.reshard_stats()
        assert rs["epochs"] == 1 and rs["last"]["committed"]
        assert rs["last"]["members_after"] == []   # nothing changed
        d.set_members([a1])                        # retry succeeds
        assert d.size() == 1
        assert d.reshard_stats()["epochs"] == 2
    finally:
        d.clear()
        g1.shutdown()


# ---------------------------------------------------------------------------
# drain-and-forward handoff
# ---------------------------------------------------------------------------

def test_reshard_handoff_reroutes_buffered_metrics():
    """Scale-down with a wedged leaver: metrics still queued behind a
    stalled sender re-route through the NEW ring (handoff) instead of
    dying in the close sweep — the survivor receives them, the reshard
    record counts them, and they are NOT double-counted as dropped."""
    g1, s1 = boot_global()
    g2, s2 = boot_global()
    a1 = f"127.0.0.1:{g1.grpc_import.port}"
    a2 = f"127.0.0.1:{g2.grpc_import.port}"
    proxy = Proxy(ProxyConfig(
        static_destinations=[a1, a2],
        discovery_interval=3600,              # drive discovery manually
        reshard_handoff_timeout=0.2))
    proxy.start()
    try:
        # find keys owned by each destination under the CURRENT ring
        dest1 = proxy.destinations._dests[a1]
        keys_to_1, keys_to_2 = [], []
        i = 0
        while (len(keys_to_1) < 6 or len(keys_to_2) < 6) and i < 500:
            name = f"ho.k{i}"
            pb = convert.to_pb(fm_counter(name, 1))
            (keys_to_1 if proxy.destinations.get(
                proxy.routing_key(pb)) is dest1 else keys_to_2).append(
                    name)
            i += 1
        victim_keys = keys_to_1[:6]

        # wedge the victim's sender: the first send sleeps well past the
        # handoff drain window, so everything enqueued after it is still
        # in the queue when the sweep runs
        failpoints.configure("proxy.send_batch", "delay",
                             delay_s=1.2, times=1)
        proxy.handle_metric(convert.to_pb(fm_counter(victim_keys[0], 1)))
        time.sleep(0.1)          # the sender dequeues + starts sleeping
        for name in victim_keys[1:]:
            proxy.handle_metric(convert.to_pb(fm_counter(name, 1)))

        # scale the victim out: two-phase reshard with drain-and-forward
        proxy.destinations.set_members([a2])
        rs = proxy.destinations.reshard_stats()
        assert rs["last"]["removed"] == [a1]
        assert rs["last"]["handoff_metrics"] >= len(victim_keys) - 1
        assert rs["handoff_total"] == rs["last"]["handoff_metrics"]
        with proxy._stats_lock:
            assert proxy.stats["rerouted"] >= len(victim_keys) - 1

        # the survivor aggregates the handed-off keys
        deadline = time.time() + 10
        got = set()
        while time.time() < deadline and not set(
                victim_keys[1:]) <= got:
            g2.flush()
            while not s2.queue.empty():
                for m in s2.queue.get():
                    got.add(m.name)
            time.sleep(0.05)
        assert set(victim_keys[1:]) <= got, (victim_keys, got)
    finally:
        failpoints.clear()
        proxy.stop()
        g1.shutdown()
        g2.shutdown()


# ---------------------------------------------------------------------------
# breaker retention across membership flaps (satellite 1)
# ---------------------------------------------------------------------------

def test_tripped_breaker_survives_reshard_flap():
    """Trip an address's breaker, flap it out of and back into the
    wanted set while the breaker is still OPEN: the tripped state must
    survive the flap (no probe-free resurrection), and only a
    successful half-open probe may restore the member."""
    # an address nothing listens on: dials fail fast (connection refused)
    import socket
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()               # released: connects now get RST
    dead = f"127.0.0.1:{dead_port}"

    d = Destinations(dial_timeout_s=0.3, breaker_threshold=1,
                     breaker_reset_s=30.0)
    try:
        d.set_members([dead])               # dial fails -> breaker OPEN
        st = d.breaker_stats()[dead]
        assert st["state"] == "open" and st["trips"] == 1

        # flap out: the engaged breaker is RETAINED (the old behavior
        # deleted it here, so the re-add below would dial probe-free)
        d.set_members([])
        assert d.breaker_stats()[dead]["trips"] == 1

        # flap back in while open: no dial is admitted, state keeps its
        # trip history, and the member stays out of the ring
        d.set_members([dead])
        st = d.breaker_stats()[dead]
        assert st["state"] == "open" and st["trips"] == 1
        assert d.size() == 0

        # a live server appears at the address AND the cooldown expires:
        # the next offer becomes the half-open probe and restores it
        with d._lock:
            d._breakers[dead].open_until = time.monotonic() - 0.01
        cfg = config_mod.Config(grpc_address=dead, interval=0.05,
                                percentiles=[0.5], aggregates=["count"],
                                hostname="g")
        srv = Server(cfg)
        srv.start()
        try:
            d.set_members([dead])
            assert d.size() == 1
            assert dead not in d.breaker_stats()   # breaker closed
        finally:
            srv.shutdown()
    finally:
        d.clear()


def test_proxy_debug_vars_exposes_reshard_record():
    g1, _ = boot_global()
    a1 = f"127.0.0.1:{g1.grpc_import.port}"
    proxy = Proxy(ProxyConfig(static_destinations=[a1],
                              discovery_interval=3600,
                              http_enable_profiling=True))
    proxy.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{proxy.http_port}/debug/vars",
                timeout=5) as resp:
            stats = json.loads(resp.read())
        assert stats["reshard"]["epochs"] == 1
        assert stats["reshard"]["last"]["committed"] is True
        assert stats["reshard"]["last"]["members_after"] == [a1]
        assert "rerouted" in stats
    finally:
        proxy.stop()
        g1.shutdown()
