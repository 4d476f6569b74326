"""The sketch-family seam (ISSUE 30): an arena cuts its own interval.

Three contracts:

(a) FORMAT — the part `snapshot_part()` returns is a data format that
    arena.py, query/rings.py, retention/timeline.py and the aggregator's
    emitters read back by key.  FROZEN below is that format as the
    commit before the seam wrote it from `_snapshot_and_reset`'s
    hand-written dict literals (keys, dtypes, ranks; every array owns
    its memory — a part never aliases the live arena).
(b) EXTENSION — a family's columns are named once, in `_COLUMNS`; a
    subclass that adds one gets it snapshotted, reset and grown with no
    edit to the aggregator.
(c) ONE LAUNCH — device-assembled (resident) and host-built (staged)
    operands reach the same launch of the unmeshed digest program and
    of the moments program: same guard key, bit-equal outputs, one
    call site.
"""

import ast
import inspect
import textwrap

import numpy as np
import pytest

from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.samplers.metric_key import MetricScope, UDPMetric

F8, I8, OBJ = "float64", "int64", "object"
_IDENT = {"rows": (I8, 1), "names": (OBJ, 1), "tags": (OBJ, 1),
          "scopes": ("int8", 1)}
_STAGED = ("tuple", ((I8, 1), (F8, 1), (F8, 1)))
_DIGEST = {**_IDENT, "kinds": (OBJ, 1), "name_hashes": (I8, 1),
           "staged": _STAGED,
           **{c: (F8, 1) for c in (
               "l_weight", "l_min", "l_max", "l_sum", "l_rsum",
               "d_min", "d_max", "d_rsum", "d_weight", "d_sum")}}
_SCALAR = {**_IDENT, "values": (F8, 1)}
# written down from the parent commit's _snapshot_and_reset (b51f760),
# one entry per (family, deployment); "planes" is the one key that
# moved: it was snap["counter_planes"] beside the part
FROZEN = {
    ("digests", "unmeshed"): {**_DIGEST, "uniform": "bool",
                              "resident": "NoneType"},
    ("digests", "resident"): {**_DIGEST, "uniform": "bool",
                              "resident": "dict"},
    ("moments", "unmeshed"): {**_DIGEST, "uniform": "bool",
                              "resident": "NoneType",
                              "d_logn": (F8, 1), "ivec": (F8, 2),
                              "iv_a": (F8, 1), "iv_b": (F8, 1)},
    ("compactors", "unmeshed"): {**_DIGEST, "cvals": ("float32", 3),
                                 "ccnt": (I8, 2), "ccomps": (I8, 1),
                                 "cclip": (I8, 1)},
    ("sets", "unmeshed"): {**_IDENT, "legacy_ests": "NoneType",
                           "host_regs": ("uint8", 2)},
    ("sets", "resident"): {**_IDENT, "legacy_ests": "NoneType",
                           "lanes": "ArrayImpl"},
    ("sets", "meshed"): {**_IDENT, "legacy_ests": "NoneType",
                         "lanes": "ArrayImpl"},
    ("counters", "unmeshed"): {**_IDENT, "host_totals": (F8, 1),
                               "planes": "function"},
    ("counters", "meshed"): {**_IDENT, "host_totals": "NoneType",
                             "planes": "function"},
    ("gauges", "unmeshed"): _SCALAR,
    ("status", "unmeshed"): {**_SCALAR, "messages": "dict",
                             "hostnames": "dict"},
}


def _udp(name, value, mtype, tags=("a:b",)):
    return UDPMetric(name=name, type=mtype, value=value, sample_rate=1.0,
                     tags=list(tags), joined_tags=",".join(sorted(tags)),
                     scope=MetricScope.MIXED)


def _agg(deployment="unmeshed", **kw):
    if deployment == "meshed":
        from veneur_tpu.parallel.mesh import make_mesh
        kw["mesh"] = make_mesh(4, 2)
    else:
        kw["sketch_family_rules"] = [
            {"match": "mom.*", "family": "moments"},
            {"match": "cmp.*", "family": "compactor"}]
    if deployment == "resident":
        kw.update(flush_resident_arenas=True,
                  resident_device_assembly=True)
    return MetricAggregator(percentiles=[0.5, 0.99], **kw)


def _fill(agg, weights_uniform=True):
    rate = 1.0 if weights_uniform else 0.5
    for i in range(3):
        for v in (1.0, 2.0, 5.0, 9.0):
            for name in (f"h{i}", f"mom.{i}", f"cmp.{i}"):
                m = _udp(name, v, "histogram")
                m.sample_rate = rate
                agg.process_metric(m)
        agg.process_metric(_udp(f"c{i}", 3.0, "counter"))
        agg.process_metric(_udp(f"g{i}", 4.0, "gauge"))
        agg.process_metric(_udp(f"s{i}", "member", "set"))
        agg.process_metric(_udp(f"st{i}", 1.0, "status"))


def _describe(v):
    if isinstance(v, np.ndarray):
        return (str(v.dtype), v.ndim)
    if isinstance(v, tuple):
        return ("tuple", tuple(_describe(x) for x in v))
    return type(v).__name__


def _arrays_of(v):
    if isinstance(v, np.ndarray):
        yield v
    elif isinstance(v, tuple):
        for x in v:
            yield from _arrays_of(x)


# -- (a) the format ---------------------------------------------------------

@pytest.mark.parametrize("family,deployment", sorted(FROZEN))
def test_snapshot_part_format_is_the_parents(family, deployment):
    agg = _agg(deployment)
    _fill(agg)
    ar = getattr(agg, family)
    with agg.lock:
        ar.sync()
        part = ar.snapshot_part()
    try:
        assert len(part["rows"]) == 3
        assert {k: _describe(v) for k, v in part.items()} \
            == FROZEN[family, deployment]
        live = [v for v in vars(ar).values() if isinstance(v, np.ndarray)]
        for key, v in part.items():
            for a in _arrays_of(v):
                assert not any(np.shares_memory(a, col) for col in live), \
                    f"{family} part[{key!r}] aliases live arena state"
    finally:
        if "lanes" in part:
            ar.unpin_lanes(part["lanes"])


def test_aggregator_snapshot_is_the_arenas_parts():
    """_snapshot_and_reset adds nothing to a part and names no column:
    each family's entry is its arena's snapshot_part(), cut in
    _FAMILIES order, and the arenas are reset after the cut."""
    agg = _agg()
    _fill(agg)
    with agg.lock:
        snap = agg._snapshot_and_reset()
    for name in MetricAggregator._FAMILIES:
        want = FROZEN[name, "unmeshed"]
        assert {k: _describe(v) for k, v in snap[name].items()} == want
        assert len(getattr(agg, name).touched_rows()) == 0
    assert set(snap["key_fingerprints"]) == {
        "digest", "moments", "compactor", "set", "counter", "gauge",
        "status"}
    src = inspect.getsource(MetricAggregator._snapshot_and_reset)
    for col, _ in arena_mod.CompactorArena._COLUMNS \
            + arena_mod.MomentsArena._COLUMNS:
        assert col not in src, f"_snapshot_and_reset names column {col}"


# -- (b) a column is named once ---------------------------------------------

@pytest.mark.parametrize("family,base", [
    ("digests", arena_mod.DigestArena),
    ("moments", arena_mod.MomentsArena),
    ("compactors", arena_mod.CompactorArena),
    ("gauges", arena_mod.GaugeArena)])
def test_subclass_column_is_cut_reset_and_grown(family, base):
    class Extended(base):
        _COLUMNS = base._COLUMNS + (("x_peak", -1.0),)

        def __init__(self, **kw):
            super().__init__(**kw)
            self.x_peak = np.full(self.capacity, -1.0)

    agg = _agg()
    ar = Extended(capacity=4)
    setattr(agg, family, ar)
    _fill(agg)
    for i in range(3, 8):       # 8 keys in a 4-row arena: it grows
        agg.process_metric(_udp(
            {"digests": f"h{i}", "moments": f"mom.{i}",
             "compactors": f"cmp.{i}", "gauges": f"g{i}"}[family],
            2.0, "gauge" if family == "gauges" else "histogram"))
    assert ar.capacity >= 8 and len(ar.x_peak) == ar.capacity
    rows = ar.touched_rows()
    assert len(rows) == 8
    ar.x_peak[rows] = np.arange(8.0)
    with agg.lock:
        snap = agg._snapshot_and_reset()
    np.testing.assert_array_equal(snap[family]["x_peak"], np.arange(8.0))
    np.testing.assert_array_equal(ar.x_peak, np.full(ar.capacity, -1.0))


# -- (c) one launch ---------------------------------------------------------

class _RecordingGuard(MetricAggregator._CompileGuard):
    keys: list = []

    def __init__(self, agg, shape):
        super().__init__(agg, shape)
        type(self).keys.append(shape)


def _launches(deployment, uniform, monkeypatch):
    """Flush one filled aggregator; return the guard keys it launched
    under and the fetched digest / moments outputs."""
    agg = _agg(deployment, flush_delta_chunk_keys=1024)
    rng = np.random.default_rng(5)
    for i in range(24):
        for name in (f"h{i}", f"mom.{i}"):
            for v in rng.gamma(2.0, 10.0, 48):
                m = _udp(name, float(v), "histogram")
                m.sample_rate = 1.0 if uniform else 0.5
                agg.process_metric(m)
    if deployment == "resident":
        agg.sync_staged(min_samples=1)
        assert agg.digests._res_bytes > 0 and agg.moments._res_bytes > 0
    calls = []
    real = agg._launch_digests

    def spy(*a):
        calls.append(a)
        return real(*a)

    monkeypatch.setattr(agg, "_launch_digests", spy)
    monkeypatch.setattr(_RecordingGuard, "keys", [])
    monkeypatch.setattr(MetricAggregator, "_CompileGuard", _RecordingGuard)
    pending = agg.flush_dispatch(is_local=False)
    pend = pending._pend
    (tier,) = pend["tiers"]         # no deep key: one operand
    outs = (np.asarray(tier["outs"][0]), np.asarray(pend["moments"]["out"]))
    resident = agg.last_flush_segments.get("resident") == 1.0
    pending.emit()
    assert len(calls) == 1
    return list(_RecordingGuard.keys), outs, resident


@pytest.mark.parametrize("uniform", [True, False],
                         ids=["uniform", "weighted"])
def test_resident_and_staged_operands_reach_one_launch(uniform,
                                                       monkeypatch):
    k_staged, o_staged, r_staged = _launches("unmeshed", uniform,
                                             monkeypatch)
    k_res, o_res, r_res = _launches("resident", uniform, monkeypatch)
    assert r_res and not r_staged      # each arm took its own assembly
    digest_key = ((32, 64), uniform, True)
    moments_key = ("moments", (32, 64), uniform)
    for keys in (k_staged, k_res):
        assert digest_key in keys and moments_key in keys
    assert [k for k in k_staged if k in (digest_key, moments_key)] \
        == [k for k in k_res if k in (digest_key, moments_key)]
    for a, b in zip(o_staged, o_res):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("callee,where,count", [
    ("self._launch_digests", "_dispatch_flush", 1),
    ("self.flush_fn", "_launch_digests", 1),
    ("self.flush_fn", "_launch_meshed", 1),
    ("self.moments_fn", "_dispatch_moments", 1),
    ("self.moments_fn.depth_variant", "_dispatch_moments", 1),
    ("self.compactor_fn", "_dispatch_compactors", 1)])
def test_each_flush_program_has_one_call_site(callee, where, count):
    """Every call of a flush program in the class sits in the one
    method that owns its launch (prewarm lowers, it does not call), and
    a flush reaches that method from one place; the only other caller
    is the boot (prewarm_launch), which launches each program once on
    zeros through the same method."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(MetricAggregator)))
    sites = {}
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef):
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and ast.unparse(node.func) == callee:
                    sites[fn.name] = sites.get(fn.name, 0) + 1
    sites.pop("prewarm_launch", None)
    assert sites == {where: count} or (
        callee == "self.flush_fn"
        and sites == {"_launch_digests": 1, "_launch_meshed": 1})
