"""`MetricBatch.materialize` / `MetricSegment.materialize`: the bulk form
gives the records `metric(i)` gives one at a time, whatever the column
holds, and leaves the cyclic collector as it found it — with no young
pass owed for the records it made.  The records are built in native
code where the host can (`record_builder`), by the interpreter where it
cannot: the same records either way.
"""

import dataclasses
import gc
import queue
import sys

import numpy as np
import pytest

from veneur_tpu.samplers import record_builder
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.sinks.simple import ChannelMetricSink

N = 37
BASES = [f"svc.latency.k{i:03d}" for i in range(N)]
TAGS = [[f"host:h{i % 5}", "env:prod"] for i in range(N)]
SEL = np.array([0, 3, 4, 11, 36], np.int64)


def segment(case: str) -> sm.MetricSegment:
    rng = np.random.default_rng(28)
    if case == "dense_f64":
        return sm.MetricSegment(BASES, TAGS, ".50percentile",
                                rng.gamma(2.0, 10.0, N), sm.GAUGE, 1700000000)
    if case == "dense_f32_no_suffix":
        return sm.MetricSegment(BASES, TAGS, "",
                                rng.gamma(2.0, 10.0, N).astype(np.float32),
                                sm.GAUGE, 1700000000)
    if case == "integer_counts":
        return sm.MetricSegment(BASES, TAGS, ".count",
                                rng.integers(0, 1 << 40, N), sm.COUNTER,
                                1700000000)
    if case == "sparse":
        return sm.MetricSegment(BASES, TAGS, ".max", rng.random(len(SEL)),
                                sm.GAUGE, 1700000000, sel=SEL)
    if case == "sparse_routed":
        return sm.MetricSegment(BASES, TAGS, ".min", rng.random(len(SEL)),
                                sm.GAUGE, 1700000000, sel=SEL,
                                sinks=[{"a"}, set(), {"a", "b"}, None, {"b"}])
    if case == "value_list":
        return sm.MetricSegment(BASES, TAGS, ".sum", [float(i) for i in
                                                      range(N)],
                                sm.GAUGE, 1700000000)
    raise AssertionError(case)


CASES = ["dense_f64", "dense_f32_no_suffix", "integer_counts", "sparse",
         "sparse_routed", "value_list"]


@pytest.mark.parametrize("case", CASES)
def test_segment_materialize_is_metric_by_metric(case):
    seg = segment(case)
    one_by_one = [seg.metric(i) for i in range(len(seg))]
    got = seg.materialize()
    assert got == one_by_one
    assert list(seg) == one_by_one
    for m, ref in zip(got, one_by_one):
        # equal is not enough: an int would encode as "1", not "1.0"
        assert type(m.value) is float and m.value == ref.value
        assert m.tags is ref.tags          # shared, not copied
        assert m.sinks == ref.sinks


@pytest.mark.parametrize("collector_on", [True, False])
def test_batch_materialize_keeps_order_and_collector_state(collector_on):
    batch = sm.MetricBatch()
    for case in CASES:
        batch.add_segment(segment(case))
    batch.append(sm.InterMetric("loose.one", 1700000000, 1.0, [], sm.GAUGE))
    want = [m for case in CASES for m in segment(case)] + batch.loose
    was = gc.isenabled()
    try:
        (gc.enable if collector_on else gc.disable)()
        got = batch.materialize()
        assert gc.isenabled() is collector_on
    finally:
        (gc.enable if was else gc.disable)()
    assert got == want == list(batch)
    assert len(got) == len(batch)


def test_batch_materialize_restores_collector_when_a_column_is_bad():
    batch = sm.MetricBatch()
    batch.add_segment(sm.MetricSegment(BASES, TAGS, ".bad", ["x"] * N,
                                       sm.GAUGE, 1700000000))
    assert gc.isenabled()
    with pytest.raises(ValueError):
        batch.materialize()
    assert gc.isenabled()


def test_batch_materialize_owes_no_young_pass_for_its_records():
    """A batch far beyond the collector's young threshold: lifting the
    pause sets off no pass over the records (they are in the oldest
    generation, spliced there unlooked-at), nothing stays frozen, and
    they die by reference count."""
    n = 20 * gc.get_threshold()[0]
    bases = [f"big.k{i}" for i in range(n)]
    tags = [["env:prod"]] * n
    batch = sm.MetricBatch()
    batch.add_segment(sm.MetricSegment(bases, tags, ".count",
                                       np.arange(n), sm.COUNTER, 1700000000))
    passes = []

    def on_pass(phase, info):
        if phase == "start":
            passes.append(info["generation"])

    assert gc.isenabled()
    gc.collect()
    gc.callbacks.append(on_pass)
    try:
        got = batch.materialize()
        young_owed = gc.get_count()[0]
        handed_on = [got, {"n": len(got)}]      # the caller's next allocations
    finally:
        gc.callbacks.remove(on_pass)
    assert len(handed_on[0]) == n and passes == []
    assert young_owed < gc.get_threshold()[0]
    assert gc.get_freeze_count() == 0
    oldest = {id(o) for o in gc.get_objects(generation=2)}
    assert id(got[0]) in oldest and id(got[-1]) in oldest
    tracked = len(gc.get_objects())
    del got, handed_on
    assert len(gc.get_objects()) <= tracked - n


@pytest.mark.parametrize("columnar", [True, False])
def test_channel_sink_hands_on_records(columnar):
    batch = sm.MetricBatch()
    batch.add_segment(segment("dense_f64"))
    batch.add_segment(segment("sparse"))
    want = list(batch)
    out: queue.Queue = queue.Queue()
    sink = ChannelMetricSink(out=out)
    res = sink.flush(batch if columnar else list(want))
    got = out.get_nowait()
    assert type(got) is list and got == want
    assert res.flushed == len(want)


# -- the native builder against the interpreter's ---------------------------

BIG = 70_000        # crosses record_builder.CHUNK four times


@pytest.fixture(scope="module")
def native():
    """The builder of this host; without a compiler (or the
    interpreter's headers) there is nothing native to compare."""
    try:
        lib = record_builder.load_builder_library()
    except (OSError, RuntimeError) as e:
        pytest.skip(f"no native record builder on this host: {e}")
    return record_builder.RecordBuilder(lib, sm.InterMetric)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(sm, "_builder", None)


def big_segment(suffix=".count", sinks=False) -> sm.MetricSegment:
    bases = [f"big.k{i}" for i in range(BIG)]
    tags = [[f"shard:{i % 7}"] for i in range(BIG)]
    return sm.MetricSegment(
        bases, tags, suffix, np.arange(BIG), sm.COUNTER, 1700000000,
        sinks=[{"a"} if i % 3 else None for i in range(BIG)] if sinks
        else None)


class _Str(str):
    pass


# the builder joins ASCII names itself and leaves every other pair to
# the interpreter's own concatenation
ODD_BASES = ["plain.ascii", "caf\u00e9.latin1", "\u65e5\u672c.bmp",
             "\U0001f600.astral", "", _Str("a.subclass")]


def odd_names(suffix) -> sm.MetricSegment:
    return sm.MetricSegment(ODD_BASES, [[] for _ in ODD_BASES], suffix,
                            np.arange(len(ODD_BASES)), sm.GAUGE, 1700000000)


def native_cases():
    empty = sm.MetricSegment(BASES, TAGS, ".max", np.zeros(0), sm.GAUGE,
                             1700000000, sel=np.zeros(0, np.int64))
    return {**{case: segment(case) for case in CASES},
            "zero_rows": empty, "big": big_segment(),
            "big_routed_no_suffix": big_segment("", sinks=True),
            "odd_names_ascii_suffix": odd_names(".max"),
            "odd_names_latin1_suffix": odd_names(".m\u00e1x"),
            "odd_names_astral_suffix": odd_names(".\U0001f600")}


@pytest.mark.parametrize("case", sorted(native_cases()))
def test_native_records_are_the_interpreters(case, native, monkeypatch):
    seg = native_cases()[case]
    monkeypatch.setattr(sm, "_builder", native)
    got: list = []
    assert seg.extend_records(got) is True
    monkeypatch.setattr(sm, "_builder", None)
    want: list = []
    assert seg.extend_records(want) is False
    assert got == want and len(got) == len(seg)
    for i, (m, ref) in enumerate(zip(got, want)):
        assert type(m) is sm.InterMetric
        assert type(m.name) is str and type(m.value) is float
        assert hash(m.name) == hash(ref.name)
        assert m.name.encode("utf-8") == ref.name.encode("utf-8")
        assert type(m.timestamp) is int and m.type is seg.type
        assert m.tags is ref.tags is seg.tags[seg.row(i)]
        assert m.message == "" and m.hostname == ""
        if seg.sinks is None:
            assert m.sinks is None
        else:
            assert m.sinks is seg.sinks[i]
        if not seg.suffix:
            assert m.name is seg.bases[seg.row(i)]


def test_a_native_record_is_an_ordinary_record(native, monkeypatch):
    """Mutable, routable, comparable, collectable: what the class call
    makes."""
    monkeypatch.setattr(sm, "_builder", native)
    batch = sm.MetricBatch()
    batch.add_segment(segment("dense_f64"))
    batch.append(sm.InterMetric("loose.one", 1700000000, 1.0, [], sm.GAUGE))

    class Rule:
        match, matched, not_matched = "k00", ["a"], ["b"]

    batch.apply_routing([Rule], lambda pat, name, tags: pat in name)
    got = batch.materialize()
    assert batch.built == (N, 0) and len(got) == N + 1
    assert [m.sinks for m in got[:11]] == [{"a"}] * 10 + [{"b"}]
    got[0].sinks = {"c"}
    got[0].hostname = "h"
    assert dataclasses.replace(got[1], value=2.0).value == 2.0
    assert repr(got[1]).startswith("InterMetric(name='svc.latency.k001")
    assert gc.is_tracked(got[0]) == gc.is_tracked(batch.loose[0])


def _counts(shared):
    gc.collect()
    return ([sys.getrefcount(o) for o in shared], len(gc.get_objects()),
            sys.getallocatedblocks())


def _assert_nothing_kept(before, after):
    """Every shared object's reference count is back, and no record and
    no name stayed alive: a leak is an object or a block a row (5,000
    at least a build), the test's own locals a handful."""
    assert after[0] == before[0]
    assert abs(after[1] - before[1]) < 100
    assert abs(after[2] - before[2]) < 1000


def test_native_build_balances_every_reference(native, monkeypatch):
    monkeypatch.setattr(sm, "_builder", native)
    n = 10_000
    bases = [f"ref.k{i}" for i in range(n)]
    row_tags, row_sinks = ["env:prod"], {"a"}
    suffix, typ, ts = ".sum.of.it", "gauge-" + str(n), 1700000000 + n
    shared = [row_tags, row_sinks, suffix, typ, ts, bases[0], sm.InterMetric]

    def build_and_drop():
        batch = sm.MetricBatch()
        for sinks in (None, [row_sinks] * n):
            batch.add_segment(sm.MetricSegment(
                bases, [row_tags] * n, suffix, np.arange(n), typ, ts,
                sinks=sinks))
        batch.add_segment(sm.MetricSegment(
            bases, [row_tags] * n, "", np.arange(n), typ, ts))
        assert len(batch.materialize()) == 3 * n
        assert batch.built == (3 * n, 0)

    build_and_drop()                    # the library's own first-call state
    before = _counts(shared)
    for _ in range(20):
        build_and_drop()
    _assert_nothing_kept(before, _counts(shared))


def test_a_bad_base_raises_and_leaks_nothing(native, monkeypatch):
    monkeypatch.setattr(sm, "_builder", native)
    n = 5_000
    bases = [f"bad.k{i}" for i in range(n)]
    bases[n - 2] = None                 # a row that was never named
    row_tags, suffix = ["env:prod"], ".p99.of.it"
    seg = sm.MetricSegment(bases, [row_tags] * n, suffix, np.arange(n),
                           sm.GAUGE, 1700000000)
    shared = [row_tags, suffix, bases[0], sm.InterMetric]
    before = _counts(shared)
    for _ in range(3):
        out: list = []
        with pytest.raises(TypeError):
            seg.extend_records(out)
        assert out == []
    _assert_nothing_kept(before, _counts(shared))
    # the interpreter refuses the same column
    monkeypatch.setattr(sm, "_builder", None)
    with pytest.raises(TypeError):
        seg.materialize()


def test_columns_that_are_not_lists_take_the_interpreter(native,
                                                         monkeypatch):
    monkeypatch.setattr(sm, "_builder", native)
    tags = np.empty(N, object)
    for i, row_tags in enumerate(TAGS):
        tags[i] = row_tags
    seg = sm.MetricSegment(tuple(BASES), tags, ".max", np.arange(N),
                           sm.GAUGE, 1700000000)
    out: list = []
    assert seg.extend_records(out) is False
    assert out == [seg.metric(i) for i in range(N)]
    # the library itself refuses them too, and a range past the columns
    with pytest.raises(ValueError):
        native._build(sm.InterMetric, native._offsets, tuple(BASES), ".max",
                      1, [1.0] * N, TAGS, sm.GAUGE, None, 0, N)
    with pytest.raises(ValueError):
        native._build(sm.InterMetric, native._offsets, BASES, ".max", 1,
                      [1.0] * N, TAGS, sm.GAUGE, None, 0, N + 1)


def test_an_unloadable_library_leaves_the_interpreters_path(tmp_path,
                                                            monkeypatch):
    """No compiler, no header, a source that does not build: the batch
    builds the same records in Python and says so."""
    broken = tmp_path / "record_builder.cpp"
    broken.write_text("this is not C++;\n")
    monkeypatch.setattr(record_builder, "_SRC", str(broken))
    monkeypatch.setattr(record_builder, "_SO",
                        str(tmp_path / ".build" / "libvnrecords.so"))
    monkeypatch.setattr(record_builder, "_lib", None)
    monkeypatch.setattr(sm, "_builder", sm._UNLOADED)
    batch = sm.MetricBatch()
    for case in CASES:
        batch.add_segment(segment(case))
    got = batch.materialize()
    assert sm._builder is None
    assert batch.built == (0, len(got)) and len(got) == len(batch)
    assert got == [seg.metric(i) for seg in batch.segments
                   for i in range(len(seg))]


def test_the_layout_check_refuses_what_it_cannot_vouch_for(native):
    lib = record_builder.load_builder_library()

    class Sub(sm.InterMetric):
        __slots__ = ()

    @dataclasses.dataclass(slots=True)
    class Nine:
        name: str
        timestamp: int
        value: float
        tags: list
        type: str
        message: str = ""
        hostname: str = ""
        sinks: object = None
        ninth: int = 0

    @dataclasses.dataclass
    class WithDict:
        name: str
        timestamp: int
        value: float
        tags: list
        type: str
        message: str = ""
        hostname: str = ""
        sinks: object = None

    @dataclasses.dataclass(slots=True)
    class Renamed:
        name: str
        timestamp: int
        value: float
        tags: list
        type: str
        message: str = ""
        host: str = ""
        sinks: object = None

    for cls in (Sub, Nine, WithDict, Renamed, dict, 7):
        with pytest.raises(TypeError):
            record_builder.RecordBuilder(lib, cls)
        assert record_builder.load(cls) is None
    # the record's own layout: eight pointers behind the object header,
    # in the dataclass's field order
    assert record_builder.SLOTS == tuple(
        f.name for f in dataclasses.fields(sm.InterMetric))
    # (the interpreter lays slots out by name, not in this order)
    assert sorted(native._offsets) == [16 + 8 * i for i in range(8)]
