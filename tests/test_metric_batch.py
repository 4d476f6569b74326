"""`MetricBatch.materialize` / `MetricSegment.materialize`: the bulk form
gives the records `metric(i)` gives one at a time, whatever the column
holds, and leaves the cyclic collector as it found it — with no young
pass owed for the records it made.
"""

import gc
import queue

import numpy as np
import pytest

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
