"""Generator kind `forward_sets` and its reference, against each other at
the rehearse size; the new readers on hand-made contexts; the cell's
rehearsal.  (`test_readers_absent.py` already walks every metric file,
the new ones with it, over a context of the tree before PR 26.)"""

import json
import os

import numpy as np
import pytest

from conftest import BENCH, load, run_rehearsal
from test_rehearse import KEYS

CELL = "sets50k.union"
SEED = 3200000032


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "forward-sets50k.json")) as f:
        p = json.load(f)
    p.update(p.pop("rehearse"))
    return p


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "global-sets50k.json")) as f:
        return json.load(f)


gen = load("loadgen", "forward_sets.py")
ref = load("reference", "forward_sets.py")


def emitted_by(how, m: dict) -> np.ndarray:
    """What a program would emit for the model's keys: the rounded
    estimate of the union it built."""
    if how == "union":
        est = ref.estimate(ref.union_on_wire(m))
    elif how == "dropped_local":
        est = ref.estimate(ref.union_on_wire(
            {"regs": m["regs"][:1], "dense": m["dense"][:1]}))
    else:                                   # estimates added up
        est = sum(ref.estimate(ref.union_on_wire(
            {"regs": m["regs"][i:i + 1], "dense": m["dense"][i:i + 1]}))
            for i in range(len(m["regs"])))
    return np.floor(est + 0.5)


def run_compare(mix, cfg, how="union", bf16=False):
    pl = ref.plan(gen, SEED, mix, cfg)
    intervals = []
    for iv in range(mix["variants"]):
        keys = np.arange(mix["set_keys"])
        m = gen.model(SEED, mix, iv, keys)
        got = {f"{gen.PREFIX}.s.{k}": v
               for k, v in zip(keys.tolist(), emitted_by(how, m).tolist())}
        intervals.append({"interval": iv, "got": got})
    out = ref.compare(gen, SEED, mix, cfg, pl, intervals, bf16=bf16)
    return {c["name"]: c["value"] <= c["limit"] for c in out}, \
        {c["name"]: c["value"] for c in out}


def test_sizes_are_zipf_and_sum_to_the_members(mix):
    sizes = gen.sizes_by_rank(mix)
    assert sizes.sum() == mix["members_total"]
    assert (np.diff(sizes) <= 0).all() and sizes[-1] >= 1
    full = dict(mix, set_keys=50000, members_total=10_000_000)
    sizes = gen.sizes_by_rank(full)
    assert sizes.sum() == 10_000_000
    assert 870_000 < sizes[0] < 885_000 and sizes[-1] == 17
    a, b = (gen.sizes_by_key(SEED, mix, v) for v in (0, 1))
    assert sorted(a) == sorted(b) and (a != b).any()   # the hot sets move


def test_model_says_what_the_sender_puts_on_the_wire(mix):
    """Registers recovered from the wire (the program's own codec) are
    the model's, clamped where the model says dense."""
    from veneur_tpu.sketches import hll

    keys = np.arange(mix["set_keys"])
    m = gen.model(SEED, mix, 0, keys)
    assert m["dense"].any() and not m["dense"].all()
    # a third of every set is seen by both locals
    both = ((m["regs"][0] != 0) & (m["regs"][1] != 0)).sum()
    assert both > 0
    for loc in range(mix["locals"]):
        for j in range(0, len(keys), 7):
            wire = hll.marshal(m["regs"][loc, j])
            assert (wire[3] == 0) == bool(m["dense"][loc, j])
            want = m["regs"][loc, j]
            if m["dense"][loc, j]:
                want = np.minimum(want, ref.DENSE_RANK_MAX)
            assert np.array_equal(hll.unmarshal(wire), want)


def test_reference_estimate_is_the_programs_formula(mix):
    """Independent of sketches/hll.py, and equal to its numpy twin to
    float32's rounding."""
    from veneur_tpu.sketches import hll

    m = gen.model(SEED, mix, 1, np.arange(mix["set_keys"]))
    union = ref.union_on_wire(m)
    ours = ref.estimate(union)
    theirs = hll.estimate_np_rows(union)
    assert np.all(np.abs(np.floor(ours + 0.5) - theirs)
                  <= np.maximum(1.0, 2e-6 * ours))
    # and within the HLL bound of the truth
    assert np.all(np.abs(ours - m["sizes"])
                  <= np.maximum(5.0, 0.03 * m["sizes"]))


def test_compare_passes_the_union_and_fails_the_faults(mix, cfg):
    ok, values = run_compare(mix, cfg)
    assert all(ok.values()), values
    assert values["estimate_excess_vs_reference"] == 0.0
    for fault in ("dropped_local", "summed"):
        ok, values = run_compare(mix, cfg, fault)
        assert not ok["estimate_excess_vs_reference"], fault
        # about a third off on the sets large enough to show it, and
        # far beyond the HLL rule on every such set
        assert 0.2 < values["estimate_excess_vs_reference"] < 0.5, values
        assert not ok["set_err_over_hll_bound"], fault
        assert not ok["sets_beyond_hll_bound"], fault
    # a missing set is counted, whatever its size
    pl = ref.plan(gen, SEED, mix, cfg)
    m = gen.model(SEED, mix, 0, np.arange(mix["set_keys"]))
    got = {f"{gen.PREFIX}.s.{k}": v for k, v in
           enumerate(emitted_by("union", m).tolist())}
    del got[f"{gen.PREFIX}.s.{int(pl['keys'][0][0])}"]
    out = {c["name"]: c["value"] for c in ref.compare(
        gen, SEED, mix, cfg, pl, [{"interval": 0, "got": got}])}
    assert out["sets_not_emitted"] == 1
    assert out["sampled_metrics_missing"] == 1


def test_bf16_accumulation_fails_the_limit(mix, cfg):
    """The control: the same answers held against a reference whose two
    register sums are accumulated in bfloat16 miss the limit by two
    orders; float64 reads 0."""
    ok, values = run_compare(mix, cfg, bf16=True)
    assert not ok["estimate_excess_vs_reference"]
    lim = cfg["limits"]["estimate_excess"]
    assert values["estimate_excess_vs_reference"] > 20 * lim, values
    assert ok["sets_not_emitted"] and ok["sampled_metrics_missing"]


def test_sampled_keys_hold_the_edges(mix, cfg):
    pl = ref.plan(gen, SEED, mix, cfg)
    assert len(pl["wanted"]) == mix["set_keys"]
    for v, keys in pl["keys"].items():
        by_rank = gen.key_of_rank(SEED, mix, v)
        e = mix["edge_ranks"]
        assert set(by_rank[:e]) | set(by_rank[-e:]) <= set(keys.tolist())
        assert len(keys) == min(mix["sampled_keys"], mix["set_keys"])


# -- the readers on hand-made contexts ----------------------------------------

def trace_ctx(programs, flushes=4, rows=None):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    return {"trace": {"programs": programs,
                      "kernel_ms_per_flush": [1.0] * flushes},
            "window": [{"row": r} for r in (rows or [])],
            "device": {"kind": "TPU v5 lite"}, "peaks": peaks,
            "kernel_bytes_mod": load("kernel_bytes.py")}


def test_program_trace_reader():
    reader = load("readers", "program_trace.py")
    est = {"programs": ["set_estimate_plane"]}
    share = dict(est, hbm_share_of={
        "rows_from_bytes_field": "set_readback_bytes",
        "bytes_per_row_out": 4, "bytes_per_row_in": 16384})
    # nothing to read: no trace, no traced flush, a program without it
    assert reader.read({"trace": None}, **est) is None
    assert reader.read({}, **est) is None
    others = [["jit_flush_body(123)", 4, 2e-3]]
    assert reader.read(trace_ctx(others), **est) is None
    assert reader.read(trace_ctx(others), **share) is None
    mine = others + [["jit_set_estimate_plane(77)", 4, 8e-3],
                     ["jit__set_lane_scatter(5)", 30, 0.3]]
    assert reader.read(trace_ctx(mine, flushes=0), **est) is None
    assert reader.read(trace_ctx(mine), **est) == pytest.approx(2.0)
    assert reader.read(trace_ctx(mine), programs=[
        "set_lane_scatter", "set_reset_mask"]) == pytest.approx(75.0)
    # the share: the row has to say how many rows the program read
    assert reader.read(trace_ctx(mine), **share) is None
    rows = [{"set_readback_bytes": 4 * 65536}] * 4
    got = reader.read(trace_ctx(mine, rows=rows), **share)
    one = 65536 * (16384 + 4)
    assert reader.rows_bytes(65536, 16384, 4) == one
    assert got == pytest.approx(100.0 * (4 * one / 819e9) / 8e-3)
    assert 0 < got <= 100


def test_row_readers_leave_out_what_a_program_lacks():
    row_reader = load("readers", "timeline_row.py")
    ctx = {"window": [{"row": {"import_held_ms": 3.0}}]}
    assert row_reader.read(ctx, field="set_sync_ms") is None    # not 0
    ctx["window"].append({"row": {"set_sync_ms": 7.5,
                                  "import_held_ms": 5.0}})
    assert row_reader.read(ctx, field="set_sync_ms") == 7.5


# -- the rehearsal ------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse_sets50k(bench_json, trace):
    """Walks the cell's control flow on the CPU; the set arena is
    pre-sized small (the deployment's gigabyte of registers makes a CPU
    flush outlast the rehearsal's 2 s interval), still resident."""
    rc, lines, err = run_rehearsal(
        CELL, "--server-override", "set_arena_initial_capacity=2048",
        seed=SEED, trace=trace)
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert KEYS <= set(last) and last.get("probe") is True
    assert last["correct"] is False            # a rehearsal never passes
    verdict = [ln for ln in lines if ln.get("info") == "verdict"][0]
    bad = [ln for ln in lines if ln.get("ok") is False or "problem" in ln]
    assert verdict["comparisons_ok"], bad
    compared = {ln["compared"] for ln in lines if "compared" in ln}
    assert {"estimate_excess_vs_reference", "set_err_over_hll_bound",
            "sets_beyond_hll_bound", "sets_not_emitted",
            "intervals_with_wrong_import_count",
            "import_errors_or_duplicates", "late_or_failed_forwards",
            "compile_events_in_window"} <= compared
    assert last["failed"] == 0 and last["attempted"] > 0
    which = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m for m in bench_json[which]
              if CELL in (m.get("workloads") or [CELL])}
    # what only a device trace can give is left out on the CPU
    on_cpu = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert on_cpu <= set(last["metrics"]) <= set(listed)
    if trace:
        for name in ("set_sync_ms", "import_held_ms.sets50k",
                     "import_rate.sets50k"):
            assert last["metrics"][name]["value"] > 0, name
    else:
        assert set(last["metrics"]) == {"flush_p50_ms", "setup_s"}
