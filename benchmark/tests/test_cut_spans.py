"""PR 39's per-layer metrics: the columns span's five parts, the sink
call's three, the lane's CPU time and collector passes, the tick's lateness
and tick-to-sink.  All twelve are metric FILES over readers that were
there (`flush_spans`, `timeline_row`).

(i)   each file returns nothing against what the readers were handed in a
      run of the tree BEFORE this PR (data/ctx_before_pr39.<cell>.json,
      made by dump_ctx.py in a checkout of the parent commit with this
      PR's `benchmark/` and `BENCHMARK.json` laid over it): absent, not 0;
(ii)  a traced rehearsal of this tree reports every one as a number, and
      the parts add up to the spans they split;
(iii) every new `per_layer` entry names a file that exists and cells that
      exist.
"""

import json
import numbers
import os

import pytest

from conftest import BENCH, load, run_rehearsal

NEW_IN_PR39 = (
    "flush_columns_cache_ms", "flush_columns_cut_ms",
    "flush_columns_reset_ms", "flush_columns_end_ms",
    "flush_columns_rest_ms", "lane_sink_records_ms", "lane_sink_splice_ms",
    "lane_sink_put_ms", "lane_sink_cpu_ms", "lane_gc_passes",
    "tick_late_ms", "tick_to_sink_ms")
CELLS = ("node1.fanout", "mesh4.steady", "sets50k.union", "zipf.hotset")
# the cells whose context of the parent tree is stored, and what their
# rehearsal needs on the CPU (the full gigabyte of set registers makes a
# CPU flush outlast the rehearsal's interval)
STORED = {"node1.fanout": (),
          "sets50k.union": ("--server-override",
                            "set_arena_initial_capacity=2048")}


def read_metric(name, ctx):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    reader = load("readers", spec["reader"] + ".py")
    return reader.read(ctx, **spec.get("args", {}))


@pytest.mark.parametrize("cell", sorted(STORED))
@pytest.mark.parametrize("name", NEW_IN_PR39)
def test_metric_file_reads_nothing_from_the_tree_before(name, cell):
    with open(os.path.join(BENCH, "tests", "data",
                           f"ctx_before_pr39.{cell}.json")) as f:
        ctx = json.load(f)
    # the stored run did flush, traced: the spans and rows PR 26 added
    # are there, this PR's are not
    assert ctx["window"] and ctx["flush_spans"]
    assert read_metric("flush_snapshot_columns_ms", ctx) is not None
    assert read_metric("lane_sink_ms", ctx) is not None
    assert read_metric(name, ctx) is None


@pytest.fixture(scope="module", params=sorted(STORED))
def traced(request):
    rc, lines, err = run_rehearsal(request.param, *STORED[request.param],
                                   trace=1)
    assert rc == 0, err[-2000:]
    return lines[-1]["metrics"]


@pytest.mark.parametrize("name", NEW_IN_PR39)
def test_rehearsal_of_this_tree_reports_the_metric(traced, name):
    assert name in traced, (name, sorted(traced))
    value = traced[name]["value"]
    assert isinstance(value, numbers.Real) and not isinstance(value, bool)
    assert value >= (-0.001 if name == "flush_columns_rest_ms" else 0)


def test_the_parts_add_up_to_the_spans_they_split(traced):
    """Medians of parts against the median of the whole, a few flushes:
    loose, the exact sum per flush is tests/test_interval_ledger.py's."""
    v = {k: m["value"] for k, m in traced.items()}
    columns = sum(v[f"flush_columns_{p}_ms"]
                  for p in ("cache", "cut", "reset", "end", "rest"))
    assert columns == pytest.approx(v["flush_snapshot_columns_ms"],
                                    rel=0.25, abs=0.05)
    sink = sum(v[f"lane_sink_{p}_ms"] for p in ("records", "splice", "put"))
    assert sink == pytest.approx(v["lane_sink_ms"], rel=0.25, abs=0.05)
    # (a host that counts thread CPU in 10 ms ticks may read one tick over)
    assert v["lane_sink_cpu_ms"] <= v["lane_sink_ms"] + 10.0
    assert v["tick_to_sink_ms"] >= v["tick_late_ms"] >= 0.0


def test_every_new_entry_names_a_file_and_cells_that_exist(bench_json):
    cells = {w["name"] for w in bench_json["workloads"]}
    entries = {m["name"]: m for m in bench_json["per_layer"]}
    # appended, in the issue's order, after what the benchmark had
    assert tuple(m["name"] for m in bench_json["per_layer"]
                 )[-len(NEW_IN_PR39):] == NEW_IN_PR39
    for name in NEW_IN_PR39:
        m = entries[name]
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", f"{name}.json")), name
        assert tuple(m["workloads"]) == CELLS and set(CELLS) <= cells
        assert m["moves"] == "flush_p50_ms" and m["better"] == "lower"
        # each cell listed reports the end-to-end metric it moves
        e2e = [e for e in bench_json["end_to_end"]
               if e["name"] == m["moves"]][0]
        assert set(m["workloads"]) <= set(e2e["workloads"])
