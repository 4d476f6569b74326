"""Every metric file survives a program that lacks what it reads.

The driver runs the parent commit's program under the benchmark tree of the
PR it checks: a per-layer metric new in that PR finds no span and no row
field there.  Its reader has to say "nothing to read" (None; the metric is
then left out of the result line), never raise and never report 0 for
"missing".

(i)  every file under layer_metrics/ and end_to_end/, against what the
     readers were handed in a run of the tree BEFORE PR 26
     (data/ctx_before_pr26.<cell>.json, made by dump_ctx.py);
(ii) a rehearsal of this tree, traced, each cell: every per-layer metric
     PR 26 added comes back as a number.
"""

import glob
import json
import numbers
import os

import pytest

from conftest import BENCH, load, run_rehearsal

CELLS = ["node1.fanout", "fleet8.steady"]
# what PR 26 added: spans and row fields the tree before it does not have
NEW_IN_PR26 = {
    "node1.fanout": {"flush_lock_wait_ms", "flush_snapshot_staged_ms",
                     "flush_snapshot_columns_ms", "lane_wait_ms",
                     "lane_filter_ms", "lane_sink_ms", "fold_ns_per_line"},
    "fleet8.steady": {"flush_lock_wait_ms.global", "import_lock_wait_ms",
                      "import_scan_ms", "import_held_ms"},
}
METRIC_FILES = sorted(
    os.path.relpath(p, BENCH)
    for folder in ("layer_metrics", "end_to_end")
    for p in glob.glob(os.path.join(BENCH, folder, "*.json")))


def stored_ctx(cell):
    with open(os.path.join(BENCH, "tests", "data",
                           f"ctx_before_pr26.{cell}.json")) as f:
        ctx = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    ctx.update(metrics_mod=load("metrics.py"),
               kernel_bytes_mod=load("kernel_bytes.py"), peaks=peaks)
    return ctx


def read_metric(path, ctx):
    with open(os.path.join(BENCH, path)) as f:
        spec = json.load(f)
    reader = load("readers", spec["reader"] + ".py")
    return spec["name"], reader.read(ctx, **spec.get("args", {}))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("path", METRIC_FILES)
def test_metric_file_survives_the_tree_before(path, cell):
    name, value = read_metric(path, stored_ctx(cell))
    assert value is None or (isinstance(value, numbers.Real)
                             and not isinstance(value, bool)), (name, value)
    if name in NEW_IN_PR26["node1.fanout"] | NEW_IN_PR26["fleet8.steady"]:
        assert value is None, (name, value)     # absent, not 0


@pytest.mark.parametrize("path", METRIC_FILES)
def test_metric_file_survives_an_empty_run(path):
    """No flush in the window, no trace, no native engine."""
    ctx = {"window": [], "flush_ms": [], "forward_ms": [],
           "loadgen_reports": [], "cpu_seconds": 0.0, "lines_accounted": 0,
           "setup_s": 1.0, "stage_before": None, "stage_after": None,
           "device": {"kind": "cpu"}, "trace": None,
           "metrics_mod": load("metrics.py")}
    _name, value = read_metric(path, ctx)
    assert value is None or isinstance(value, numbers.Real)


def test_no_metric_file_reads_a_stage_the_tree_before_lacks():
    """readers/stage_stats.py indexes its stages: a metric file may only
    name stages and counters that `stage_stats()["totals"]` had before."""
    totals = stored_ctx("node1.fanout")["stage_after"]
    for path in METRIC_FILES:
        with open(os.path.join(BENCH, path)) as f:
            spec = json.load(f)
        if spec["reader"] != "stage_stats":
            continue
        args = spec["args"]
        assert all(s in totals and "ns" in totals[s]
                   for s in args["stages"]), path
        assert args["per"][1] in totals[args["per"][0]], path


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_this_tree_reports_every_new_metric(cell):
    rc, lines, err = run_rehearsal(cell, trace=1)
    assert rc == 0, err[-2000:]
    got = lines[-1]["metrics"]
    for name in NEW_IN_PR26[cell]:
        assert name in got, (name, sorted(got))
        assert isinstance(got[name]["value"], numbers.Real)
        assert got[name]["value"] >= 0
