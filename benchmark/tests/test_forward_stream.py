"""Generator kind `forward_stream` and the cell `fleet8.northstar`: the
rehearsal end to end, the two controls the comparison must fail, and that
the kind's model, rule and comparison are `forward`'s own (imported, not
copied)."""

import json
import os

from conftest import BENCH, ROOT, load, run_rehearsal

CELL = "fleet8.northstar"


def failed_names(lines):
    return [ln["compared"] for ln in lines if ln.get("ok") is False]


def test_the_cell_names_files_that_exist(bench_json):
    cell = next(w for w in bench_json["workloads"] if w["name"] == CELL)
    config = next(c for c in bench_json["configs"]
                  if c["name"] == cell["config"])
    assert cell["chips"] == 1
    assert config["reduced"] == ["locals", "digests", "interval"]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == config["reduced"]
    assert cfg["server"]["prewarm_depths"] == [256]
    assert {"a_ack_after_import", "b_merged_exactly_once",
            "c_a_bad_message_fails_alone", "d_open_stream_bound",
            "e_unscannable_messages"} <= set(cfg["guarantees"])
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        p = json.load(f)
    assert p["kind"] == "forward_stream"
    # the source's 10,000 messages a second (rules (i) and (ii))
    assert p["locals"] * p["keys_per_local"] / p["interval_s"] == 10_000
    # the mix is ISSUE 42's but for what rule (ii) changes
    assert p["due_share"] == 0.025
    assert p["due_share"] * p["interval_s"] == \
        cfg["assumed"]["forwards_due_after_tick_s"]
    assert p["sender_processes"] == p["locals"] == 8
    for m in bench_json["per_layer"]:
        if CELL in m.get("workloads", []):
            assert os.path.exists(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".json")), m["name"]


def test_model_rule_and_comparison_are_forwards_own():
    gen, fwd = load("loadgen", "forward_stream.py"), load("loadgen",
                                                          "forward.py")
    ref, rfwd = load("reference", "forward_stream.py"), load("reference",
                                                             "forward.py")
    p = {"locals": 2, "keys_per_local": 5, "samples_per_digest": 16,
         "samples_per_centroid": 4, "variants": 2, "sampled_keys": 3}
    a, b = gen.model(9, p, 1), fwd.model(9, p, 1)
    assert all((a[k] == b[k]).all() for k in b)
    assert gen.ledger(p) == fwd.ledger(p) and gen.PREFIX == fwd.PREFIX
    cfg = {"server": {"percentiles": [0.5, 0.99]}}
    assert ref.plan(gen, 9, p, cfg)["wanted"] == rfwd.plan(
        fwd, 9, p, cfg)["wanted"]
    # one source file for both kinds' comparison
    assert ref.compare.__code__.co_filename.endswith(
        os.path.join("reference", "forward.py"))
    assert gen.model.__code__.co_filename.endswith(
        os.path.join("loadgen", "forward.py"))


def test_rehearse_end_to_end(bench_json):
    rc, lines, err = run_rehearsal(CELL, seed=2 ** 31 + 11)
    assert rc == 0, err[-2000:]
    last = lines[-1]
    verdict = [ln for ln in lines if ln.get("info") == "verdict"][0]
    bad = [ln for ln in lines if ln.get("ok") is False or "problem" in ln]
    assert verdict["comparisons_ok"], bad
    assert last["correct"] is False            # a rehearsal never passes
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"forward_p95_ms", "setup_s"}
    ready = [ln for ln in lines if ln.get("info") == "loadgen_ready"][0]
    assert ready["ready"]["sender_processes"] == 8
    reports = [ln for ln in lines if ln.get("info") == "loadgen_interval"]
    assert all(len(r["ack_s"]) == 8 and None not in r["ack_s"]
               for r in reports)


def test_rehearse_traced_reports_the_streams_share(bench_json):
    rc, lines, err = run_rehearsal(CELL, trace=1)
    assert rc == 0, err[-2000:]
    last = lines[-1]
    listed = {m["name"] for m in bench_json["per_layer"]
              if CELL in m.get("workloads", [])}
    on_cpu = {n for n in listed if "kernel" not in n}
    assert on_cpu <= set(last["metrics"]) <= listed
    got = {k: v["value"] for k, v in last["metrics"].items()}
    # one chunk a stream at the rehearsal's 64 messages (fewer a chunk
    # where a loaded machine held a stream past the 0.25 s bound)
    assert 0 < got["import_stream_chunk_msgs"] <= 64
    assert got["import_stream_recv_ms"] > got["import_stream_frame_ms"] > 0
    assert got["import_row_misses.global"] == 0


def test_bf16_staging_fails_the_rule_comparison():
    rc, lines, err = run_rehearsal(
        CELL, "--server-override", "digest_bf16_staging=true")
    assert rc == 0, err[-2000:]
    assert any("span_err_vs_rule" in n for n in failed_names(lines))
    assert not [ln for ln in lines if "problem" in ln]


def test_a_dropped_stream_fails_the_count_and_the_rule():
    rc, lines, err = run_rehearsal(CELL, script="tests/broken_stream.py")
    assert rc == 0, err[-2000:]
    names = failed_names(lines)
    assert "intervals_with_wrong_import_count" in names, names
    # seven locals' centroids where the rule merges eight (the samples'
    # own percentiles move too little at this size to pass their limits)
    assert any("span_err_vs_rule" in n for n in names), names
    # every forward was acked: the loss is the global's, after the ack
    assert "late_or_failed_forwards" not in names
    assert lines[-1]["correct"] is False and lines[-1]["failed"] > 0
