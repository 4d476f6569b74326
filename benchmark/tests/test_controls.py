"""The two controls the comparison must fail, at a size a test can hold.

1. The lower-precision control: the program's own bf16 staging path
   (`digest_bf16_staging: true` in the deployment's YAML) must fail one of
   the cell's percentile comparisons.  On the chip, at the cells' own
   sizes, the readings are in PERF.md section 2.
2. The timed path broken underneath (`broken_run.py`: a sink that alters
   the percentile answers where the server hands them over) must come out
   as not correct, with the harness's look for a chip skipped.
"""

import pytest

from conftest import run_rehearsal


def failed_names(lines):
    return [ln["compared"] for ln in lines if ln.get("ok") is False]


@pytest.mark.parametrize("workload,prefix", [
    ("node1.fanout", "span_err_vs_hazen"),
    ("fleet8.steady", "span_err_vs_rule"),
])
def test_bf16_staging_fails_the_percentile_comparison(workload, prefix):
    rc, lines, err = run_rehearsal(
        workload, "--server-override", "digest_bf16_staging=true")
    assert rc == 0, err[-2000:]
    verdict = [ln for ln in lines if ln.get("info") == "verdict"][0]
    assert not verdict["comparisons_ok"]
    assert any(prefix in n for n in failed_names(lines)), failed_names(lines)
    # ... and it is the precision that failed, not the run
    assert not [ln for ln in lines if "problem" in ln]


@pytest.mark.parametrize("workload", ["node1.fanout", "fleet8.steady"])
def test_broken_timed_path_is_not_correct(workload):
    rc, lines, err = run_rehearsal(workload, script="tests/broken_run.py")
    assert rc == 0, err[-2000:]
    verdict = [ln for ln in lines if ln.get("info") == "verdict"][0]
    assert not verdict["comparisons_ok"]
    assert any("span_err" in n for n in failed_names(lines))
    assert lines[-1]["correct"] is False
