#!/usr/bin/env python3
"""Drives run.py's `run_cell` with the timed path broken underneath: the
sink alters each percentile answer by one part in a thousand where the
server hands it over.  Takes run.py's arguments; used by test_controls.py
with --rehearse, which skips the harness's look for a chip."""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

spec = importlib.util.spec_from_file_location(
    "bench_run", os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def broken_sink():
    from veneur_tpu.sinks.simple import ChannelMetricSink

    class Broken(ChannelMetricSink):
        def flush(self, metrics):
            out = list(metrics)
            for m in out:
                if m.name.endswith("percentile"):
                    m.value = m.value * 1.001
            self.queue.put(out)

    return Broken()


if __name__ == "__main__":
    ap = run.arg_parser()
    args = ap.parse_args()
    rc = 1
    try:
        print(json.dumps(run.run_cell(args, sink_factory=broken_sink)),
              flush=True)
        rc = 0
    except BaseException:      # noqa: BLE001 - report, then leave
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    os._exit(rc)
