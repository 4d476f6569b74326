#!/usr/bin/env python3
"""Drives run.py's `run_cell` with the stream import broken underneath:
of every eight `SendMetricsV2` streams the global acks one without
importing a message of it — a local whose forward is lost after the ack.
The merge then holds seven locals' centroids where the reference merges
eight: the import count and the rule comparison must say so.
Takes run.py's arguments; used by test_forward_stream.py with --rehearse
and on the chip as `fleet8.northstar`'s second control."""

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


def drop_one_stream_in_eight():
    from veneur_tpu.sources.proxy import GrpcImportServer

    real = GrpcImportServer._import_chunk_task
    dropped = {}        # id(stream) -> is it one of the dropped

    async def dropping(self, st):
        if dropped.setdefault(id(st), len(dropped) % 8 == 0):
            st.pending, st.pending_bytes = [], 0
            return
        await real(self, st)

    GrpcImportServer._import_chunk_task = dropping


if __name__ == "__main__":
    ap = run.arg_parser()
    args = ap.parse_args()
    rc = 1
    try:
        drop_one_stream_in_eight()
        print(json.dumps(run.run_cell(args)), flush=True)
        rc = 0
    except BaseException:      # noqa: BLE001 - report, then leave
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    os._exit(rc)
