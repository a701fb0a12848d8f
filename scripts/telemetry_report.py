#!/usr/bin/env python
"""Fold telemetry JSONL event streams into the human table.

  python scripts/telemetry_report.py RUN_DIR              # all ranks' files
  python scripts/telemetry_report.py a/events_rank0.jsonl b/events_rank0.jsonl
  python scripts/telemetry_report.py RUN_DIR --json agg.json   # aggregate out
  python scripts/telemetry_report.py RUN_DIR --bench           # metric rows
  python scripts/telemetry_report.py RUN_DIR --trace out.json  # Perfetto

Accepts any mix of run directories (expanded to every events_rank*.jsonl
inside — the multi-host layout) and explicit event files; multiple runs
fold into one aggregate.  Pure host-side JSON folding: no jax import, safe on a
machine with no accelerator.

The table includes a "recovery event" section (loader/bad_record,
train/nan_*, train/preempted, checkpoint/retry — zeros included) so
fault-tolerance triage reads off one block; script/fault_smoke.sh
asserts on it.  Streams from a serving run (serve.py) additionally get
a "serve health" section — requests/batches plus the rejection,
deadline-exceeded, and post-warmup recompile counters, zeros included.
Streams from a fabric router (serve.py --fabric) get a "fabric health"
section on top: membership churn (member_joined / member_evicted /
member_quarantined), circuit-breaker opens, hedges fired/won, retries,
partitions, and rolling reloads, zeros included.  Streams from a model pool
(serve.py --models) get a "model pool" section: weight page-in/out and
cross-model scheduler counters plus the per-model paging variants,
zeros included.

Streams carrying ``pipeline_cell`` meta rows — a live run of ``python -m
mx_rcnn_tpu.train.pipeline``, or its ``--sweep-out`` JSONL passed
directly as a path — get a "pipeline cell" section: one row per sweep cell (fastest
first) with imgs/s and the loader_wait / assembly_wait / dispatch
breakdown, so "which knob moved the needle and where did the time go"
reads off one table (tests/test_pipeline.py).

Streams carrying ``eval_pipeline`` meta rows (any ``pred_eval`` run —
test.py) get an "eval
pipeline" section: one row per eval run with imgs/s, wall time, the
loader / readback / host-post-process wait split and the overlap
fraction (how much host post-process hid under the device forward), so
serial-vs-pipelined-vs-device-postprocess comparisons read off one
table.

Run dirs also expand distributed-trace span streams
(``spans_<member>.jsonl``, a serve.py --trace run): a "tracing" counter
section appears, and ``--trace out.json`` folds the cross-hop spans
into per-member Perfetto process groups with flow arrows linking each
trace id across hops (per-trace forensics: scripts/trace_query.py).

Run dirs also expand watchtower transition logs
(``alerts_<member>.jsonl``, a serve.py --watch run): an "alerts"
section appears — per alertname, how often it went pending / firing /
resolved / silenced and the total time spent firing, cross-member —
so "what paged, how often, for how long" reads off one table
(per-alert forensics: scripts/alert_query.py).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mx_rcnn_tpu.telemetry.report import (aggregate, bench_rows, load_events,
                                          render_table)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("paths", nargs="+",
                    help="run directories and/or events_rank*.jsonl files")
    ap.add_argument("--json", default="",
                    help="also write the aggregated summary JSON here")
    ap.add_argument("--bench", action="store_true",
                    help="print one BENCH-compatible JSON line per rate "
                         "gauge instead of the table")
    ap.add_argument("--trace", default="",
                    help="also fold the events into Chrome/Perfetto "
                         "trace_event JSON here (open in "
                         "https://ui.perfetto.dev)")
    args = ap.parse_args()

    events = load_events(args.paths)
    summary = aggregate(events)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
    if args.trace:
        from mx_rcnn_tpu.telemetry.trace import write_chrome_trace

        n = write_chrome_trace(events, args.trace)
        print(f"wrote {n} trace events to {args.trace}")
    if args.bench:
        for row in bench_rows(summary):
            print(json.dumps(row))
    else:
        print(render_table(summary))


if __name__ == "__main__":
    main()
