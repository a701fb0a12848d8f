"""Fold telemetry JSONL event streams into the aggregated summary, the
human table, and ``{"metric", "value", "unit"}`` rate rows.

Library half of ``scripts/telemetry_report.py`` (importable so tests and
other tools fold without a subprocess).  Input is any mix of event files
and run directories; a directory expands to every ``events_rank*.jsonl``
inside it, so the multi-host case (one file per rank, mirroring the
``profile_dir`` rank-split) folds into ONE cross-rank aggregate — span
totals/counters sum over ranks, gauge extrema span all ranks.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Iterable, List

from mx_rcnn_tpu.telemetry.sink import (SCHEMA_VERSION, Hist,
                                        quantile_from_counts)

# the fault-tolerance subsystem's recovery events (train/resilience.py):
# rendered as their own table section — zeros included — so "did the run
# recover from anything?" is answerable at a glance (and greppable by
# script/fault_smoke.sh) without knowing which counters might exist
RECOVERY_COUNTERS = (
    "loader/bad_record",
    "loader/worker_respawn",
    "train/nan_detected",
    "train/nan_skipped",
    "train/nan_rollback",
    "train/preempted",
    "checkpoint/retry",
)

# the serving subsystem's health counters (serve/engine.py): rendered as
# their own section — zeros included — whenever the stream carries any
# serve/* event, so "did the endpoint shed load, blow deadlines, or
# recompile after warmup?" reads off one block
SERVE_COUNTERS = (
    "serve/requests",
    "serve/images",
    "serve/batches",
    "serve/rejected",
    "serve/shed",
    "serve/deadline_exceeded",
    "serve/recompile",
    "serve/warmup_programs",
)

# the cross-host fabric's membership/routing health (serve/fabric.py):
# rendered as their own section — zeros included — whenever the stream
# carries any fabric/* event, so "did the pool evict anyone, trip a
# breaker, hedge, or declare a partition?" is one block
# (tests/test_fabric.py::test_telemetry_report_fabric_health_section)
FABRIC_COUNTERS = (
    "fabric/requests",
    "fabric/member_joined",
    "fabric/member_evicted",
    "fabric/member_quarantined",
    "fabric/breaker_open",
    "fabric/hedge_fired",
    "fabric/hedge_won",
    "fabric/retry",
    "fabric/retry_ok",
    "fabric/partition",
    "fabric/reload",
    "fabric/reload_rollback",
)

# the data flywheel's loop progress (flywheel/capture.py + miner.py +
# the loader's replay mixing): rendered as their own section — zeros
# included — whenever the stream carries any flywheel/* event, so "did
# traffic actually capture, mine, and replay into training?" is one
# block (tests/test_flywheel.py::test_flywheel_counters_render_as_report_table)
FLYWHEEL_COUNTERS = (
    "flywheel/captured",
    "flywheel/spilled_bytes",
    "flywheel/shards",
    "flywheel/spill_error",
    "flywheel/mined",
    "flywheel/skipped_unlabeled",
    "flywheel/skipped_bad_row",
    "flywheel/replayed",
    "flywheel/train_failed",
    # fleet mode (flywheel/fleet.py): merge/mine fault tolerance and the
    # gated-promotion loop — "did the fleet converge to a promoted
    # generation, and what did chaos cost?" in the same block
    "flywheel/shard_missing",
    "flywheel/manifest_dup_dropped",
    "flywheel/mine_member_failed",
    "flywheel/eval_skipped",
    "flywheel/promotion_gate_pass",
    "flywheel/promotion_gate_reject",
    "flywheel/promoted",
    "flywheel/rejected",
    "flywheel/drift_detected",
)

# the multi-model pool's paging + cross-model scheduling health
# (serve/pool.py): rendered as their own section — zeros included —
# whenever the stream carries any of these, so "did weights page under
# the budget, and did the scheduler actually interleave tenants?" is
# one block; the per-model variants (serve/weight_page_in/<model>, ...)
# render inside the same section
POOL_COUNTERS = (
    "serve/weight_page_in",
    "serve/weight_page_out",
    "serve/sched_batches",
    "serve/sched_switches",
)

# streaming serving's temporal-reuse progress (serve/stream.py + the
# engine's stream-aware flush bookkeeping): rendered as their own
# section — zeros included — whenever the stream carries any stream/*
# event, so "did frames actually skip, and did streams share batches?"
# is one block (tests/test_stream.py::test_telemetry_report_streaming_section)
STREAM_COUNTERS = (
    "stream/frames",
    "stream/forwarded",
    "stream/skipped",
    "stream/delta_dispatches",
    "stream/refreshes",
    "stream/bucket_switches",
    "stream/stale_seq",
    "stream/evicted",
    "stream/batches",
    "stream/batch_frames",
    "stream/coalesced_batches",
)

# distributed request tracing (telemetry/tracectx.py): rendered as its
# own section — zeros included — whenever the stream carries any
# trace/* counter, so "did spans actually emit, and were the slow trees
# tail-kept?" is one block
TRACE_COUNTERS = (
    "trace/spans_emitted",
    "trace/spans_dropped",
    "trace/tail_kept",
)

# cascade serving's routing decisions (serve/pool.py CascadeRouter):
# rendered as their own section — zeros included — whenever the stream
# carries any cascade/* event, so "did the gate actually run, and what
# fraction of traffic escalated?" is one block
CASCADE_COUNTERS = (
    "cascade/answered_small",
    "cascade/escalated",
    "cascade/forced_big",
    "cascade/gate_batches",
    "cascade/escalation_rejected",
)


def event_files(paths: Iterable[str]) -> List[str]:
    """Expand run dirs to their per-rank event files; pass files through.

    Distributed-trace span streams (``spans_<member>.jsonl``,
    telemetry/tracectx.py) fold alongside the per-rank files: same JSONL
    schema, ``kind: "span"`` records whose additive trace fields old
    readers ignore — so ``--trace`` output gains per-member hop tracks
    and the span table counts cross-hop work with zero extra plumbing.
    Watchtower transition logs (``alerts_<member>.jsonl``,
    telemetry/watch.py) fold the same way: ``kind: "alert"`` records
    that old readers ignore, new ones render as the alerts table."""
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            found = sorted(glob.glob(os.path.join(p, "events_rank*.jsonl")))
            found += sorted(glob.glob(os.path.join(p, "spans_*.jsonl")))
            found += sorted(glob.glob(os.path.join(p, "alerts_*.jsonl")))
            if not found:
                raise FileNotFoundError(
                    f"no events_rank*.jsonl, spans_*.jsonl, or "
                    f"alerts_*.jsonl under {p} — was the run started "
                    f"with --telemetry-dir?")
            out.extend(found)
        else:
            out.append(p)
    return out


def load_events(paths: Iterable[str]) -> List[dict]:
    events = []
    for path in event_files(paths):
        with open(path) as f:
            for ln, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError as e:
                    raise ValueError(f"{path}:{ln}: not a JSON object "
                                     f"({e})") from None
                events.append(rec)
    return events


def aggregate(events: Iterable[dict]) -> dict:
    """Events → the ``Telemetry.summary()`` shape, cross-rank.

    The fold is the same math the live sink keeps in memory, so a
    single-rank run folds to byte-identical span/counter/gauge blocks —
    the round-trip the schema test pins.
    """
    spans: dict = {}
    counters: dict = {}
    gauges: dict = {}
    hists: dict = {}
    alerts: dict = {}
    ranks = set()
    meta: dict = {}
    pipeline: list = []
    eval_pipeline: list = []
    programs: list = []
    for e in events:
        kind = e.get("kind")
        name = e.get("name")
        ranks.add(e.get("rank", 0))
        if kind == "span":
            d = float(e["dur_s"])
            n = int(e.get("n", 1))
            s = spans.get(name)
            if s is None:
                spans[name] = [n, d, d, d]
            else:
                s[0] += n
                s[1] += d
                s[2] = min(s[2], d)
                s[3] = max(s[3], d)
        elif kind == "counter":
            counters[name] = counters.get(name, 0) + int(e["inc"])
        elif kind == "gauge":
            v = float(e["value"])
            g = gauges.get(name)
            if g is None:
                gauges[name] = [1, v, v, v, v]
            else:
                g[0] += 1
                g[1] += v
                g[2] = min(g[2], v)
                g[3] = max(g[3], v)
                g[4] = v
        elif kind == "hist":
            h = hists.get(name)
            if h is None:
                h = hists[name] = Hist()
            h.observe(float(e["value"]))
        elif kind == "alert":
            # watchtower lifecycle transitions (telemetry/watch.py
            # alerts_<member>.jsonl): per-alertname tallies + the total
            # time spent firing, cross-member — "what paged, how often,
            # for how long" off one fold
            aname = str(e.get("alert", "?"))
            a = alerts.get(aname)
            if a is None:
                a = alerts[aname] = {
                    "severity": str(e.get("severity", "warning")),
                    "pending": 0, "firing": 0, "resolved": 0,
                    "silenced": 0, "firing_s": 0.0, "members": set()}
            state = str(e.get("state", "?"))
            if state in ("pending", "firing", "resolved"):
                a[state] += 1
            if e.get("silenced"):
                a["silenced"] += 1
            fs = e.get("firing_s")
            if isinstance(fs, (int, float)):
                a["firing_s"] += float(fs)
            if e.get("member") is not None:
                a["members"].add(str(e["member"]))
        elif kind == "meta":
            if name == "run" and not meta:
                meta = dict(e.get("fields", {}))
            elif name == "pipeline_cell":
                # one row per tuning-sweep cell (train/pipeline.py —
                # also the shape its --sweep-out JSONL holds, so that
                # artifact folds here too)
                pipeline.append(dict(e.get("fields", {})))
            elif name == "eval_pipeline":
                # one row per pred_eval run (eval/pipeline.py overlap
                # breakdown: device-busy vs host post-process vs idle)
                eval_pipeline.append(dict(e.get("fields", {})))
            elif name == "compile/program":
                # one row per first-dispatched program (compile/
                # registry.py note_dispatch): kind/shape/dtype/aot — the
                # registry table below distinguishes fused serve_e2e
                # programs from legacy predict/device_prep ones
                programs.append(dict(e.get("fields", {})))
    out_extra = {"pipeline": pipeline} if pipeline else {}
    if eval_pipeline:
        out_extra["eval_pipeline"] = eval_pipeline
    if programs:
        out_extra["programs"] = programs
    if alerts:
        # additive key: a stream with no alert records folds to the
        # exact pre-watchtower summary shape
        out_extra["alerts"] = {
            k: {**{f: v for f, v in a.items() if f != "members"},
                "members": sorted(a["members"])}
            for k, a in sorted(alerts.items())}
    return {
        "schema": SCHEMA_VERSION,
        "ranks": sorted(ranks),
        "meta": meta,
        **out_extra,
        "spans": {k: {"count": c, "total_s": t, "mean_s": t / max(c, 1),
                      "min_s": lo, "max_s": hi}
                  for k, (c, t, lo, hi) in sorted(spans.items())},
        "counters": dict(sorted(counters.items())),
        "gauges": {k: {"count": c, "mean": t / max(c, 1), "min": lo,
                       "max": hi, "last": last}
                   for k, (c, t, lo, hi, last) in sorted(gauges.items())},
        "hists": {k: h.to_dict() for k, h in sorted(hists.items())},
    }


def render_table(summary: dict) -> str:
    """The human view: spans ranked by total time, then counters/gauges."""
    lines = []
    ranks = summary.get("ranks")
    if ranks:
        lines.append(f"ranks: {','.join(str(r) for r in ranks)}")
    spans = summary.get("spans", {})
    if spans:
        lines.append(f"{'span':<34}{'count':>8}{'total_s':>10}"
                     f"{'mean_ms':>10}{'max_ms':>10}")
        for name, s in sorted(spans.items(),
                              key=lambda kv: -kv[1]["total_s"]):
            lines.append(f"{name:<34}{s['count']:>8}{s['total_s']:>10.3f}"
                         f"{s['mean_s'] * 1e3:>10.3f}"
                         f"{s['max_s'] * 1e3:>10.3f}")
    counters = summary.get("counters", {})
    serving = any(k.startswith("serve/") for k in counters) or any(
        k.startswith("serve/") for k in summary.get("spans", {}))
    fabric = any(k.startswith("fabric/") for k in counters) or any(
        k.startswith("fabric/") for k in summary.get("gauges", {}))
    flywheel = any(k.startswith("flywheel/") for k in counters) or any(
        k.startswith("flywheel/") for k in summary.get("gauges", {}))
    streaming = any(k.startswith("stream/") for k in counters) or any(
        k.startswith("stream/") for k in summary.get("gauges", {}))
    pool = any(k in POOL_COUNTERS or k.startswith("serve/weight_page")
               or k.startswith("serve/sched_") for k in counters)
    tracing = any(k.startswith("trace/") for k in counters)
    cascading = any(k.startswith("cascade/") for k in counters) or any(
        k.startswith("cascade/") for k in summary.get("gauges", {}))
    pool_extra = sorted(
        n for n in counters if n not in POOL_COUNTERS
        and (n.startswith("serve/weight_page_in/")
             or n.startswith("serve/weight_page_out/")))
    if counters:
        lines.append("")
        lines.append(f"{'counter':<34}{'total':>8}")
        # dtype-labeled recompile counters (serve/recompile/bfloat16, ...)
        # and the program registry's AOT split render inside the serve
        # health block, not the general section
        serve_extra = sorted(
            n for n in counters
            if n.startswith("serve/recompile/") or n.startswith("compile/"))
        for name, v in counters.items():
            if name in RECOVERY_COUNTERS:
                continue  # recovery events get their own section below
            if serving and (name in SERVE_COUNTERS or name in serve_extra):
                continue  # ditto serve health
            if fabric and name in FABRIC_COUNTERS:
                continue  # ditto fabric health
            if flywheel and name in FLYWHEEL_COUNTERS:
                continue  # ditto the flywheel table
            if streaming and name in STREAM_COUNTERS:
                continue  # ditto the streaming table
            if pool and (name in POOL_COUNTERS or name in pool_extra):
                continue  # ditto the model-pool table
            if tracing and name in TRACE_COUNTERS:
                continue  # ditto the tracing table
            if cascading and name in CASCADE_COUNTERS:
                continue  # ditto the cascade table
            lines.append(f"{name:<34}{v:>8}")
        lines.append("")
        lines.append(f"{'recovery event':<34}{'total':>8}")
        for name in RECOVERY_COUNTERS:
            lines.append(f"{name:<34}{counters.get(name, 0):>8}")
        if serving:
            lines.append("")
            lines.append(f"{'serve health':<34}{'total':>8}")
            for name in SERVE_COUNTERS:
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
            for name in serve_extra:  # per-dtype recompiles + AOT split
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
        if fabric:
            lines.append("")
            lines.append(f"{'fabric health':<34}{'total':>8}")
            for name in FABRIC_COUNTERS:
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
        if flywheel:
            lines.append("")
            lines.append(f"{'flywheel':<34}{'total':>8}")
            for name in FLYWHEEL_COUNTERS:
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
        if streaming:
            lines.append("")
            lines.append(f"{'streaming':<34}{'total':>8}")
            for name in STREAM_COUNTERS:
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
        if pool:
            lines.append("")
            lines.append(f"{'model pool':<34}{'total':>8}")
            for name in POOL_COUNTERS:
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
            for name in pool_extra:  # per-model paging counters
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
        if tracing:
            lines.append("")
            lines.append(f"{'tracing':<34}{'total':>8}")
            for name in TRACE_COUNTERS:
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
        if cascading:
            lines.append("")
            lines.append(f"{'cascade':<34}{'total':>8}")
            for name in CASCADE_COUNTERS:
                lines.append(f"{name:<34}{counters.get(name, 0):>8}")
    gauges = summary.get("gauges", {})
    if gauges:
        lines.append("")
        lines.append(f"{'gauge':<34}{'count':>8}{'mean':>10}{'min':>10}"
                     f"{'max':>10}{'last':>10}")
        for name, g in gauges.items():
            lines.append(f"{name:<34}{g['count']:>8}{g['mean']:>10.3f}"
                         f"{g['min']:>10.3f}{g['max']:>10.3f}"
                         f"{g['last']:>10.3f}")
    pipeline = summary.get("pipeline", [])
    if pipeline:
        # tuning-sweep cells, fastest first (train/pipeline.py): the full
        # wait breakdown per cell, so "which knob moved the needle and
        # where did the time go" is one block
        lines.append("")
        lines.append(f"{'pipeline cell':<18}{'imgs/s':>10}{'loader_s':>10}"
                     f"{'assembly_s':>11}{'dispatch_s':>11}{'wait%':>8}")
        for row in sorted(pipeline,
                          key=lambda r: -(r.get("imgs_per_sec") or 0.0)):
            lines.append(
                f"{row.get('cell', '?'):<18}"
                f"{row.get('imgs_per_sec') or 0.0:>10.3f}"
                f"{row.get('loader_wait_s') or 0.0:>10.3f}"
                f"{row.get('assembly_wait_s') or 0.0:>11.3f}"
                f"{row.get('dispatch_s') or 0.0:>11.3f}"
                f"{100 * (row.get('loader_wait_frac') or 0.0):>7.1f}%")
    eval_pipeline = summary.get("eval_pipeline", [])
    if eval_pipeline:
        # one row per pred_eval run: how much host post-process time hid
        # under the device forward (overlap%), and where the main thread
        # actually waited (loader / readback / host tail)
        lines.append("")
        lines.append(f"{'eval pipeline':<20}{'imgs/s':>10}{'wall_s':>9}"
                     f"{'loader_s':>10}{'readbk_s':>10}{'post_s':>9}"
                     f"{'overlap%':>9}")
        for row in sorted(eval_pipeline,
                          key=lambda r: -(r.get("imgs_per_sec") or 0.0)):
            lines.append(
                f"{row.get('mode', '?'):<20}"
                f"{row.get('imgs_per_sec') or 0.0:>10.3f}"
                f"{row.get('wall_s') or 0.0:>9.2f}"
                f"{row.get('loader_wait_s') or 0.0:>10.3f}"
                f"{row.get('readback_wait_s') or 0.0:>10.3f}"
                f"{row.get('host_post_s') or 0.0:>9.3f}"
                f"{100 * (row.get('overlap_frac') or 0.0):>8.1f}%")
    programs = summary.get("programs", [])
    if programs:
        # the program registry table, grouped by (kind, dtype): how many
        # distinct executables each program family first-dispatched and
        # how many of them warm-started from the AOT cache — serve_e2e
        # (fused) vs predict/predict_wf (legacy) vs device_prep read off
        # separate rows
        groups: dict = {}
        for row in programs:
            key = (str(row.get("kind", "?")), str(row.get("dtype", "?")))
            g = groups.setdefault(key, [0, 0])
            g[0] += 1
            if row.get("aot") == "hit":
                g[1] += 1
        lines.append("")
        lines.append(f"{'program kind':<24}{'dtype':<16}{'programs':>9}"
                     f"{'aot_hit':>9}")
        for (kind, dtype), (n, hits) in sorted(groups.items()):
            lines.append(f"{kind:<24}{dtype:<16}{n:>9}{hits:>9}")
    hists = summary.get("hists", {})
    if hists:
        lines.append("")
        lines.append(f"{'latency':<34}{'count':>8}{'mean_ms':>10}"
                     f"{'p50_ms':>10}{'p99_ms':>10}")
        for name, h in hists.items():
            n = h.get("count", 0)
            le, buckets = h.get("le", []), h.get("buckets", [])
            p50 = quantile_from_counts(le, buckets, n, 0.50)
            p99 = quantile_from_counts(le, buckets, n, 0.99)
            mean = h.get("sum", 0.0) / max(n, 1)
            lines.append(f"{name:<34}{n:>8}{mean * 1e3:>10.3f}"
                         f"{(p50 or 0.0) * 1e3:>10.3f}"
                         f"{(p99 or 0.0) * 1e3:>10.3f}")
    alerts = summary.get("alerts", {})
    if alerts:
        # the watchtower's lifecycle, folded: how often each alert went
        # pending/firing/resolved and the total firing time — zero-firing
        # rows still render so "nothing fired" is a visible fact
        lines.append("")
        lines.append(f"{'alert':<28}{'severity':<10}{'pending':>8}"
                     f"{'firing':>8}{'resolved':>9}{'silenced':>9}"
                     f"{'firing_s':>10}")
        for name, a in sorted(alerts.items()):
            lines.append(f"{name:<28}{a.get('severity', '?'):<10}"
                         f"{a.get('pending', 0):>8}"
                         f"{a.get('firing', 0):>8}"
                         f"{a.get('resolved', 0):>9}"
                         f"{a.get('silenced', 0):>9}"
                         f"{a.get('firing_s', 0.0):>10.2f}")
    return "\n".join(lines)


def bench_rows(summary: dict) -> List[dict]:
    """Rate gauges → ``{"metric", "value", "unit"}`` rows.  A rate gauge
    is one whose name contains ``imgs_per_sec`` (the Speedometer feed,
    pred_eval's rate).  The rows' readers — the CPU gate and its smokes —
    were deleted in PR 30 (ROADMAP D13)."""
    rows = []
    for name, g in summary.get("gauges", {}).items():
        if "imgs_per_sec" in name:
            rows.append({"metric": name.replace("/", "_"),
                         "value": round(g["mean"], 3),
                         "unit": "imgs/sec",
                         "samples": g["count"]})
    return rows
