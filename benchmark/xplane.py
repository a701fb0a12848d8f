"""From a profiler trace (``*.xplane.pb``) to what the metrics need: the
union of the intervals in which an operation ran on each device, the idle
gaps and what the host was doing in them, and the time of each program and
each op family.  Read with ``jax.profiler.ProfileData`` alone.

The reduction works on plain tuples ``(name, start_ns, duration_ns)`` so
that it can be checked on a small recorded trace (``testdata/``) and on
hand-made events.  The window is the span between the two markers the
harness writes into the trace (``MARK_BEGIN`` / ``MARK_END`` — host events
on the trace's own clock); a trace without them is read over the extent of
its device events.
"""

from __future__ import annotations

import glob
import os
import re

MARK_BEGIN, MARK_END = "bench_window_begin", "bench_window_end"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# host events that say nothing about what the host was doing
_DULL = re.compile(r"^(\$|ThreadpoolListener|bench_window_)")


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, duration_ns), ...]}}"""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
        out[plane.name] = lines
    return out


def device_planes(planes: dict) -> list:
    """Names of the planes that are chips' compute cores, in order."""
    names = [n for n in planes
             if n.startswith("/device:TPU:") and "Sparse" not in n
             and OPS_LINE in planes[n]]
    return sorted(names, key=lambda n: int(re.sub(r"\D", "", n) or 0))


def window_of(planes: dict):
    """(begin_ns, end_ns) from the harness's markers, or None."""
    begin = end = None
    for name, lines in planes.items():
        if not name.startswith("/host:"):
            continue
        for events in lines.values():
            for ename, start, _ in events:
                if ename == MARK_BEGIN:
                    begin = start if begin is None else min(begin, start)
                elif ename == MARK_END:
                    end = start if end is None else max(end, start)
    return (begin, end) if begin is not None and end is not None else None


def union(intervals: list) -> list:
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events: list, lo: float, hi: float) -> list:
    """[(name, start, end)] of the parts of events inside [lo, hi]."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def short(text: str) -> str:
    """An op event's text is its whole HLO instruction; keep the name, and
    say where it is a custom call (a Pallas / Mosaic kernel is one)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return name + (" custom-call" if " custom-call(" in text else "")


def family(name: str) -> str:
    """An op's family: its name without the trailing numbering, so that
    ``fusion.123`` and ``fusion.7`` add up (the grouping idea of
    ``scripts/parse_xplane.py``)."""
    return re.sub(r"[._]\d+( custom-call)?$", r"\1", name.lstrip("%"))


def host_label(planes: dict, lo: float, hi: float) -> str:
    """What the host was doing in [lo, hi]: the host event (python frames
    and dull bookkeeping left out) that covers most of it."""
    best, best_cover = "unattributed", 0.0
    for pname, lines in planes.items():
        if not pname.startswith("/host:"):
            continue
        for lname, events in lines.items():
            if lname == "python":
                continue
            for name, start, dur in events:
                if _DULL.match(name):
                    continue
                cover = min(start + dur, hi) - max(start, lo)
                if cover > best_cover:
                    best, best_cover = name, cover
    return best if best_cover >= 0.2 * (hi - lo) else "unattributed"


def reduce(planes: dict, chips: int = 1, host_window_s: float | None = None,
           top: int = 10) -> dict:
    """The trace's numbers.  Seconds throughout.

    busy_s: union of op intervals per device, averaged over the chips used;
    busy_worst_s: on the least busy device (its idle share is the worst);
    modules: {program name: [durations]} on the first device;
    op_time: {op name: (total seconds, count)} on the first device;
    device_ops / idle_gaps: the breakdown's two lists."""
    devs = device_planes(planes)[:chips]
    if not devs:
        raise RuntimeError(f"no TPU plane with an {OPS_LINE!r} line in the "
                           f"trace; planes: {sorted(planes)}")
    win = window_of(planes)
    if win is None:
        starts = [s for d in devs for _, s, _ in planes[d][OPS_LINE]]
        ends = [s + u for d in devs for _, s, u in planes[d][OPS_LINE]]
        win = (min(starts), max(ends))
    lo, hi = win
    window_s = (hi - lo) / 1e9
    busy_iv = [union([(s, e) for _, s, e in clip(planes[d][OPS_LINE], lo, hi)])
               for d in devs]
    busy = [sum(e - s for s, e in iv) / 1e9 for iv in busy_iv]
    first = devs[0]
    op_time: dict = {}
    fam_time: dict = {}
    for text, s, e in clip(planes[first][OPS_LINE], lo, hi):
        name = short(text)
        t, n = op_time.get(name, (0.0, 0))
        op_time[name] = (t + (e - s) / 1e9, n + 1)
        fam_time[family(name)] = fam_time.get(family(name), 0.0) + (e - s) / 1e9
    modules: dict = {}
    for name, start, dur in planes[first].get(MODULES_LINE, []):
        if start >= lo and start + dur <= hi:    # whole executions only
            modules.setdefault(name, []).append(dur / 1e9)
    edges = [lo] + [t for pair in busy_iv[0] for t in pair] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:top]
    labelled: dict = {}
    for length, a, b in gaps:
        lab = host_label(planes, a, b)
        labelled[lab] = labelled.get(lab, 0.0) + length / 1e9
    return {
        "window_s": window_s, "host_window_s": host_window_s,
        "busy_s": sum(busy) / len(busy), "busy_worst_s": min(busy),
        "devices": devs, "modules": modules, "op_time": op_time,
        "device_ops": [[k, v] for k, v in sorted(
            fam_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            labelled.items(), key=lambda kv: -kv[1])[:top]],
    }


def module_times(red: dict, pattern: str) -> list:
    """Durations of the executions of the programs whose name matches."""
    rx = re.compile(pattern)
    return [d for name, ds in red["modules"].items() if rx.search(name)
            for d in ds]


def op_seconds(red: dict, pattern: str) -> tuple:
    """(total seconds, executions) of the ops whose name matches."""
    rx = re.compile(pattern)
    hits = [v for name, v in red["op_time"].items() if rx.search(name)]
    return sum(t for t, _ in hits), sum(n for _, n in hits)
