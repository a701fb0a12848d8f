#!/usr/bin/env python
"""Minimal XProf xplane.pb parser: per-op device-time totals without
tensorboard (the installed tensorboard_plugin_profile is incompatible with
this TF's protobuf).  Hand-rolled protobuf wire-format walk over the XSpace
schema (planes=1; XPlane: name=2, lines=3, event_metadata=4; XLine:
name=2, events=4; XEvent: metadata_id=1, duration_ps=3).

Usage:
  python - <<'PY'
  with jax.profiler.trace("/tmp/prof"): ...   # run the jitted fn a few times
  PY
  python scripts/parse_xplane.py /tmp/prof/plugins/profile/*/vm.xplane.pb [topN]

Reading the output: the 'XLA Modules' line gives whole-program device time
per jit call (the trustworthy number — the host's wall clock adds a
dispatch per chained call and swamps sub-ms effects);
'XLA Ops' rows are per-op busy times grouped by op family + output
shape; 'Async XLA Ops' spans overlap compute and must not be summed.
Each line's busy total naively sums event durations — valid for the
serial Modules/Ops lines, an overestimate on any line with overlapping
spans.
"""

import struct, collections, sys, re

def read_varint(buf, i):
    r, s = 0, 0
    while True:
        b = buf[i]; i += 1
        r |= (b & 0x7f) << s
        if not b & 0x80:
            return r, i
        s += 7

def parse_fields(buf):
    i, n = 0, len(buf)
    while i < n:
        key, i = read_varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v, i = read_varint(buf, i)
        elif wt == 2:
            ln, i = read_varint(buf, i)
            v = buf[i:i+ln]; i += ln
        elif wt == 5:
            v = struct.unpack("<I", buf[i:i+4])[0]; i += 4
        elif wt == 1:
            v = struct.unpack("<Q", buf[i:i+8])[0]; i += 8
        else:
            raise ValueError(f"wt {wt}")
        yield fno, wt, v

def iter_tpu_lines(path):
    """Yield (plane_name, line_name, [(op_name, duration_ps), ...]) for every
    line of every TPU plane in the capture.  Multi-chip captures yield one
    group of lines per device plane."""
    data = open(path, "rb").read()
    for fno, wt, plane_buf in parse_fields(data):
        if fno != 1:
            continue
        plane_name, meta, lines = None, {}, []
        for f2, w2, v2 in parse_fields(plane_buf):
            if f2 == 2 and w2 == 2:
                plane_name = v2.decode(errors="replace")
            elif f2 == 4 and w2 == 2:
                k = name = None
                for f3, w3, v3 in parse_fields(v2):
                    if f3 == 1 and w3 == 0: k = v3
                    elif f3 == 2 and w3 == 2:
                        for f4, w4, v4 in parse_fields(v3):
                            if f4 == 2 and w4 == 2:
                                name = v4.decode(errors="replace")
                if k is not None:
                    meta[k] = name
            elif f2 == 3 and w2 == 2:
                lines.append(v2)
        if "TPU" not in (plane_name or ""):
            continue
        for lb in lines:
            line_name = None
            evs = []
            for f3, w3, v3 in parse_fields(lb):
                if f3 == 2 and w3 == 2:
                    try: line_name = v3.decode()
                    except Exception: pass
                if f3 == 4 and w3 == 2:  # XLine.events (probed empirically)
                    try:
                        mid = dur = None
                        for f4, w4, v4 in parse_fields(v3):
                            if f4 == 1 and w4 == 0: mid = v4
                            elif f4 == 3 and w4 == 0: dur = v4
                        if mid is not None and dur is not None and mid in meta:
                            evs.append((meta[mid], dur))
                    except Exception:
                        pass
            if evs:
                yield plane_name, line_name, evs

def xplane_lines(path):
    """Library form: -> {line_name: (n_events, total_ms, fam, full)} where
    ``fam`` maps op-family → ms and ``full`` maps full op name → ms.
    Multi-chip captures are AGGREGATED across device planes (totals are the
    sum over all cores)."""
    out = {}
    for plane_name, line_name, evs in iter_tpu_lines(path):
        n0, t0, fam, full = out.setdefault(
            line_name, (0, 0.0, collections.Counter(), collections.Counter()))
        for name, d in evs:
            m = re.match(r"%?([a-zA-Z_\-]+)", name)
            fam[m.group(1) if m else name] += d / 1e9
            full[name] += d / 1e9
        out[line_name] = (n0 + len(evs),
                          t0 + sum(d for _, d in evs) / 1e9, fam, full)
    return out

def main(path, topn=20):
    for plane_name, line_name, evs in iter_tpu_lines(path):
        total = collections.Counter()
        for name, d in evs:
            # group by op family + dtype/shape
            fam = re.match(r"%?([a-zA-Z_\-]+)", name)
            k2 = fam.group(1) if fam else name
            tm = re.search(r"= ((?:bf16|f32|s32|u32|s8|pred|u8)\[[^\]]*\])", name)
            if tm: k2 += " " + tm.group(1)
            total[k2] += d
        print(f"-- line '{line_name}' on {plane_name}: {len(evs)} events, busy {sum(d for _, d in evs)/1e9:.2f} ms")
        for nm, ps in total.most_common(topn):
            print(f"  {ps/1e9:9.3f} ms  {nm[:95]}")

if __name__ == "__main__":
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    topn = 15
    paths = sys.argv[1:]
    if len(paths) > 1 and paths[-1].isdigit():  # trailing topN after glob paths
        topn = int(paths[-1])
        paths = paths[:-1]
    for _p in paths:
        if len(paths) > 1:
            print(f"==== {_p}")
        main(_p, topn)
