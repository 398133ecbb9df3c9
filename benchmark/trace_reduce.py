"""From the profiler's .xplane.pb to numbers: device busy time (union of
the op line's event intervals, averaged over the device planes), every
op event with its device duration, the top device ops, and the longest
idle gaps named by what the daemon's own spans say the host was doing.
Reads the file with jax.profiler.ProfileData, which parses protobuf and
initialises no backend."""
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def names() -> dict:
    with open(os.path.join(HERE, "trace_names.json")) as f:
        return json.load(f)


def newest_xplane(profile_dir):
    found = []
    for d, _sub, files in os.walk(profile_dir or ""):
        found += [os.path.join(d, f) for f in files
                  if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def load(path):
    """The trace as ProfileData; `.xz` files (the recorded fixture) are
    decompressed first."""
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        import lzma
        with lzma.open(path) as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def merge(intervals):
    """Union of [start, end) intervals → sorted disjoint list."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(path, window_s=None):
    """→ dict or None when the trace has no device plane with events."""
    nm = names()
    plane_re, line_re = re.compile(nm["device_plane"]), re.compile(
        nm["op_line"])
    data = load(path)
    per_device = []
    op_events = []
    anchor_ns = None
    lo, hi = None, None
    for plane in data.planes:
        is_device = bool(plane_re.search(plane.name))
        for line in plane.lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                lo = a if lo is None else min(lo, a)
                hi = b if hi is None else max(hi, b)
                if not is_device and anchor_ns is None \
                        and ev.name == nm["anchor_event"]:
                    anchor_ns = a
            if is_device and line_re.search(line.name):
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
                per_device.append(merge((a, b) for a, b, _n in evs))
                op_events += [(n, (b - a) / 1e9) for a, b, n in evs]
    if not per_device or not any(per_device):
        return None
    busy = [sum(b - a for a, b in iv) / 1e9 for iv in per_device]
    return {"devices": len(per_device),
            "busy_s": sum(busy) / len(busy),
            "window_s": window_s if window_s else (hi - lo) / 1e9,
            "op_events": op_events,
            "intervals_ns": per_device[0],
            "span_ns": (lo, hi),
            "anchor_ns": anchor_ns}


def top_ops(op_events, n=10):
    total = {}
    for name, dur in op_events:
        name = name.split(" = ")[0]   # the HLO text after it is long
        total[name] = total.get(name, 0.0) + dur
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def label_gap(a_us, b_us, spans, floor):
    """What the daemon was doing in [a, b] (microseconds of its clock):
    the kind of span that covers most of it."""
    cover = {}
    for e in spans:
        s, t = e["ts"], e["ts"] + e.get("dur", 0)
        if t <= a_us or s >= b_us:
            continue
        kind = e["name"]
        if kind == "device_verify":
            unique = (e.get("args") or {}).get("unique", 0)
            kind = "host OpenSSL batch" if unique < floor \
                else "device_verify host side"
        elif kind != "coalesce":
            continue
        cover[kind] = cover.get(kind, 0) + min(t, b_us) - max(s, a_us)
    if not cover or max(cover.values()) < 0.5 * (b_us - a_us):
        return "waiting for frames"
    return max(cover, key=cover.get)


def breakdown(red, spans, bracket, floor=512, n=10):
    """→ {"device_ops": [[name, s]...], "idle_gaps": [[what, s]...]}."""
    out = {"device_ops": top_ops(red["op_events"], n), "idle_gaps": []}
    iv = red["intervals_ns"]
    # gaps under a microsecond are ops of one launch back to back
    gaps = [(iv[i][1], iv[i + 1][0]) for i in range(len(iv) - 1)
            if iv[i + 1][0] - iv[i][1] >= 1000]
    gaps.sort(key=lambda g: g[0] - g[1])
    anchor, start = red.get("anchor_ns"), bracket.get("start")
    for a, b in gaps[:n]:
        what = "unattributed"
        if anchor is not None and start is not None:
            # trace ns → microseconds of the daemon's perf_counter
            a_us, b_us = (start * 1e6 + (t - anchor) / 1e3 for t in (a, b))
            what = label_gap(a_us, b_us, spans, floor)
        out["idle_gaps"].append([what, (b - a) / 1e9])
    return out


def inspect(path, limit=12):
    """Builder: what planes, lines and event names a trace holds."""
    out = []
    for plane in load(path).planes:
        for line in plane.lines:
            names_, n, total = {}, 0, 0.0
            for ev in line.events:
                n += 1
                total += ev.duration_ns
                agg = names_.setdefault(ev.name, [0, 0.0])
                agg[0] += 1
                agg[1] += ev.duration_ns
            top = sorted(names_.items(), key=lambda kv: -kv[1][1])[:limit]
            out.append({"plane": plane.name, "line": line.name,
                        "events": n, "total_ms": total / 1e6,
                        "top": [[k, c, d / 1e6] for k, (c, d) in top]})
    return out


if __name__ == "__main__":
    import sys
    for path in sys.argv[1:]:
        for row in inspect(path):
            print(json.dumps(row))
        red = reduce(path)
        if red:
            print(json.dumps({k: red[k] for k in (
                "devices", "busy_s", "window_s", "anchor_ns", "span_ns")}))
