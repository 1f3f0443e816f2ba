#!/usr/bin/env python3
"""Reduce a profiler trace (``*.xplane.pb``) to what the benchmark
reports, and print it as one JSON line.

    python benchmarks/tracereduce.py TRACE_DIR_OR_FILE [SESSION_SECONDS]
    python benchmarks/tracereduce.py TRACE_DIR_OR_FILE --explore

  window_s       the traced stretch: first to last event of any plane
  busy_s         seconds in which an operation ran on the device (union
                 of the intervals on the device's "XLA Ops" line),
                 averaged over the devices that ran anything
  plan_windows   executions of the plan program: the module executions
                 ("XLA Modules" line) that hold an operation under one
                 of the scopes cronsun.fire_mask / compact / fanout /
                 assign
  plan_device_s  union of the operation intervals inside those
  scope_s        device seconds by scope (self time is not separated:
                 a loop holds its body)
  device_ops     [[name [scope], seconds], ...] largest first
  idle_gaps      [[what the host was doing, seconds], ...] longest
                 first: the cronsun.* host annotations that overlap the
                 gap, else the annotations on either side of it

Runs in a process of its own with JAX_PLATFORMS=cpu (the harness never
imports JAX); it only needs ``jax.profiler.ProfileData``.
"""

import json
import os
import re
import sys

SCOPE = re.compile(r"cronsun\.(?:fire_mask|compact|fanout|assign|deps|"
                   r"tenants)")
HOST_MARK = re.compile(r"cronsun\.[a-z_.]+")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = [os.path.join(root, fn) for root, _d, files in os.walk(path)
             for fn in files if fn.endswith(".xplane.pb")]
    if not found:
        raise SystemExit(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def _varint(buf: bytes, i: int):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: enough
    of the wire format to walk XSpace -> XPlane -> event_metadata."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val


def op_scopes(xplane_path: str) -> dict:
    """{operation name: scope}: the profiler keeps an operation's
    ``op_name`` (where the named scope is) in the event METADATA of
    the device plane, which ``ProfileData`` does not hand out — read
    from the file itself.  XSpace.planes=1; XPlane.name=2,
    .event_metadata=4 (map entry: value=2); XEventMetadata.name=2."""
    with open(xplane_path, "rb") as f:
        raw = f.read()
    out = {}
    for num, wt, plane in _fields(raw):
        if num != 1 or wt != 2:
            continue
        fields = list(_fields(plane))
        name = next((v for n, w, v in fields if n == 2 and w == 2), b"")
        if not name.startswith(b"/device:"):
            continue
        for n, w, entry in fields:
            if n != 4 or w != 2:
                continue
            for n2, w2, meta in _fields(entry):
                if n2 != 2 or w2 != 2:
                    continue
                m = SCOPE.search(meta.decode("utf-8", "replace"))
                if m:
                    op = next((v for n3, w3, v in _fields(meta)
                               if n3 == 2 and w3 == 2), b"")
                    out[op.decode("utf-8", "replace")] = m.group(0)
    return out


def short(op: str) -> str:
    """'%fusion.214 = s32[...] fusion(...)' -> '%fusion.214'."""
    return op.split(" = ", 1)[0][:60]


def reduce_trace(path: str, window_s: float = 0.0) -> dict:
    from jax.profiler import ProfileData
    xplane = find_xplane(path)
    scopes = op_scopes(xplane)
    data = ProfileData.from_file(xplane)
    t_min, t_max = float("inf"), 0.0
    devices = {}           # plane name -> {"ops": [...], "modules": [...]}
    marks = []             # (start, end, name) host annotations
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") \
            and "CPU" not in plane.name
        for line in plane.lines:
            for ev in line.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                t_min, t_max = min(t_min, a), max(t_max, b)
                if is_dev and line.name == "XLA Ops":
                    devices.setdefault(plane.name, {"ops": [], "modules": []}
                                       )["ops"].append(
                        (a, b, short(ev.name), scopes.get(ev.name, "")))
                elif is_dev and line.name == "XLA Modules":
                    devices.setdefault(plane.name, {"ops": [], "modules": []}
                                       )["modules"].append((a, b, ev.name))
                elif not is_dev:
                    m = HOST_MARK.search(ev.name)
                    if m:
                        marks.append((a, b, m.group(0)))
    if t_max <= t_min:
        raise SystemExit("the trace holds no event")
    marks.sort()
    busy, plan_s, plan_n = [], [], []
    by_name, by_scope, gaps = {}, {}, []
    for name, d in sorted(devices.items()):
        if not d["ops"]:
            continue
        ivs = union([(a, b) for a, b, _n, _s in d["ops"]])
        busy.append(total(ivs))
        plan_mods = [(a, b) for a, b, _n in d["modules"]
                     if any(s and a <= oa and ob <= b + 1e-9
                            for oa, ob, _on, s in d["ops"])]
        if d["modules"]:
            inside = [(oa, ob) for oa, ob, _on, _s in d["ops"]
                      if any(a <= oa and ob <= b + 1e-9
                             for a, b in plan_mods)]
            plan_s.append(total(union(inside)))
            plan_n.append(len(plan_mods))
        else:
            plan_s.append(total(union([(a, b) for a, b, _n, s in d["ops"]
                                       if s])))
            plan_n.append(sum(1 for _a, _b, n in marks
                              if n == "cronsun.plan.dispatch"))
        for a, b, n, s in d["ops"]:
            key = f"{n} [{s}]" if s else n
            by_name[key] = by_name.get(key, 0.0) + (b - a)
            if s:
                by_scope[s] = by_scope.get(s, 0.0) + (b - a)
        if name == sorted(devices)[0]:
            edges = [[t_min, t_min]] + ivs + [[t_max, t_max]]
            for (_, end), (start, _) in zip(edges, edges[1:]):
                if start - end > 0:
                    gaps.append((start - end, end, start))
    if not busy:
        raise SystemExit("no operation ran on a device in this trace")

    def host_doing(a: float, b: float) -> str:
        over = sorted({n for s, e, n in marks if s < b and e > a})
        if over:
            return " + ".join(over)
        before = [n for s, e, n in marks if e <= a]
        after = [n for s, e, n in marks if s >= b]
        short = lambda n: n.rsplit(".", 1)[-1]  # noqa: E731
        return (f"after {short(before[-1]) if before else 'trace start'}, "
                f"before {short(after[0]) if after else 'trace end'}")

    gaps.sort(reverse=True)
    return {
        # the launcher's own clock around the profiler session where it
        # is given: the first event comes some way into the session
        "window_s": max(window_s, t_max - t_min),
        "busy_s": sum(busy) / len(busy),
        "devices": len(busy),
        "plan_windows": max(plan_n) if plan_n else 0,
        "plan_device_s": sum(plan_s) / len(plan_s) if plan_s else 0.0,
        "scope_s": by_scope,
        "device_ops": [[k, v] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[host_doing(a, b), g] for g, a, b in gaps[:10]],
    }


def explore(path: str):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:3]:
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats "
                      f"{[(k, str(v)[:80]) for k, v in ev.stats][:8]}")


if __name__ == "__main__":
    if "--explore" in sys.argv:
        explore(sys.argv[1])
    else:
        print(json.dumps(reduce_trace(
            sys.argv[1], float(sys.argv[2]) if len(sys.argv) > 2 else 0.0)))
