"""``.xplane.pb`` -> device busy time, per-operation totals, collective
exposure and idle gaps named by the host span over each.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.
How planes, lines and operations are named is data
(``trace_names.json``), not code.  All times are seconds.

* busy: the union of the intervals in which an operation ran on a
  device (its ``op_lines``), so nested or overlapping events count once;
* collective exposure: the part of the collective operations' intervals
  during which no other operation ran on that device;
* idle gaps: what lies between the merged busy intervals of the first
  device, each named by the innermost of the benchmark's own host spans
  (``TraceAnnotation``) that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re


def find_xplane(directory):
    """The newest ``*.xplane.pb`` under ``directory``."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % directory)
    return max(found, key=os.path.getmtime)


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def length(merged):
    return sum(end - start for start, end in merged)


def subtract(merged, holes):
    """Total length of ``merged`` not covered by the merged ``holes``."""
    total, j = 0.0, 0
    for start, end in merged:
        cur = start
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cur:
                total += holes[k][0] - cur
            cur = max(cur, holes[k][1])
            k += 1
        if cur < end:
            total += end - cur
    return total


_HLO = re.compile(r"^%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(")


def kind(name, width=96):
    """A device operation's event name is its whole HLO instruction;
    its kind is the opcode with the result's type and dimensions (no
    layout), so that the sixteen layers' copies of one thing add up."""
    m = _HLO.match(name)
    if not m:
        return name[:width]
    shape = "(tuple)" if m.group(1).startswith("(") \
        else m.group(1).split("{")[0]
    return ("%s %s" % (m.group(2), shape))[:width]


def _events(plane, line_names):
    for line in plane.lines:
        if line.name in line_names:
            for ev in line.events:
                yield ev.name, ev.start_ns * 1e-9, \
                    (ev.start_ns + ev.duration_ns) * 1e-9


def reduce_profile(profile, names, window_s=None):
    """The reduced trace of a ``ProfileData``."""
    device_re = re.compile(names["device_plane"])
    host_re = re.compile(names["host_plane"])
    collective_re = re.compile(names["collective_ops"])
    devices, spans = [], []
    for plane in profile.planes:
        m = device_re.match(plane.name)
        if m:
            ops = list(_events(plane, names["op_lines"]))
            totals, coll, rest = {}, [], []
            for name, start, end in ops:
                totals[name] = totals.get(name, 0.0) + (end - start)
                (coll if collective_re.match(name) else rest).append(
                    (start, end))
            modules = {}
            for name, start, end in _events(plane, names["module_lines"]):
                count, secs = modules.get(name, (0, 0.0))
                modules[name] = (count + 1, secs + end - start)
            busy = merge((s, e) for _, s, e in ops)
            coll = merge(coll)
            devices.append({
                "id": int(m.group(1)), "busy": busy,
                "busy_s": length(busy), "ops": totals,
                "modules": modules, "collective_s": length(coll),
                "collective_exposed_s": subtract(coll, merge(rest))})
        elif host_re.match(plane.name):
            wanted = set(names["host_spans"])
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns)
                                      * 1e-9))
    devices.sort(key=lambda d: d["id"])
    if not devices:
        return {"devices": [], "busy_s": None, "window_s": window_s,
                "device_ops": [], "idle_gaps": []}
    first = devices[0]
    gaps = {}
    for (_, end), (start, _) in zip(first["busy"], first["busy"][1:]):
        mid = 0.5 * (end + start)
        over = [s for s in spans if s[1] <= mid < s[2]]
        name = min(over, key=lambda s: s[2] - s[1])[0] if over \
            else "no benchmark span"
        gaps[name] = gaps.get(name, 0.0) + (start - end)
    if window_s is None and first["busy"]:
        window_s = first["busy"][-1][1] - first["busy"][0][0]
    for d in devices:
        del d["busy"]
    kinds = {}
    for name, secs in first["ops"].items():
        kinds[kind(name)] = kinds.get(kind(name), 0.0) + secs
    top = sorted(kinds.items(), key=lambda kv: -kv[1])
    return {
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "window_s": window_s,
        "device_ops": [[k, v] for k, v in top[:10]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce(path, names, window_s=None):
    """The reduced trace of the file ``path``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), names, window_s)
