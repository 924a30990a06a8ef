"""``.xplane.pb`` -> device busy time, per-operation totals, collective
exposure and idle gaps named by the host span over each.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.
How planes, lines and operations are named is data
(``trace_names.json``), not code.  All times are seconds.

* the traced window is a SPAN, not the file: the host span named
  ``window_span`` (``bench.window``), which ``harness.DeviceTrace``
  opens where it stamps ``t_start`` and closes where it stamps
  ``t_stop``.  The host plane and the device planes share the
  profiler's clock, and every device event is clipped to that span
  before anything is summed: a serving engine goes on ticking while
  ``stop_trace`` collects, so the file holds device work from after
  the stamp.  ``window_s`` is the span's own length, so
  ``busy_s <= window_s`` by construction;
* busy: the union of the intervals in which an operation ran on a
  device (its ``op_lines``) inside the window, so nested or overlapping
  events count once;
* a module's seconds are clipped like an operation's; an EXECUTION is
  counted where it starts inside the window (the roofline readers
  multiply a dispatch's work by that count);
* collective exposure: the part of the collective operations' intervals
  during which no other operation ran on that device;
* idle gaps: what the merged busy intervals of the first device leave
  of the window, its two edges included (they add up to ``window_s -
  busy_s``), each named by the innermost of the benchmark's own host
  spans (``TraceAnnotation``) that covers its middle.
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


def _nothing(window_s, reason):
    """A trace with nothing to read, and why: no ``busy_s``, so the
    result line carries none (never a fallback to another window)."""
    return {"devices": [], "busy_s": None, "window_s": window_s,
            "device_ops": [], "idle_gaps": [], "reason": reason}


def _device(plane, names, collective_re, lo, hi):
    """One device plane inside the window ``lo .. hi``."""
    totals, coll, rest = {}, [], []
    for name, start, end in _events(plane, names["op_lines"]):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        totals[name] = totals.get(name, 0.0) + (end - start)
        (coll if collective_re.match(name) else rest).append((start, end))
    modules = {}
    for name, start, end in _events(plane, names["module_lines"]):
        secs = min(end, hi) - max(start, lo)
        if secs <= 0:
            continue
        count, was = modules.get(name, (0, 0.0))
        modules[name] = (count + (lo <= start < hi), was + secs)
    busy, coll = merge(coll + rest), merge(coll)
    return {"busy": busy, "busy_s": length(busy), "ops": totals,
            "modules": modules, "collective_s": length(coll),
            "collective_exposed_s": subtract(coll, merge(rest))}


def reduce_profile(profile, names, window_s=None):
    """The reduced trace of a ``ProfileData``.

    The window is the host span ``names["window_span"]``: every device
    event on every device plane is clipped to it, and the ``window_s``
    returned is its length (``window_host_s`` keeps the caller's
    ``window_s``, the host stamps' difference, as the cross-check).
    A caller that gives ``window_s`` traced a window of its own: a
    file of its run without the span reads nothing (``busy_s`` None
    and a ``reason``).  Called with no span and no ``window_s`` (a
    recorded trace) the window is the first device's first operation
    to its last."""
    device_re = re.compile(names["device_plane"])
    host_re = re.compile(names["host_plane"])
    collective_re = re.compile(names["collective_ops"])
    mark, wanted = names["window_span"], set(names["host_spans"])
    planes, spans, marks = [], [], []
    for plane in profile.planes:
        m = device_re.match(plane.name)
        if m:
            planes.append((int(m.group(1)), plane))
        elif host_re.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    into = marks if ev.name == mark else \
                        spans if ev.name in wanted else None
                    if into is not None:
                        into.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns)
                                     * 1e-9))
    if not planes:
        return _nothing(window_s, "no device plane in the trace")
    window_host_s = None
    if marks:
        _, lo, hi = marks[0]
        window_host_s, window_s = window_s, hi - lo
    elif window_s is not None:
        return _nothing(window_s, "no %r span on the host plane: the "
                        "traced window is not marked in the file" % mark)
    else:
        lo, hi = float("-inf"), float("inf")
    devices = [dict(_device(plane, names, collective_re, lo, hi), id=number)
               for number, plane in sorted(planes, key=lambda p: p[0])]
    first = devices[0]
    busy = first["busy"]
    if not marks:
        # a recorded trace: its first operation to its last
        lo, hi = (busy[0][0], busy[-1][1]) if busy else (0.0, 0.0)
        if window_s is None and busy:
            window_s = hi - lo
    gaps = {}
    edges = [lo] + [t for pair in busy for t in pair] + [hi]
    for end, start in zip(edges[::2], edges[1::2]):
        if start <= end:
            continue
        mid = 0.5 * (end + start)
        over = [s for s in spans if s[1] <= mid < s[2]]
        name = min(over, key=lambda s: s[2] - s[1])[0] if over \
            else "no benchmark span"
        gaps[name] = gaps.get(name, 0.0) + (start - end)
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
        "window_host_s": window_host_s,
        "device_ops": [[k, v] for k, v in top[:10]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce(path, names, window_s=None):
    """The reduced trace of the file ``path``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), names, window_s)
