"""What every run shares: finding a cell's files by the names in
BENCHMARK.json, the device check, the compile cache and the compile
counter, the profiler window, statistics, and the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """The run cannot produce a result (exit code 1, no result line)."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# cells are data
# ---------------------------------------------------------------------------
def find_file(bench, relative):
    """``relative`` under the first directory of ``paths`` that has it."""
    for base in bench["paths"]:
        path = os.path.join(ROOT, base, relative)
        if os.path.exists(path):
            return path
    raise BenchError("no %s under %s" % (relative, bench["paths"]))


def load_module(bench, relative):
    """Import the file ``relative`` (found by :func:`find_file`) as a
    module of its own; names may hold dots (``kernel.mfu_pct.py``)."""
    path = find_file(bench, relative)
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in relative)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _merged(base, section):
    out = dict(base)
    out.update(base.get(section) or {})
    return out


class Cell:
    """One entry of ``workloads`` with everything its name leads to."""

    def __init__(self, name, rehearse=False, control=False):
        bench = self.bench = load_json(os.path.join(ROOT,
                                                    "BENCHMARK.json"))
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise BenchError("no workload %r in BENCHMARK.json (has: %s)"
                             % (name, ", ".join(w["name"] for w in
                                                bench["workloads"])))
        self.workload = rows[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = [c for c in bench["configs"]
                 if c["name"] == self.workload["config"]][0]
        self.config = load_json(os.path.join(ROOT, entry["file"]))
        self.traffic = load_json(find_file(
            bench, "traffic/%s.json" % self.workload["traffic"]))
        if "mix" in self.traffic:
            # lengths and sharing shared by several arrival patterns
            mix = load_json(find_file(
                bench, "traffic/%s.json" % self.traffic["mix"]))
            toy = dict(mix.get("rehearse") or {},
                       **(self.traffic.get("rehearse") or {}))
            self.traffic = dict(mix, **self.traffic)
            self.traffic["rehearse"] = toy
        if rehearse:
            self.config = _merged(self.config, "rehearse")
            self.traffic = _merged(self.traffic, "rehearse")
        if control:
            # the lower precision a later PR would be tempted by
            self.config = dict(self.config,
                               **dict(self.config["control"]))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        # every per-layer entry lists its cells under "workloads"
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m["workloads"]]

    def module(self, kind, key=None):
        """``<kind>/<name>.py``; the name is the config's ``<kind>`` key
        (so two configurations can share a reference) or ``key``."""
        return load_module(self.bench, "%s/%s.py" % (
            kind, key or self.config.get(kind, self.workload["config"])))

    def driver(self):
        return load_module(self.bench,
                           "drivers/%s.py" % self.traffic["driver"])

    def peaks(self, device_kind):
        table = load_json(find_file(self.bench, "peaks.json"))
        if device_kind not in table["device_kind"]:
            raise BenchError(
                "device_kind %r is not in peaks.json (has: %s); add its "
                "published peaks with their source"
                % (device_kind, ", ".join(sorted(table["device_kind"]))))
        return table["device_kind"][device_kind]


# ---------------------------------------------------------------------------
# device, cache, compile counter
# ---------------------------------------------------------------------------
def devices_for(chips, rehearse):
    """The ``chips`` devices of this run.  A measuring run needs that
    many TPU chips and fails without them; a rehearsal runs on the CPU
    by name and measures nothing."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if rehearse:
        if platform != "cpu":
            raise BenchError("--rehearse runs on the CPU, JAX reports %r"
                             % platform)
    elif platform != "tpu":
        raise BenchError("the benchmark measures on a TPU and JAX "
                         "reports %r; there is no CPU fallback "
                         "(--rehearse is the plumbing run)" % platform)
    if len(devs) < chips:
        raise BenchError("cell needs %d chips, JAX reports %d"
                         % (chips, len(devs)))
    return devs[:chips]


def use_compile_cache():
    """JAX's persistent cache at the program's fixed place inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    kept however fast it compiled: a cell's second run compiles nothing."""
    import jax
    from mxnet_tpu.base import use_compile_cache as place
    path = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compilations (or fetches from the persistent
    cache) through jax.monitoring; ``mark()`` opens the timed window
    and ``in_window`` must stay 0."""

    def __init__(self):
        import jax
        self.total = 0
        self.seconds = 0.0
        self._mark = None
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += 1
            self.seconds += duration

    def mark(self):
        self._mark = self.total

    def freeze(self):
        self.in_window = self.total - self._mark
        return self.in_window

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def memory_peak_bytes(devices):
    """Peak bytes on the fullest chip (None where the backend keeps no
    such statistic, as the CPU's).  On a TPU ``peak_bytes_in_use``
    counts only the buffers the process holds; the scratch of the
    programs it runs shows in ``peak_bytes_reserved`` alone (a ResNet-50
    step: 2.2 GB against 5.5 GB, the compiler's own figure being
    5.4 GB), so the peak is the larger of the two."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        both = [stats.get(k) for k in ("peak_bytes_in_use",
                                       "peak_bytes_reserved")]
        both = [b for b in both if b is not None]
        if both:
            peaks.append(max(both))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the profiler window of a --trace 1 run
# ---------------------------------------------------------------------------
class DeviceTrace:
    """Starts and stops ``jax.profiler`` around a short part of the
    window and reduces the ``.xplane.pb`` it leaves (under TMPDIR).

    The traced window is a SPAN inside the file, not the file:
    ``start()`` opens a host span (``window_span`` of
    ``reduce/trace_names.json``) where it stamps ``t_start`` and
    ``stop()`` closes it where it stamps ``t_stop``, before
    ``stop_trace``; the reducer clips every device event to that span
    and ``window_s`` is its length.  A serving engine goes on ticking
    while its ``bench-trace`` thread sits in ``stop_trace``, so the file
    holds device work from after the stamp, which is not the window's.
    Both calls are made on one thread (a span ends on the thread it
    began on)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t_start = self.t_stop = None
        self._names = load_json(find_file(
            load_json(os.path.join(ROOT, "BENCHMARK.json")),
            "reduce/trace_names.json"))
        self._span = None

    def start(self):
        import jax
        # host spans (TraceAnnotation) yes, the Python call tracer no:
        # it costs the host more than everything else traced
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        # (a TraceAnnotation runs from its construction)
        self._span = jax.profiler.TraceAnnotation(
            self._names["window_span"])
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, bench):
        """The reduced trace (``reduce/xplane.py``), or None when the
        window was never opened; the raw trace is deleted.  Says the
        window's two lengths (the span's, which is ``window_s``, and
        the host stamps') and the busy seconds inside the span, or why
        nothing was read."""
        try:
            if self.t_stop is None:
                return None
            xplane = load_module(bench, "reduce/xplane.py")
            got = xplane.reduce(xplane.find_xplane(self.dir), self._names,
                                window_s=self.t_stop - self.t_start)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if not got["busy_s"]:
            say(trace_unread=got.get(
                "reason", "no device operation inside the window span"))
            return got
        host_s = got["window_host_s"]
        say(trace_window_s=got["window_s"], trace_host_stamps_s=host_s,
            trace_busy_s=got["busy_s"])
        if abs(got["window_s"] - host_s) > 1e-3:
            say(trace_window_disagrees="the window span is %.6f s and "
                "the host stamps are %.6f s apart"
                % (got["window_s"], host_s))
        return got


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------
def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def check(name, value, limit, ok=None):
    """One number compared, beside its limit (printed in every run)."""
    value = float(value)
    if ok is None:
        ok = math.isfinite(value) and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------
REHEARSAL = False       # set by run.py --rehearse: print no time, no rate


def _timed(key):
    return key.endswith(("_s", "_ms", "_per_s", "_s_median"))


def say(**record):
    """An earlier line of standard output (medians, memory, checks).
    A rehearsal on the CPU prints counts only: its clock readings are
    dropped here so that none is ever mistaken for a device number."""
    if REHEARSAL:
        record = {k: v for k, v in record.items() if not _timed(k)}
    print(json.dumps(record, default=float), flush=True)


def finish(cell, devices, out, trace_on, rehearse):
    """Print the checks and then the result line from a driver's
    outcome ``out``; returns the process exit code."""
    say(checks=out["checks"])
    correct = all(c["ok"] for c in out["checks"])
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.get("memory_peak_bytes")}
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {},
              "device": device}
    if rehearse:
        # a plumbing run on the CPU: counts only, no time, no rate
        result["rehearsal"] = {
            "note": "CPU plumbing run at toy size; nothing measured",
            "counters": out.get("counters", {})}
    elif not trace_on:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": float(out["end_to_end"][m["name"]]),
                "unit": m["unit"]}
    trace = out.get("trace")
    if trace_on:
        run = {"cell": cell, "config": cell.config,
               "traffic": cell.traffic, "chips": cell.chips,
               "end_to_end": out["end_to_end"],
               "counters": out.get("counters", {}),
               "host": out.get("host", {}), "trace": trace,
               "peaks": None if rehearse
               else cell.peaks(dev.device_kind)}
        for m in cell.per_layer:
            reader = load_module(cell.bench,
                                 "layer_metrics/%s.py" % m["name"])
            value = reader.read(run)
            if rehearse:
                # the reader ran (plumbing); its value is no measurement
                result["rehearsal"].setdefault("readers_ran", []).append(
                    m["name"])
            elif value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        if trace is not None and trace.get("busy_s") and not rehearse:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {
                "device_ops": trace["device_ops"][:10],
                "idle_gaps": trace["idle_gaps"][:10]}
        result["end_to_end_traced"] = out["end_to_end"] \
            if not rehearse else {}
    print(json.dumps(result), flush=True)
    return 0
