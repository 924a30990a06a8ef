"""Profiler: Chrome-trace op timing + native XLA profiling.

Reference: ``src/engine/profiler.{h,cc}`` (per-op OprExecStat → Chrome trace
JSON via DumpProfile) + ``python/mxnet/profiler.py`` control API.

Two layers here:
* the engine-seam profiler — records python-dispatch spans for every op the
  engine facade executes (names match op registry names), dumping the same
  Chrome ``traceEvents`` JSON the reference emits;
* ``jax.profiler`` passthrough (``start``/``stop`` with a logdir) for real
  XLA/TPU traces (the modern equivalent of per-kernel timing).
"""
from __future__ import annotations

import json
import threading
import time

from jax.profiler import TraceAnnotation

from . import engine as _engine
from . import metrics as _metrics
from . import tracing as _tracing
from .analysis.lockcheck import make_lock
from .base import get_env

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "Profiler", "phase", "phase_totals", "mark_step",
           "start_step_profile", "stop_step_profile",
           "aggregate_phase_trace", "PHASES", "StarvedClock",
           "SERVE_PHASES", "GEN_SERVE_PHASES", "FRONTDOOR_PHASES"]

# The per-step wall-time attribution phases of one Module.fit batch
# (tools/step_profile.py renders them; docs/perf.md explains the
# methodology).
PHASES = ("data_wait", "data_next", "h2d_stage", "compute",
          "metric_fetch", "spmd_step", "comm_overlap")

# Phases that overlap (h2d_stage: stager thread concurrent with
# compute) or nest inside (spmd_step: the sharded step-program dispatch
# within compute; data_next: the pipeline consumer seam inside
# data_wait; comm_overlap: the dist_mesh submit→drain window inside
# spmd_step) another phase — reported, but excluded from the
# step-percentage denominator so the breakdown still sums to 100%.
_NON_ADDITIVE_PHASES = frozenset(["h2d_stage", "spmd_step", "data_next",
                                  "comm_overlap"])

# The forward batcher's cycle (serving/scheduler.py) and the front
# door's spans (frontdoor.py, replica_set.py).  What each span brackets,
# the generation engine's spans (serve_tick and what nests in it) and
# who reads which: docs/architecture/observability.md, the inventory.
SERVE_PHASES = ("serve_wait", "serve_batch", "serve_compute")
GEN_SERVE_PHASES = ("serve_tick", "serve_idle", "serve_admit",
                    "serve_prepare", "serve_prefill", "serve_decode",
                    "serve_sample", "serve_resolve", "cow_fork",
                    "device_starved", "device_launch")
FRONTDOOR_PHASES = ("serve_http", "serve_dispatch")


class Profiler:
    def __init__(self, filename="profile.json"):
        self.filename = filename
        self.records = []  # (name, start_ns, end_ns, thread_id, category)
        self._lock = make_lock("profiler.records")
        self._t0 = time.perf_counter_ns()

    def record(self, name, start_ns, end_ns, cat="operator"):
        """Record one span.  ``cat`` tags the dispatch kind: "operator"
        (eager engine seam), "cache_hit" / "compile" (cached-op JIT
        dispatch, cached_op.py), "backward" (tape replay), "rpc_retry" /
        "rpc_reconnect" (dist-kvstore fault-tolerance events,
        kvstore_dist.py — the backoff sleeps and redials taken when a
        parameter server misses its RPC deadline), "kvstore_push" /
        "kvstore_pull" (one wire batch of the async data-plane pipeline,
        kvstore_pipeline.py; coalesced bucket RPCs show their extra key
        count in the name) and "comm_overlap" (one submit->flush window
        of that pipeline — its span against the op spans inside it is
        the visual evidence of compute/comm overlap)."""
        with self._lock:
            self.records.append((name, start_ns, end_ns,
                                 threading.get_ident(), cat))

    def dump(self, filename=None):
        filename = filename or self.filename
        events = []
        for name, start, end, tid, cat in self.records:
            events.append({
                "name": name, "cat": cat, "ph": "B",
                "ts": (start - self._t0) / 1000.0,
                "pid": 0, "tid": tid % 100000})
            events.append({
                "name": name, "cat": cat, "ph": "E",
                "ts": (end - self._t0) / 1000.0,
                "pid": 0, "tid": tid % 100000})
        with open(filename, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return filename


_state = {"profiler": None, "filename": "profile.json", "jax_logdir": None}


# ---------------------------------------------------------------------------
# Step-phase attribution: ``phase()`` is the one way the program records
# a span.  While it is open it is a ``jax.profiler.TraceAnnotation``
# (inside a profiler session the span lies in the ``.xplane.pb`` on the
# working thread's line, on the device trace's clock; with no session
# it is the profiler's own no-op); when it closes it goes to
# * the process-wide, always-on totals behind ``phase_totals()``;
# * a ``StepPhaseCollector`` installed for a window — it only sums and
#   never blocks dispatch (the engine-seam profiler synchronizes every
#   dispatched program), so it can stay on DURING a timed window;
# * the Chrome-trace profiler above (cat="step_phase"), the registry's
#   ``phase_seconds{phase}`` histogram, the traces activated on this
#   thread and the flight ring.
# ---------------------------------------------------------------------------
class StepPhaseCollector:
    """Accumulates per-phase wall time, spans and the counts the spans
    carried."""

    def __init__(self):
        self.totals = {}    # phase -> ns
        self.spans = {}     # phase -> spans
        self.counts = {}    # phase -> {count name: sum}
        self.steps = 0
        self._lock = make_lock("profiler.phase_collector")

    def record(self, name, dur_ns, counts=None):
        with self._lock:
            self.totals[name] = self.totals.get(name, 0) + dur_ns
            self.spans[name] = self.spans.get(name, 0) + 1
            if counts:
                sums = self.counts.setdefault(name, {})
                for key, n in counts.items():
                    sums[key] = sums.get(key, 0) + n

    def mark_step(self):
        with self._lock:
            self.steps += 1

    def snapshot(self):
        """``{phase: {"spans": n, "ns": total, "counts": {key: sum}}}``."""
        with self._lock:
            return {name: {"spans": self.spans[name], "ns": ns,
                           "counts": dict(self.counts.get(name, ()))}
                    for name, ns in self.totals.items()}

    def report(self):
        """Per-step phase breakdown: {phase: {total_ms, mean_ms,
        per_step_ms, pct}} plus step count.  ``pct`` is each phase's
        share of the summed NON-overlapped top-level phases (h2d_stage
        runs on the stager thread concurrently with compute, spmd_step
        nests inside compute — both are excluded from the
        denominator)."""
        with self._lock:
            totals = dict(self.totals)
            spans = dict(self.spans)
            steps = self.steps
        denom = sum(v for k, v in totals.items()
                    if k not in _NON_ADDITIVE_PHASES)
        phases = {}
        for name in sorted(totals, key=lambda n: -totals[n]):
            t = totals[name]
            phases[name] = {
                "total_ms": round(t / 1e6, 3),
                "mean_ms": round(t / 1e6 / max(1, spans[name]), 3),
                "per_step_ms": round(t / 1e6 / max(1, steps), 3),
                "pct": round(100.0 * t / denom, 1) if denom and
                name not in _NON_ADDITIVE_PHASES else None,
                "spans": spans[name],
            }
        return {"steps": steps, "phases": phases,
                "overlapped": sorted(_NON_ADDITIVE_PHASES
                                     & set(totals) | {"h2d_stage"})}


_phase_state = {"collector": None}
_lifetime = StepPhaseCollector()    # never uninstalled


def phase_totals(since=None):
    """Every phase's spans, nanoseconds and summed counts since the
    process started: ``{name: {"spans": n, "ns": total, "counts":
    {key: sum}}}``.  Always on, and kept by the process, not by the
    engine or module that did the work.  ``since`` is an earlier
    reading: what a window gained is ``phase_totals(since=opened)``."""
    now = _lifetime.snapshot()
    for name, was in (since or {}).items():
        got = now[name]
        got["spans"] -= was["spans"]
        got["ns"] -= was["ns"]
        for key, n in was["counts"].items():
            got["counts"][key] -= n
    return now


def start_step_profile():
    """Install a fresh step-phase collector (cheap: a few dict updates
    per fit batch; safe inside timed benchmark windows).  Returns it."""
    col = StepPhaseCollector()
    _phase_state["collector"] = col
    return col


def stop_step_profile():
    """Uninstall the collector and return its ``report()`` (None when
    none was running)."""
    col = _phase_state["collector"]
    _phase_state["collector"] = None
    return col.report() if col is not None else None


def _phase_hist(name):
    """The phase's registry histogram (the metrics plane's aggregate
    view of the same spans: p50/p95/p99 per phase without storing
    samples; metrics.cached_histogram keeps this one dict lookup)."""
    return _metrics.cached_histogram(
        "phase_seconds", help="wall time of one profiler phase span",
        labels={"phase": name})


def _span_ended(name, start_ns, end_ns, counts, requests=True):
    """A closed span to the lifetime totals and to whichever other
    sinks are active (the early-out keeps an unobserved span at a few
    dict/env lookups: hot loops open spans unconditionally).
    ``requests=False``: not to the request traces (a span of the
    device's, not of the requests the thread works for just then)."""
    _lifetime.record(name, end_ns - start_ns, counts)
    col = _phase_state["collector"]
    prof = _state["profiler"]
    mets = _metrics.phase_on()
    if col is None and prof is None and not mets \
            and not _tracing.sinks_active():
        return
    if col is not None:
        col.record(name, end_ns - start_ns, counts)
    if prof is not None:
        prof.record(name, start_ns, end_ns, cat="step_phase")
    if mets:
        _phase_hist(name).observe((end_ns - start_ns) / 1e9)
    if requests:
        _tracing.on_phase(name, start_ns, end_ns)


class StarvedClock:
    """For how long the device had NOTHING QUEUED, by the one thread
    that queues its work and fetches its results (the generation
    engine's loop).  Programs run in order on one stream, so when the
    fetch of dispatch ``k`` returns every dispatch up to ``k`` is
    through: the device is starved from the moment the newest dispatch
    has been fetched (:meth:`fetched`) until the next dispatch call is
    ENTERED (:meth:`launching`).  It can only under-read a trace's
    idle share: the device was through before the fetch returned, and
    starts somewhere inside the dispatch call (PERF.md section 6,
    PR 36).  A call entered with nothing queued is timed too, to its
    return (:meth:`dispatched`): the span ``device_launch``, an upper
    bound of what the launch adds to the device's gap.

    Each interval, when it closes, is a span ``device_starved`` of
    every sink a ``phase()`` reaches but the request traces, and while
    open a ``TraceAnnotation`` on the thread's line of a profiler
    session.  Installed on its thread (:meth:`install`), every
    ``phase()`` that closes there gains the count ``starved_ns``: the
    part of its interval the clock ran.  A wait that is not the
    engine's to shorten (an empty queue) stops the clock:
    :meth:`pause`, :meth:`resume`.

    One thread's: no lock, and no clock read but the one stamp each
    call takes (``now_ns``: a test's hand-made stamp)."""

    __slots__ = ("queued", "through", "since_ns", "closed_ns",
                 "_entered_ns", "_annotation")

    def __init__(self):
        self.queued = 0         # dispatches made
        self.through = 0        # the newest of them known to be through
        self.since_ns = None    # the open interval's start
        self.closed_ns = 0      # what the closed intervals sum to
        self._entered_ns = None     # the launch that closed the last
        self._annotation = None

    def install(self):
        """This thread's clock from here on (a thread has one)."""
        _thread.starved = self
        return self

    def _close(self, now_ns):
        """End the open interval, if one is; returns its end."""
        if self.since_ns is None:
            return None
        if now_ns is None:
            now_ns = time.perf_counter_ns()
        since_ns, self.since_ns = self.since_ns, None
        self.closed_ns += now_ns - since_ns
        self._annotation.__exit__(None, None, None)
        _span_ended("device_starved", since_ns, now_ns, {},
                    requests=False)
        return now_ns

    def _open(self, now_ns):
        if self.through >= self.queued and self.since_ns is None:
            self.since_ns = time.perf_counter_ns() if now_ns is None \
                else now_ns
            # (a TraceMe runs from its construction)
            self._annotation = TraceAnnotation("device_starved")

    def launching(self, now_ns=None):
        """A dispatch call is about to be made: the device's wait for
        the HOST'S other work ends here."""
        self._entered_ns = self._close(now_ns)

    def dispatched(self, now_ns=None):
        """The dispatch call returned: the device has work.  Returns
        the dispatch's number, for the :meth:`fetched` of its result."""
        self.queued += 1
        entered_ns = self._entered_ns
        if entered_ns is None:
            # a caller that told of no launching(): the interval ends
            # here at the latest
            self._close(now_ns)
        else:
            self._entered_ns = None
            _span_ended("device_launch", entered_ns,
                        time.perf_counter_ns() if now_ns is None
                        else now_ns, {}, requests=False)
        return self.queued

    def fetched(self, number=None, now_ns=None):
        """The fetch of dispatch ``number``'s result returned (None:
        the newest's, a fetch that follows its dispatch at once)."""
        if number is None:
            number = self.queued
        if number > self.through:
            self.through = number
        self._open(now_ns)

    def pause(self, now_ns=None):
        """A wait that is the traffic's opens: not starvation."""
        self._close(now_ns)

    def resume(self, now_ns=None):
        """The wait returned; starved again if nothing is in flight."""
        self._open(now_ns)

    def read(self, at_ns):
        """Starved nanoseconds up to the stamp ``at_ns``."""
        if self.since_ns is None:
            return self.closed_ns
        return self.closed_ns + at_ns - self.since_ns


class _PerThread(threading.local):
    # the thread's StarvedClock; a default every thread finds (a miss
    # on a plain threading.local raises inside getattr: 0.5 us)
    starved = None


_thread = _PerThread()


class phase:
    """``with profiler.phase(name, **counts):`` — one span of the
    program.  ``counts`` are what the span worked on (rows, tokens,
    blocks) and are summed in the totals; those known only once the
    work is done are added inside the block with :meth:`add` (they
    reach the totals, not the trace annotation, which is written when
    the span opens).  ``labels`` go to the annotation alone (an
    ordinal: nothing a sum means anything of).  On a thread that has
    a :class:`StarvedClock` the span's close adds ``starved_ns``."""

    __slots__ = ("name", "counts", "_annotation", "_start_ns",
                 "_clock", "_starved_ns")

    def __init__(self, name, labels=None, **counts):
        self.name = name
        self.counts = counts
        self._annotation = TraceAnnotation(name, **(labels or {}), **counts)

    def add(self, **counts):
        for key, n in counts.items():
            self.counts[key] = self.counts.get(key, 0) + n

    def cancel(self):
        """The block turned out not to be this span's work (the
        iterator was at its end, the placement did not fail): its close
        reports to no sink.  Inside a profiler session the annotation
        has been written all the same."""
        self._start_ns = None

    def __enter__(self):
        self._annotation.__enter__()
        self._start_ns = time.perf_counter_ns()
        clock = self._clock = _thread.starved
        if clock is not None:
            # clock.read(start), written out here and below for what
            # the two calls a span cost, of the 1 us a span may cost
            # more than it did (PERF.md section 6, PR 36 has both
            # readings, on the chip's host)
            self._starved_ns = clock.closed_ns if clock.since_ns is None \
                else clock.closed_ns + self._start_ns - clock.since_ns
        return self

    def __exit__(self, *exc):
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        if self._start_ns is not None:
            clock = self._clock
            if clock is not None:
                self.counts["starved_ns"] = (
                    clock.closed_ns if clock.since_ns is None
                    else clock.closed_ns + end_ns - clock.since_ns
                ) - self._starved_ns
            _span_ended(self.name, self._start_ns, end_ns, self.counts)
        return False


def mark_step():
    """Count one completed fit step (phase ``pct`` normalizes by it;
    the registry's ``fit_steps_total`` counts it too)."""
    col = _phase_state["collector"]
    if col is not None:
        col.mark_step()
    if _metrics.phase_on():
        _metrics.counter("fit_steps_total",
                         help="completed Module.fit steps").inc()


def aggregate_phase_trace(filename):
    """Per-step phase breakdown from a dumped Chrome trace: pairs the
    cat="step_phase" B/E events (per name+tid stack) and aggregates
    them exactly like ``StepPhaseCollector.report``."""
    with open(filename) as f:
        trace = json.load(f)
    col = StepPhaseCollector()
    open_spans = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("cat") != "step_phase":
            continue
        key = (ev["name"], ev.get("tid"))
        if ev["ph"] == "B":
            open_spans.setdefault(key, []).append(ev["ts"])
        elif ev["ph"] == "E" and open_spans.get(key):
            t0 = open_spans[key].pop()
            col.record(ev["name"], int((ev["ts"] - t0) * 1000))
            if ev["name"] == "compute":
                col.mark_step()
    return col.report()


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Configure output file (reference MXSetProfilerConfig)."""
    _state["filename"] = filename


def profiler_set_state(state="stop"):
    """'run' installs the engine-seam profiler (and starts a JAX trace when
    MXNET_PROFILER_JAX_LOGDIR is set); 'stop' uninstalls
    (reference MXSetProfilerState)."""
    if state == "run":
        prof = Profiler(_state["filename"])
        _state["profiler"] = prof
        _engine.get()._profiler = prof
        logdir = get_env("MXNET_PROFILER_JAX_LOGDIR")
        if logdir:
            import jax
            jax.profiler.start_trace(logdir)
            _state["jax_logdir"] = logdir
    elif state == "stop":
        _engine.get()._profiler = None
        if _state["jax_logdir"]:
            import jax
            jax.profiler.stop_trace()
            _state["jax_logdir"] = None
    else:
        raise ValueError("state must be 'run' or 'stop'")


def dump_profile():
    """Write the Chrome trace JSON (reference MXDumpProfile)."""
    prof = _state["profiler"]
    if prof is not None:
        return prof.dump()
    return None


if get_env("MXNET_PROFILER_AUTOSTART"):
    profiler_set_state("run")
