"""Per-request distributed tracing + the crash flight recorder.

**Tracing.**  A request entering the serving plane — at the HTTP front
door, or at ``submit()`` for in-process callers — mints a
:class:`Trace` (a process-unique ``trace_id`` plus a root span).  The
trace rides the request object across every thread handoff (balancer
dispatch, scheduler queue, engine admit, prefill/decode steps,
completer resolution), and whichever thread is currently working on
the request *activates* it (:func:`activate` / :func:`activate_many`
for a batch).  The existing step-phase seam
(``profiler.phase``) forwards every span to :func:`on_phase`,
so the ``serve_http`` / ``serve_dispatch`` / ``serve_batch`` /
``serve_compute`` / ``serve_prefill`` / ``serve_decode`` /
``serve_sample`` phases become *children of one trace* instead of
anonymous process-wide events — no per-site changes, the propagation
IS the activation discipline.

Sampling: ``MXNET_TRACE_SAMPLE`` (rate in [0, 1], default 1) decides
per trace — deterministically from (``MXNET_TRACE_SEED``, mint
sequence), so a seeded run samples the same requests every time
(:func:`sample_decision` is pure; pinned).  Unsampled traces still
carry an id (log correlation) but record no spans, so ``=0`` restores
the untraced fast path.

Export: :meth:`Trace.finish` writes one JSON line to the
``MXNET_TRACE_JSONL`` sink (or a sink installed via
:func:`set_jsonl_sink`) and — when the Chrome-trace profiler is
running — drops a ``cat="trace"`` root marker into it, so a dumped
profile shows each sampled request's window against the engine phases
inside it.

**Flight recorder.**  A bounded per-process ring
(``MXNET_FLIGHT_CAPACITY`` events, fixed memory, one deque append per
record) of recent spans / events / errors.  It is always listening
(capacity 0 disables); on an engine-loop crash, on the
``serve.dispatch`` faultinject ``die`` path, and on demand
(``GET /debug/flight``, :func:`dump_flight`) the ring — plus a
metrics snapshot — dumps through ``base.atomic_write`` into
``MXNET_FLIGHT_DIR``, so a killed replica leaves a readable
postmortem artifact naming what died and what the process was doing
in its last moments (docs/architecture/observability.md).
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time

from . import metrics as _metrics
from .analysis.lockcheck import make_lock
from .base import atomic_write, get_env

__all__ = ["Trace", "Span", "start_trace", "sample_decision",
           "activate", "activate_many", "current_context", "on_phase",
           "set_jsonl_sink", "FlightRecorder", "flight", "dump_flight",
           "reset_flight"]

# Spans per trace are bounded: a runaway generation (or a bug) must
# not grow one trace without limit.  Drops are counted on the trace.
MAX_SPANS_PER_TRACE = 512

_MASK64 = (1 << 64) - 1


def sample_decision(seq, rate=None, seed=None):
    """Pure, deterministic per-trace sampling decision.

    Hashes (``seed``, ``seq``) splitmix64-style into [0, 1) and
    compares against ``rate``; same (seed, seq, rate) => same verdict
    on every host and run (the determinism pin's subject).  Defaults
    read ``MXNET_TRACE_SAMPLE`` / ``MXNET_TRACE_SEED``."""
    if rate is None:
        rate = float(get_env("MXNET_TRACE_SAMPLE"))
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    if seed is None:
        seed = int(get_env("MXNET_TRACE_SEED"))
    x = (int(seq) * 0x9E3779B97F4A7C15 + int(seed)
         * 0xBF58476D1CE4E5B9 + 0x2545F4914F6CDD1D) & _MASK64
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9 & _MASK64
    x = (x ^ (x >> 27)) * 0x94D049BB133111EB & _MASK64
    x ^= x >> 31
    return (x >> 11) / float(1 << 53) < rate


class Span:
    """One timed operation inside a trace."""

    __slots__ = ("name", "span_id", "parent_id", "t0_ns", "t1_ns",
                 "thread")

    def __init__(self, name, span_id, parent_id, t0_ns, t1_ns, thread):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0_ns = t0_ns
        self.t1_ns = t1_ns
        self.thread = thread


class Trace:
    """One request's span tree.  Mint via :func:`start_trace`; the
    minter calls :meth:`finish` exactly once (idempotent) when the
    request resolves."""

    __slots__ = ("trace_id", "name", "sampled", "attrs", "root_id",
                 "t0_ns", "spans", "spans_dropped", "_seq", "_lock",
                 "_finished")

    def __init__(self, trace_id, name, sampled, attrs):
        self.trace_id = trace_id
        self.name = name
        self.sampled = sampled
        self.attrs = attrs
        self.root_id = 0
        self.t0_ns = time.perf_counter_ns()
        self.spans = []
        self.spans_dropped = 0
        self._seq = itertools.count(1)
        self._lock = threading.Lock()
        self._finished = False

    def add_span(self, name, t0_ns, t1_ns, parent_id=None):
        """Record one finished span (no-op on unsampled traces);
        returns its span id (None when unsampled/dropped)."""
        if not self.sampled:
            return None
        sid = next(self._seq)
        span = Span(name, sid, self.root_id if parent_id is None
                    else parent_id, t0_ns, t1_ns,
                    threading.get_ident() % 100000)
        with self._lock:
            if self._finished or len(self.spans) >= MAX_SPANS_PER_TRACE:
                self.spans_dropped += 1
                return None
            self.spans.append(span)
        return sid

    def finish(self, status="ok"):
        """Close the trace and export it (JSONL sink + a root marker
        in the live Chrome profiler).  Idempotent — late resolutions
        racing the minter's finish are dropped, not double-exported."""
        with self._lock:
            if self._finished:
                return
            self._finished = True
            spans = list(self.spans)
        t1 = time.perf_counter_ns()
        if not self.sampled:
            return
        _export_jsonl(self, spans, t1, status)
        _export_chrome(self, t1, status)

    def to_dict(self, spans, t1_ns, status):
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "status": status,
            "t0_ns": self.t0_ns,
            "dur_ms": round((t1_ns - self.t0_ns) / 1e6, 3),
            "attrs": self.attrs,
            "spans_dropped": self.spans_dropped,
            "spans": [{
                "name": s.name, "span_id": s.span_id,
                "parent_id": s.parent_id,
                "t0_ms": round((s.t0_ns - self.t0_ns) / 1e6, 3),
                "dur_ms": round((s.t1_ns - s.t0_ns) / 1e6, 3),
                "thread": s.thread,
            } for s in spans],
        }


_ID_SEQ = itertools.count()
_ID_BASE = "%08x" % (os.getpid() & 0xFFFFFFFF)


def start_trace(name, sampled=None, **attrs):
    """Mint a new trace.  ``sampled=None`` defers to the seeded
    ``MXNET_TRACE_SAMPLE`` decision for this mint's sequence number."""
    seq = next(_ID_SEQ)
    if sampled is None:
        sampled = sample_decision(seq)
    tr = Trace("%s%016x" % (_ID_BASE, seq), name, bool(sampled), attrs)
    fl = _flight_or_none()
    if fl is not None:
        fl.record("trace", name, trace_id=tr.trace_id,
                  sampled=tr.sampled)
    return tr


# ---------------------------------------------------------------------------
# Thread-local activation: which traces the current thread is working
# for.  A frame is a list of (trace, parent_span_id) pairs — usually
# one, but a batched dispatch serves many requests at once and its
# spans belong to every member's trace.
# ---------------------------------------------------------------------------
_tls = threading.local()


def _frames():
    fr = getattr(_tls, "frames", None)
    if fr is None:
        fr = _tls.frames = []
    return fr


class _Activation:
    """Context manager pushing one frame of (trace, parent) pairs."""

    __slots__ = ("pairs",)

    def __init__(self, pairs):
        self.pairs = pairs

    def __enter__(self):
        _frames().append(self.pairs)
        return self

    def __exit__(self, *exc):
        _frames().pop()


def activate(trace, parent_id=None):
    """Make ``trace`` the current thread's span target (a with-block);
    ``trace=None`` pushes an empty frame (explicitly untraced)."""
    if trace is None:
        return _Activation([])
    return _Activation([(trace, parent_id)])


def activate_many(pairs):
    """Batch activation: phase spans recorded inside attach to EVERY
    (trace, parent) pair — one ``serve_compute`` span lands in each
    batched request's trace."""
    return _Activation([(t, p) for (t, p) in pairs if t is not None])


def current_context():
    """(trace, parent_span_id) the current thread works for, or None.
    Request objects capture this at submit so engine threads can
    re-activate it — the cross-thread propagation handshake."""
    fr = _frames()
    if not fr or not fr[-1]:
        return None
    return fr[-1][0]


def has_context():
    fr = getattr(_tls, "frames", None)
    return bool(fr) and bool(fr[-1])


def sinks_active():
    """Whether :func:`on_phase` would do anything on this thread (an
    activated trace, or the flight ring listening) — the
    ``phase`` early-out check."""
    return has_context() or _flight_or_none() is not None


def on_phase(name, t0_ns, t1_ns):
    """The ``profiler.phase`` fan-out: attach the span to every
    trace in the current activation frame, and append it to the flight
    ring.  Cheap when idle (one tls read + one capacity check)."""
    fr = getattr(_tls, "frames", None)
    if fr and fr[-1]:
        for trace, parent in fr[-1]:
            trace.add_span(name, t0_ns, t1_ns, parent)
    fl = _flight_or_none()
    if fl is not None:
        fl.note_span(name, t0_ns, t1_ns)


def future_status(fut):
    """Trace status string from a resolved ``concurrent.futures``
    future: 'ok', 'cancelled', or the exception class name."""
    if fut.cancelled():
        return "cancelled"
    exc = fut.exception()
    return "ok" if exc is None else type(exc).__name__


def finish_on_done(trace):
    """Done-callback finishing a trace the callee minted itself (the
    in-process ingress case: submit() owned the mint, so the future's
    resolution is the request's end)."""
    def _cb(fut):
        trace.finish(status=future_status(fut))
    return _cb


# ---------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------
_sink_lock = make_lock("tracing.sink")
_sink_override = [None]   # programmatic set_jsonl_sink wins over env


def set_jsonl_sink(path):
    """Install (or, with None, fall back to ``MXNET_TRACE_JSONL``)
    the per-trace JSONL export path."""
    with _sink_lock:
        _sink_override[0] = path


def _sink_path():
    p = _sink_override[0]
    if p is not None:
        return p or None
    return get_env("MXNET_TRACE_JSONL") or None


def _export_jsonl(trace, spans, t1_ns, status):
    path = _sink_path()
    if not path:
        return
    line = json.dumps(trace.to_dict(spans, t1_ns, status))
    with _sink_lock:
        try:
            with open(path, "a") as f:
                f.write(line + "\n")
        except OSError:
            pass  # a vanished sink must never fail the request


def _export_chrome(trace, t1_ns, status):
    from . import profiler as _profiler
    prof = _profiler._state["profiler"]
    if prof is not None:
        prof.record("trace[%s]:%s" % (trace.trace_id[-8:], trace.name),
                    trace.t0_ns, t1_ns, cat="trace")


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------
class FlightRecorder:
    """Bounded ring of recent spans/events/errors (fixed memory: a
    ``deque(maxlen=capacity)`` of small dicts; one append + one lock
    per record — cheap enough to stay on in production)."""

    def __init__(self, capacity):
        self.capacity = max(0, int(capacity))
        self._ring = collections.deque(maxlen=self.capacity or 1)
        self._lock = threading.Lock()
        self._dump_seq = itertools.count()

    def record(self, kind, name, **attrs):
        if not self.capacity:
            return
        ev = {"t": round(time.time(), 6), "kind": kind, "name": name,
              "thread": threading.get_ident() % 100000}
        if attrs:
            ev.update(attrs)
        with self._lock:
            self._ring.append(ev)

    def note_span(self, name, t0_ns, t1_ns):
        self.record("span", name,
                    dur_ms=round((t1_ns - t0_ns) / 1e6, 3))

    def events(self):
        with self._lock:
            return list(self._ring)

    def dump(self, path=None, reason="", extra=None):
        """Write the ring + a metrics snapshot as one JSON artifact via
        ``base.atomic_write``.  ``path=None`` derives
        ``flight.<pid>.<n>.json`` under ``MXNET_FLIGHT_DIR`` (no dir
        configured => no file, returns None — the ring stays readable
        in-process via :meth:`events` / ``GET /debug/flight``)."""
        if path is None:
            d = get_env("MXNET_FLIGHT_DIR")
            if not d:
                return None
            path = os.path.join(d, "flight.%d.%d.json"
                                % (os.getpid(), next(self._dump_seq)))
        doc = {
            "reason": reason,
            "pid": os.getpid(),
            "time": time.time(),
            "capacity": self.capacity,
            "events": self.events(),
            "metrics": _metrics.snapshot(),
        }
        if extra:
            doc["extra"] = extra
        with atomic_write(path, "w") as f:
            json.dump(doc, f)
        return path


_flight_lock = threading.Lock()
_flight = [None]


def _flight_or_none():
    fl = _flight[0]
    if fl is None:
        fl = flight()
    return fl if fl.capacity else None


def flight():
    """The process flight recorder (lazy; capacity from
    ``MXNET_FLIGHT_CAPACITY`` at first use — :func:`reset_flight`
    re-reads after an env change)."""
    fl = _flight[0]
    if fl is None:
        with _flight_lock:
            fl = _flight[0]
            if fl is None:
                fl = FlightRecorder(int(get_env("MXNET_FLIGHT_CAPACITY")))
                _flight[0] = fl
    return fl


def reset_flight():
    """Drop the recorder (and its ring); the next use re-reads the
    capacity knob.  Tests and the overhead bench use this around env
    changes."""
    with _flight_lock:
        _flight[0] = None


def dump_flight(reason="", extra=None, path=None):
    """On-demand postmortem: dump the flight ring (see
    :meth:`FlightRecorder.dump`)."""
    return flight().dump(path=path, reason=reason, extra=extra)
