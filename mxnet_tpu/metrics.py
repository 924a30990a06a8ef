"""Process-wide metrics registry: named Counters, Gauges and
log-bucketed Histograms.

The repo grew at least six disjoint stats surfaces (serving ``stats()``
trees, ``wire_stats()``, ``imperative_cache_stats()``,
``dispatch_stats()``, program-cache stats, the engine's ``cache_hwm``)
— each a private dict with its own lock and no way to scrape them
together.  This module is the one aggregation plane they read through:

* :class:`Counter` — monotonically increasing (``_total`` names);
* :class:`Gauge`   — a settable point-in-time value (queue depth,
  in-flight window, breaker state);
* :class:`Histogram` — **fixed log-bucketed**: observations land in
  geometric buckets (growth ``2**0.25`` per bucket, ~19% wide), so
  p50/p95/p99 come from ~150 integers instead of stored samples —
  bounded memory at any request rate, with a provable quantile error
  bound (the estimate is the bucket's geometric midpoint, so the
  relative error is at most ``sqrt(growth) - 1`` ≈ 9%;
  tests/test_observability.py pins it against ``numpy.percentile``).

Instruments are named Prometheus-style (``snake_case``, ``_total``
suffix for counters, ``_seconds`` for time histograms) and may carry a
small fixed label set (e.g. ``{"engine": "fwd3"}``) — one instrument
per (name, labels) pair, created on first use and shared after
(``counter(name, labels=...)`` is get-or-create).  The process
registry renders as Prometheus text exposition
(:func:`render_prometheus` — the front door's ``GET /metrics``) and as
a plain dict (:func:`snapshot` — in-process consumers,
``callback.MetricsLogger``, ``tools/step_profile.py --metrics``).

``MXNET_METRICS=0`` turns the *ambient* instrumentation seams off (the
``profiler.phase`` histogram feed checks :func:`phase_on`);
explicitly created instruments keep working — a stats tree reading
through its counters must never see them vanish.

Per-instance labeled series (an engine's counters) are dropped from
the registry by ``drop(labels)`` when their owner closes, so a test
process churning hundreds of engines does not grow the scrape output
without bound; the owner's own references stay valid (its ``stats()``
keeps reading) — only the process-wide listing forgets the series.
"""
from __future__ import annotations

import math
import threading

from .analysis.lockcheck import make_lock
from .base import MXNetError, get_env

__all__ = ["Counter", "Gauge", "GaugeFn", "Histogram", "CounterDict",
           "HistogramWindow", "MetricsRegistry", "registry", "counter",
           "gauge", "histogram", "gauge_fn", "cached_counter",
           "cached_histogram", "snapshot", "render_prometheus",
           "phase_on", "drop", "BUCKET_GROWTH", "QUANTILE_REL_ERROR"]


def _label_key(labels):
    if not labels:
        return ()
    return tuple(sorted(labels.items()))


def _label_suffix(label_key):
    if not label_key:
        return ""
    return "{%s}" % ",".join('%s="%s"' % kv for kv in label_key)


class Counter:
    """Monotonic counter.  ``inc`` only; negative increments raise."""

    __slots__ = ("name", "help", "labels", "_value", "_lock")

    def __init__(self, name, help="", labels=None):
        self.name = name
        self.help = help
        self.labels = _label_key(labels)
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        if n < 0:
            raise MXNetError("counter %r cannot decrease" % self.name)
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value


class Gauge:
    """Point-in-time value; ``set`` / ``inc`` / ``dec``."""

    __slots__ = ("name", "help", "labels", "_value", "_lock")

    def __init__(self, name, help="", labels=None):
        self.name = name
        self.help = help
        self.labels = _label_key(labels)
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v):
        with self._lock:
            self._value = float(v)

    def inc(self, n=1):
        with self._lock:
            self._value += n

    def dec(self, n=1):
        with self._lock:
            self._value -= n

    @property
    def value(self):
        return self._value


# One bucket per quarter power of two: 4 buckets per 2x, ~150 buckets
# across [1e-6, 1e4] (microseconds to hours for _seconds histograms).
BUCKET_GROWTH = 2.0 ** 0.25
# Worst-case relative quantile error: the true value lies somewhere in
# a bucket whose edges differ by BUCKET_GROWTH; reporting the geometric
# midpoint bounds the relative error by sqrt(growth) - 1.
QUANTILE_REL_ERROR = math.sqrt(BUCKET_GROWTH) - 1.0


class Histogram:
    """Fixed log-bucketed histogram: p50/p95/p99 without samples.

    ``lo`` is the upper edge of the first bucket; values at or below it
    land there (the quantile degrades to ``lo`` — pick ``lo`` below the
    smallest latency you care to resolve).  Values above ``hi`` land in
    a final overflow bucket reported as ``hi``.  Between them bucket
    ``i`` covers ``(lo * growth**(i-1), lo * growth**i]`` and quantile
    estimates return the bucket's geometric midpoint, so the relative
    error is bounded by :data:`QUANTILE_REL_ERROR`."""

    __slots__ = ("name", "help", "labels", "lo", "hi", "_n_buckets",
                 "_log_lo", "_log_g", "_counts", "_sum", "_count",
                 "_max", "_lock")

    def __init__(self, name, help="", labels=None, lo=1e-6, hi=1e4):
        self.name = name
        self.help = help
        self.labels = _label_key(labels)
        self.lo = float(lo)
        self.hi = float(hi)
        self._log_lo = math.log(self.lo)
        self._log_g = math.log(BUCKET_GROWTH)
        self._n_buckets = int(math.ceil(
            (math.log(self.hi) - self._log_lo) / self._log_g)) + 2
        self._counts = [0] * self._n_buckets
        self._sum = 0.0
        self._count = 0
        self._max = 0.0
        self._lock = threading.Lock()

    def _index(self, v):
        if v <= self.lo:
            return 0
        i = int(math.ceil((math.log(v) - self._log_lo) / self._log_g))
        return min(i, self._n_buckets - 1)

    def observe(self, v):
        v = float(v)
        i = self._index(max(v, 0.0))
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            if v > self._max:
                self._max = v

    def edge(self, i):
        """Upper edge of bucket ``i``."""
        if i <= 0:
            return self.lo
        return math.exp(self._log_lo + i * self._log_g)

    def quantile(self, q):
        """Estimated ``q``-quantile (0..1): the geometric midpoint of
        the bucket holding the ``q``-th observation; None when empty."""
        with self._lock:
            total = self._count
            counts = list(self._counts)
        if not total:
            return None
        rank = q * (total - 1)
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum > rank:
                if i == 0:
                    return self.lo
                if i == self._n_buckets - 1:
                    return self.hi
                # geometric midpoint of (edge(i-1), edge(i)]
                return math.exp(self._log_lo + (i - 0.5) * self._log_g)
        return self.hi

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def percentiles(self):
        return {"p50": self.quantile(0.50), "p95": self.quantile(0.95),
                "p99": self.quantile(0.99)}

    def describe(self):
        with self._lock:
            count, total, mx = self._count, self._sum, self._max
        d = {"count": count, "sum": round(total, 6),
             "max": round(mx, 6) if count else None}
        d.update({k: (round(v, 9) if v is not None else None)
                  for k, v in self.percentiles().items()})
        return d

    def _scrape_state(self):
        """(counts, count, sum) captured under ONE lock acquisition —
        the exposition's buckets/_count/_sum must come from the same
        instant or a racing observe breaks the Prometheus invariant
        that ``_count`` equals the ``+Inf`` bucket."""
        with self._lock:
            return list(self._counts), self._count, self._sum

    def buckets(self):
        """(upper_edge, cumulative_count) pairs for non-empty prefix —
        the Prometheus ``_bucket{le=...}`` series (sparse: only edges
        up to the highest occupied bucket, plus +Inf)."""
        counts, total, _ = self._scrape_state()
        return self._bucket_pairs(counts, total)

    def _bucket_pairs(self, counts, total):
        out = []
        cum = 0
        hi_occupied = max((i for i, c in enumerate(counts) if c),
                          default=-1)
        for i in range(hi_occupied + 1):
            cum += counts[i]
            out.append((self.edge(i), cum))
        out.append((float("inf"), total))
        return out


class HistogramWindow:
    """Windowed quantiles over a :class:`Histogram`: deltas between
    :meth:`tick` calls.

    A cumulative histogram answers "p95 since process start", but a
    feedback controller (the serving autoscaler) needs "p95 over the
    LAST interval" — old observations must age out or one burst an hour
    ago pins the signal forever.  The window keeps the previous
    ``_scrape_state`` snapshot and each ``tick()`` returns the quantile
    of only the observations that landed since the previous one (same
    geometric-midpoint estimate and error bound as
    ``Histogram.quantile``).  Single-consumer: one window per reader."""

    __slots__ = ("_h", "_counts", "_count", "_sum")

    def __init__(self, hist):
        self._h = hist
        self._counts, self._count, self._sum = hist._scrape_state()

    def tick(self):
        """Advance the window.  Returns ``(count, sum, quantile_fn)``
        for the observations since the previous tick; ``quantile_fn(q)``
        is None when the window is empty."""
        counts, count, total = self._h._scrape_state()
        # max(0, ...) guards a registry reset() swapping in a fresh
        # instrument mid-window: a negative delta is a restart, not
        # traffic
        d = [max(0, b - a) for a, b in zip(self._counts, counts)]
        dcount = max(0, count - self._count)
        dsum = total - self._sum
        self._counts, self._count, self._sum = counts, count, total
        h = self._h

        def quantile(q, _d=d, _n=dcount):
            if not _n:
                return None
            rank = q * (_n - 1)
            cum = 0
            for i, c in enumerate(_d):
                cum += c
                if cum > rank:
                    if i == 0:
                        return h.lo
                    if i == len(_d) - 1:
                        return h.hi
                    return math.exp(h._log_lo + (i - 0.5) * h._log_g)
            return h.hi

        return dcount, dsum, quantile


class GaugeFn:
    """A gauge whose value is pulled from a callback at read time —
    zero hot-path cost for surfaces whose counters already exist
    behind their own lock (the imperative cached-op LRU): the scrape
    walks them, the dispatch path never touches the registry."""

    __slots__ = ("name", "help", "labels", "_fn")

    def __init__(self, name, help="", labels=None, fn=None):
        self.name = name
        self.help = help
        self.labels = _label_key(labels)
        self._fn = fn

    @property
    def value(self):
        try:
            return float(self._fn())
        except Exception:  # noqa: BLE001 — a scrape never raises
            return float("nan")


class MetricsRegistry:
    """(name, labels) -> instrument, with text/dict exports."""

    def __init__(self):
        self._metrics = {}
        self._lock = make_lock("metrics.registry")

    def _get(self, cls, name, help, labels, **kwargs):
        key = (name, _label_key(labels))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, help=help, labels=labels, **kwargs)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise MXNetError(
                    "metric %r is already registered as %s"
                    % (name, type(m).__name__))
        return m

    def counter(self, name, help="", labels=None):
        return self._get(Counter, name, help, labels)

    def gauge(self, name, help="", labels=None):
        return self._get(Gauge, name, help, labels)

    def histogram(self, name, help="", labels=None, lo=1e-6, hi=1e4):
        return self._get(Histogram, name, help, labels, lo=lo, hi=hi)

    def gauge_fn(self, name, fn, help="", labels=None):
        """Register (or refresh the callback of) a pull-style gauge."""
        g = self._get(GaugeFn, name, help, labels, fn=fn)
        g._fn = fn
        return g

    def get(self, name, labels=None):
        """The instrument, or None."""
        with self._lock:
            return self._metrics.get((name, _label_key(labels)))

    def value(self, name, labels=None):
        """Convenience: the counter/gauge value (None when absent)."""
        m = self.get(name, labels)
        return None if m is None else m.value

    def drop(self, labels):
        """Unregister every series whose labels contain all of
        ``labels`` (an owner retiring its per-instance series on
        close).  Existing references keep working; only the
        process-wide listing forgets them."""
        sub = set(_label_key(labels))
        if not sub:
            return 0
        with self._lock:
            doomed = [k for k in self._metrics
                      if sub.issubset(set(k[1]))]
            for k in doomed:
                del self._metrics[k]
        return len(doomed)

    def reset(self):
        """Drop everything (tests)."""
        with self._lock:
            self._metrics.clear()

    def _sorted(self):
        with self._lock:
            return sorted(self._metrics.items())

    def snapshot(self):
        """{"counters": {...}, "gauges": {...}, "histograms": {...}}
        with ``name{label="v"}`` keys — the in-process read surface."""
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for (name, lk), m in self._sorted():
            key = name + _label_suffix(lk)
            if isinstance(m, Counter):
                out["counters"][key] = m.value
            elif isinstance(m, (Gauge, GaugeFn)):
                out["gauges"][key] = m.value
            else:
                out["histograms"][key] = m.describe()
        return out

    def render_prometheus(self):
        """Prometheus text exposition (version 0.0.4) of every
        registered instrument — the ``GET /metrics`` payload."""
        lines = []
        seen_header = set()
        for (name, lk), m in self._sorted():
            suffix = _label_suffix(lk)
            if name not in seen_header:
                seen_header.add(name)
                if m.help:
                    lines.append("# HELP %s %s" % (name, m.help))
                kind = ("counter" if isinstance(m, Counter) else
                        "gauge" if isinstance(m, (Gauge, GaugeFn))
                        else "histogram")
                lines.append("# TYPE %s %s" % (name, kind))
            if isinstance(m, (Counter, Gauge, GaugeFn)):
                lines.append("%s%s %s" % (name, suffix, _fmt(m.value)))
                continue
            counts, total, s = m._scrape_state()
            base = dict(lk)
            for le, cum in m._bucket_pairs(counts, total):
                lbl = dict(base)
                lbl["le"] = "+Inf" if le == float("inf") \
                    else _fmt(le)
                lines.append("%s_bucket%s %d"
                             % (name, _label_suffix(_label_key(lbl)),
                                cum))
            lines.append("%s_sum%s %s" % (name, suffix, _fmt(s)))
            lines.append("%s_count%s %d" % (name, suffix, total))
        return "\n".join(lines) + "\n"


def _fmt(v):
    if isinstance(v, int) or (isinstance(v, float) and v.is_integer()):
        return "%d" % v
    return repr(float(v))


class CounterDict:
    """dict-like facade over a family of labeled registry counters, so
    a legacy ``stats()`` tree reads THROUGH the registry: increments go
    to real Counters (scrapeable at ``GET /metrics``), and
    ``as_dict()`` / ``[]`` read their live values back in the exact
    key layout the old private dict had."""

    __slots__ = ("_c",)

    def __init__(self, prefix, keys, labels=None, help=""):
        self._c = {k: counter(prefix + k + "_total", help=help,
                              labels=labels) for k in keys}

    def inc(self, key, n=1):
        self._c[key].inc(n)

    def __getitem__(self, key):
        return self._c[key].value

    def __contains__(self, key):
        return key in self._c

    def as_dict(self):
        return {k: c.value for k, c in self._c.items()}


_default = MetricsRegistry()


def registry():
    """The process-wide registry."""
    return _default


def counter(name, help="", labels=None):
    return _default.counter(name, help=help, labels=labels)


def gauge(name, help="", labels=None):
    return _default.gauge(name, help=help, labels=labels)


def histogram(name, help="", labels=None, lo=1e-6, hi=1e4):
    return _default.histogram(name, help=help, labels=labels,
                              lo=lo, hi=hi)


def gauge_fn(name, fn, help="", labels=None):
    return _default.gauge_fn(name, fn, help=help, labels=labels)


# Hot-path instrument cache: a plain module dict in front of the
# registry's get-or-create, so per-event sites (one increment per RPC /
# phase / program-cache event) pay one dict lookup instead of the
# registry lock.  The benign race (two threads both missing) resolves
# to the SAME registry instrument either way.  Cached references
# deliberately survive registry drop()/reset(): an owner keeps
# counting even after the process listing forgot its series.
_HOT_CACHE = {}


def cached_counter(name, help="", labels=None):
    key = (name, _label_key(labels))
    c = _HOT_CACHE.get(key)
    if c is None:
        c = _HOT_CACHE[key] = _default.counter(name, help=help,
                                               labels=labels)
    return c


def cached_histogram(name, help="", labels=None, lo=1e-6, hi=1e4):
    key = (name, _label_key(labels))
    h = _HOT_CACHE.get(key)
    if h is None:
        h = _HOT_CACHE[key] = _default.histogram(name, help=help,
                                                 labels=labels,
                                                 lo=lo, hi=hi)
    return h


def snapshot():
    return _default.snapshot()


def render_prometheus():
    return _default.render_prometheus()


def drop(labels):
    return _default.drop(labels)


def phase_on():
    """Whether the ambient instrumentation seams (the
    ``profiler.phase`` histogram feed) observe.  Explicit
    instruments ignore this — ``MXNET_METRICS=0`` silences the ambient
    feeds, it does not break stats trees reading through counters."""
    return bool(get_env("MXNET_METRICS"))
