"""The program's own account of the device's idle share: the seconds
in which the engine thread had NOTHING QUEUED on the device and was not
inside a dispatch call (the spans ``device_starved`` of
``mxnet_tpu.profiler.StarvedClock``: from the return of the fetch of
the newest dispatch to the ENTRY of the next dispatch call, the
engine's wait on an empty queue left out) over the traced seconds,
``run["trace"]["window_s"]``.  To hold beside ``1 - busy_s / window_s``
of the same run, which it can only under-read: the device was through
before the fetch returned (the largest part of a tick's gap, and in no
metric: nothing of the engine runs in it), it starts somewhere inside
the dispatch call (``engine.starved_dispatch_ms`` bounds that part from
above), and after a copy-on-write fork's copy it lies idle unseen
(PERF.md section 6, PR 36 splits one trace).

Read from ``host["traced_phases"]``, the spans' totals over the TRACED
seconds alone; never the process's lifetime.  That the clock RAN is the
key ``device_starved`` among them (the lifetime's totals carry it from
the engine's first launch on, whatever the traced seconds gained)
together with ``serve_tick`` spans in those seconds: a clock that ran
and opened no interval, a program that keeps the next tick queued
ahead of the fetch, reads 0.0 here and in the five parts, not nothing.
None without a trace (a rehearsal), without ``traced_phases`` (a driver
that does not take them), without the key ``device_starved`` (a
program from before the clock) or without ``serve_tick`` spans.  The
five readers of the parts
(``engine.starved_*``) take ``traced``, ``launch_ns`` and
``starved_ms_a_tick`` from here.  Layer: serving planes
(``decode_engine.py``)."""

# the spans that tile a tick's host work; cow_fork lies inside
# serve_prepare
LEAVES = ("serve_resolve", "serve_admit", "serve_prepare", "serve_decode",
          "serve_prefill")


def traced(run):
    """The traced seconds' totals where the clock ran, else None."""
    phases = run["host"].get("traced_phases") if run["trace"] else None
    if not phases or "device_starved" not in phases:
        return None
    if not phases.get("serve_tick") or not phases["serve_tick"]["spans"]:
        return None
    return phases


def starved_ns(phases, names):
    """The starved part of the spans ``names``, summed."""
    return sum(phases[n]["counts"].get("starved_ns", 0)
               for n in names if n in phases)


def launch_ns(phases):
    """The dispatch calls entered with nothing queued, entry to
    return (``device_launch``)."""
    return phases.get("device_launch", {"ns": 0})["ns"]


def starved_ms_a_tick(run, names, launches=False):
    phases = traced(run)
    if phases is None:
        return None
    ns = starved_ns(phases, names) + (launch_ns(phases) if launches else 0)
    return 1e-6 * ns / phases["serve_tick"]["spans"]


def read(run):
    phases = traced(run)
    if phases is None or not run["trace"].get("window_s"):
        return None
    return 100.0 * 1e-9 * phases["device_starved"]["ns"] \
        / run["trace"]["window_s"]
