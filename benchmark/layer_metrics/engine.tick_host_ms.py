"""A tick's time in which the host is not waiting on the device: the
program's own spans (``mxnet_tpu.profiler.phase_totals()``),
(``serve_tick`` ns - ``serve_sample`` ns) / ``serve_tick`` spans.  A
tick is one pass of the engine loop that did work; ``serve_sample`` is
the blocking fetch of the sampled tokens, so what is left is admission,
block allocation, copy-on-write forks, the two dispatches, pushing
tokens and resolving futures, with nothing queued on the device.

What it reads is the PROCESS's lifetime, not the window: the totals
outlive the engine (the driver closes it before readers run), and the
driver hands a reader nothing taken at the window's opening.  Measured
(PERF.md section 6, PR 24): the 5 s ramp is 40 ticks of 10.3-10.9 ms
(it admits the whole batch: 3.3 ms of admission and 1.5 ms of forks a
tick), a tick of the window 3.2 ms untraced, 3.3 ms while the profiler
records and 5.3-5.5 ms during the 5-6 s its ``stop_trace`` takes; so at
``--seconds 30`` the lifetime mean reads about 1.45 + 0.86 x the
window's, and other ``--seconds`` read otherwise.  Compare it only
between runs of one ``--seconds``.  The cure is the serve driver's:
``phase_totals(since=reading_at_the_opening)`` (PERF.md section 7).

None where the program has no ``phase_totals`` or no ``serve_tick``
span.  Layer: serving planes (``decode_engine.py``)."""

def read(run):
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    totals = getattr(profiler, "phase_totals", lambda: {})()
    tick = totals.get("serve_tick")
    if not tick or not tick["spans"]:
        return None
    waited = totals.get("serve_sample", {"ns": 0})["ns"]
    return 1e-6 * (tick["ns"] - waited) / tick["spans"]
