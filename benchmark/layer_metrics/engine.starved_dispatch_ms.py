"""A tick's launches into an idle device, an UPPER bound of what the
launch itself adds to the device's gap (host arrays to the device and
the call): the spans ``device_launch`` (``mxnet_tpu.profiler.
StarvedClock``: every dispatch call entered with nothing queued on the
device, a step's, a chunk's or a fork's copy, from its entry to its
return; the program starts somewhere inside, 0.45 ms into a call of
1.7 in the one trace split by hand, PERF.md section 6, PR 36) and the
count ``starved_ns`` of ``serve_decode`` and ``serve_prefill`` (what
of each span lies before its call is entered), over the ``serve_tick``
spans, all over the TRACED seconds alone (``host["traced_phases"]``).
Not part of ``engine.starved_pct``, which ends where the call is
entered.  None where ``engine.starved_pct`` is.  Layer: serving planes
(``decode_engine.py``)."""


def read(run):
    base = run["cell"].module("layer_metrics", "engine.starved_pct")
    return base.starved_ms_a_tick(run, ("serve_decode", "serve_prefill"),
                                  launches=True)
