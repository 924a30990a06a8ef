"""A tick's starved time inside ``serve_prepare`` (making rows
write-ready, its ``cow_fork``s inside, and building a dispatch's arrays):
the count ``starved_ns`` of ``serve_prepare`` (the part of each
span during which the engine thread had nothing queued on the device,
``mxnet_tpu.profiler.StarvedClock``) over the ``serve_tick`` spans, both
over the TRACED seconds alone (``host["traced_phases"]``).  None where
``engine.starved_pct`` is.  Layer: serving planes
(``decode_engine.py``)."""


def read(run):
    base = run["cell"].module("layer_metrics", "engine.starved_pct")
    return base.starved_ms_a_tick(run, ("serve_prepare",))
