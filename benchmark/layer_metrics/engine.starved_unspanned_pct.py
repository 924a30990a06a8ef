"""How much of the host's account of the device's gap the spans still
do not name.  The account: ``device_starved`` (from a fetch's return
to the entry of the next dispatch call) and ``device_launch`` (that
call, to its return).  Named: ``device_launch`` and what of
``device_starved`` lies inside the five leaves (``serve_resolve``,
``serve_admit``, ``serve_prepare``, ``serve_decode``, ``serve_prefill``;
their ``starved_ns`` counts).  The rest, over the account, over the
TRACED seconds alone (``host["traced_phases"]``): the engine's loop
between ticks, ``_pump``, ``_has_work``, the gauges, the tail of
``serve_sample``, each span's own opening and closing.  With the four
``engine.starved_*_ms`` it sums to the account.  An empty account (the
clock ran and opened nothing) has nothing unnamed: 0.0.  None where
``engine.starved_pct`` is.  Layer: serving planes
(``decode_engine.py``)."""


def read(run):
    base = run["cell"].module("layer_metrics", "engine.starved_pct")
    phases = base.traced(run)
    if phases is None:
        return None
    starved = phases["device_starved"]["ns"]
    account = starved + base.launch_ns(phases)
    if not account:
        return 0.0
    return 100.0 * (starved - base.starved_ns(phases, base.LEAVES)) \
        / account
