"""The six ``engine.starved_*`` readers on hand-made totals of the traced
seconds: a clock that RAN and opened no interval (a program that keeps
the next tick queued ahead of the fetch) reads 0.0 from all six, not
nothing; None stays for what it documents (no trace, a driver without
``traced_phases``, a program from before the clock, no ``serve_tick``
spans); and on the shape of input today's program hands over the values
are the ones they were."""
import pytest

from benchmark import harness

BENCH = harness.load_json(harness.ROOT + "/BENCHMARK.json")
READERS = ("engine.starved_pct", "engine.starved_resolve_ms",
           "engine.starved_admit_ms", "engine.starved_prepare_ms",
           "engine.starved_dispatch_ms", "engine.starved_unspanned_pct")
CELL = "lfm2-24b-a2b.serve-agent-backlog"


def _span(spans, ns, **counts):
    return {"spans": spans, "ns": ns, "counts": counts}


def _starving():
    """3 s traced, 50 ticks, the device starved in every one (the
    totals of ``tests/test_starved_clock.py``): the host's account of
    the gap is 180 ms, 140 starved (30 + 10 + 40 + (3 + 1) of it inside
    the five leaves, 56 outside them) and 40 inside the launches."""
    return {
        "device_starved": _span(150, 140_000_000),
        "device_launch": _span(100, 40_000_000),
        "serve_tick": _span(50, 2_900_000_000, starved_ns=110_000_000),
        "serve_resolve": _span(100, 90_000_000, starved_ns=30_000_000),
        "serve_admit": _span(50, 35_000_000, starved_ns=10_000_000),
        "serve_prepare": _span(100, 60_000_000, starved_ns=40_000_000),
        "cow_fork": _span(10, 6_000_000, starved_ns=5_000_000, blocks=10),
        "serve_decode": _span(50, 40_000_000, starved_ns=3_000_000),
        "serve_prefill": _span(50, 45_000_000, starved_ns=1_000_000),
        "serve_sample": _span(100, 2_500_000_000, starved_ns=1_000_000),
    }


def _a_tick_ahead():
    """The same seconds of a program whose clock opened nothing:
    ``phase_totals(since=...)`` keeps every name the lifetime has, so
    the keys are there with nothing gained (``device_launch`` too: no
    call was entered with the device idle)."""
    phases = _starving()
    for name, got in phases.items():
        got["counts"].pop("starved_ns", None)
        if name.startswith("device_"):
            phases[name] = _span(0, 0)
        elif name != "cow_fork":
            got["counts"]["starved_ns"] = 0
    return phases


def _run(phases, trace=True):
    host = {"window_s": 30.0}
    if phases is not None:
        host["traced_phases"] = phases
    return {"cell": harness.Cell(CELL, rehearse=True), "host": host,
            "trace": {"window_s": 3.0, "busy_s": 2.99, "devices": []}
            if trace else None}


def _read(name, run):
    return harness.load_module(BENCH,
                               "layer_metrics/%s.py" % name).read(run)


@pytest.mark.parametrize("name", READERS)
def test_a_clock_that_ran_and_opened_nothing_reads_zero(name):
    value = _read(name, _run(_a_tick_ahead()))
    assert value == 0.0 and isinstance(value, float)
    # a lifetime that never entered a call with the device idle has no
    # ``device_launch`` either: still 0.0, no division by zero
    phases = _a_tick_ahead()
    del phases["device_launch"]
    assert _read(name, _run(phases)) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_none_stays_for_what_it_documents(name):
    for phases in (_starving(), _a_tick_ahead()):
        assert _read(name, _run(phases, trace=False)) is None
        assert _read(name, _run(None)) is None
        assert _read(name, _run({})) is None
        before_the_clock = dict(phases)
        del before_the_clock["device_starved"]
        assert _read(name, _run(before_the_clock)) is None
        no_tick = dict(phases)
        del no_tick["serve_tick"]
        assert _read(name, _run(no_tick)) is None
        no_tick["serve_tick"] = _span(0, 0)
        assert _read(name, _run(no_tick)) is None


@pytest.mark.parametrize("name,value", zip(READERS, (
    100 * 0.140 / 3.0, 30 / 50, 10 / 50, 40 / 50, (40 + 3 + 1) / 50,
    100 * 56 / 180)))
def test_a_starving_program_reads_what_it_read(name, value):
    assert _read(name, _run(_starving())) == pytest.approx(value,
                                                           rel=1e-12)


def test_the_finished_line_carries_the_zeros():
    """Through ``harness.finish``: a traced line of a program that
    never starved lists all six, each 0.0."""
    import io
    import json
    from contextlib import redirect_stdout

    class Device:
        platform, device_kind = "tpu", "TPU v5 lite"

    cell = harness.Cell(CELL)
    out = {"checks": [], "attempted": 1, "failed": 0,
           "end_to_end": {"setup_s": 1.0, "serve_tokens_per_s": 1.0},
           "host": {"window_s": 30.0, "traced_phases": _a_tick_ahead()},
           "trace": {"window_s": 3.0, "busy_s": 2.99, "devices": [],
                     "device_ops": [], "idle_gaps": []}}
    text = io.StringIO()
    with redirect_stdout(text):
        assert harness.finish(cell, [Device()], out, True, False) == 0
    last = json.loads(text.getvalue().splitlines()[-1])
    for name in READERS:
        assert last["metrics"][name]["value"] == 0.0
    assert last["device"]["busy_s"] == 2.99 <= last["device"]["window_s"]
