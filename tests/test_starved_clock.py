"""The starved clock (``profiler.StarvedClock``): for how long the
device had nothing queued, by the engine thread's own count of what it
dispatched and what it fetched, and the part of every span of that
thread during which the clock ran (``starved_ns``).  The clock alone
under hand-made stamps, a toy paged engine's accounting (nothing timed:
the sums have to nest), two engines in one process, and the six readers
of ``benchmark/layer_metrics/engine.starved_*`` on hand-made totals
(docs/architecture/observability.md)."""
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.serving import GenerationEngine, ModelRegistry

from benchmark import harness


def _gained(opened, name="device_starved"):
    got = profiler.phase_totals(since=opened).get(name)
    return (got["spans"], got["ns"]) if got else (0, 0)


# ---------------------------------------------------------------------------
# (a) the clock alone, exact nanoseconds
# ---------------------------------------------------------------------------
def _queue(clock, entered, returned):
    """One dispatch call, entered and returned at hand-made stamps."""
    clock.launching(now_ns=entered)
    return clock.dispatched(now_ns=returned)


def test_two_programs_queued_before_a_fetch_give_no_starved_time():
    opened = profiler.phase_totals()
    clock = profiler.StarvedClock()
    first = _queue(clock, 90, 100)
    second = _queue(clock, 140, 150)
    assert (first, second) == (1, 2)
    # the fetch of the OLDER of two leaves the device busy
    clock.fetched(first, now_ns=400)
    assert clock.since_ns is None and clock.read(450) == 0
    # the newest is through: starved from the fetch's return on
    clock.fetched(second, now_ns=700)
    assert clock.since_ns == 700 and clock.read(760) == 60
    assert _gained(opened) == (0, 0)        # an open interval is no span
    # a step and a chunk again: the interval closes where the first
    # call is ENTERED, that call is timed to its return, and nothing
    # runs between the two
    _queue(clock, 1000, 1200)
    _queue(clock, 1250, 1300)
    assert clock.read(5000) == clock.closed_ns == 300
    assert _gained(opened) == (1, 300)
    assert _gained(opened, "device_launch") == (1, 200)


def test_a_fork_with_nothing_in_flight_ends_a_starved_interval():
    opened = profiler.phase_totals()
    clock = profiler.StarvedClock()
    clock.fetched(_queue(clock, 5, 10), now_ns=50)
    # a span of the thread opens at 70 and closes at 260, as phase()
    # reads the clock: a fork's copy_block is entered at 180
    at_open = clock.read(70)
    fork = _queue(clock, 180, 200)
    assert clock.read(260) - at_open == 180 - 70
    assert _gained(opened) == (1, 130)
    assert _gained(opened, "device_launch") == (1, 20)
    # the step that follows finds the device busy: nothing more, and
    # its call is no launch into an idle device
    step = _queue(clock, 290, 300)
    assert clock.closed_ns == 130
    assert _gained(opened, "device_launch") == (1, 20)
    # a fetch that follows its dispatch at once is the newest's
    clock.fetched(now_ns=900)
    assert clock.through == step == fork + 1 and clock.since_ns == 900


def test_serve_idle_stops_the_clock():
    """The engine's wait on an empty queue pauses the clock; a wait is
    no dispatch."""
    opened = profiler.phase_totals()
    clock = profiler.StarvedClock()
    clock.fetched(_queue(clock, 90, 100), now_ns=500)
    clock.pause(now_ns=600)                         # serve_idle opens
    assert clock.read(10_000) == 100                # the quiet seconds
    clock.resume(now_ns=10_000)                     # a request arrived
    _queue(clock, 10_250, 10_400)                   # its first program
    assert clock.closed_ns == 100 + 250 and clock.queued == 2
    assert _gained(opened) == (2, 350)
    assert _gained(opened, "device_launch") == (1, 150)
    # with a program in flight a wait's return starves nothing
    clock.pause(now_ns=10_500)
    clock.resume(now_ns=10_900)
    assert clock.since_ns is None and clock.closed_ns == 350


def test_a_late_fetch_of_an_older_dispatch_changes_nothing():
    clock = profiler.StarvedClock()
    a = _queue(clock, 0, 1)
    b = _queue(clock, 1, 2)
    clock.fetched(b, now_ns=10)
    clock.fetched(a, now_ns=20)
    assert clock.through == b and clock.since_ns == 10


def test_a_dispatch_told_only_at_its_return_ends_the_interval_there():
    opened = profiler.phase_totals()
    clock = profiler.StarvedClock()
    clock.fetched(clock.dispatched(now_ns=10), now_ns=50)
    clock.dispatched(now_ns=80)
    assert clock.since_ns is None and clock.closed_ns == 30
    assert _gained(opened) == (1, 30)
    assert _gained(opened, "device_launch") == (0, 0)
    # a call that raised was entered and never returned: the next
    # launch is not timed from it
    clock.fetched(now_ns=100)
    clock.launching(now_ns=150)
    clock.fetched(now_ns=200)
    _queue(clock, 260, 300)
    assert clock.closed_ns == 30 + 50 + 60
    assert _gained(opened, "device_launch") == (1, 40)


def test_intervals_reach_the_sinks_a_span_reaches_but_request_traces(
        monkeypatch):
    from mxnet_tpu import tracing
    clock = profiler.StarvedClock()
    seen = []
    monkeypatch.setattr(tracing, "on_phase",
                        lambda name, *stamps: seen.append(name))
    window = profiler.start_step_profile()
    try:
        clock.fetched(_queue(clock, 5, 10), now_ns=50)
        _queue(clock, 80, 95)
        with profiler.phase("starved_test_sunk"):
            pass
    finally:
        profiler.stop_step_profile()
    got = window.snapshot()
    assert got["device_starved"] == {"spans": 1, "ns": 30, "counts": {}}
    assert got["device_launch"] == {"spans": 1, "ns": 15, "counts": {}}
    assert seen == ["starved_test_sunk"]


def test_phase_counts_starved_ns_only_on_a_thread_with_a_clock():
    opened = profiler.phase_totals()
    with profiler.phase("starved_test_plain"):
        pass
    got = profiler.phase_totals(since=opened)["starved_test_plain"]
    assert "starved_ns" not in got["counts"]
    seen = {}

    def work():
        clock = profiler.StarvedClock().install()
        with profiler.phase("starved_test_busy"):
            pass                                    # nothing fetched yet
        clock.fetched(clock.dispatched())
        with profiler.phase("starved_test_starved"):
            pass
        with profiler.phase("starved_test_ended") as span:
            clock.dispatched()
        seen["closed_ns"] = clock.closed_ns
        seen["ended"] = span.counts["starved_ns"]

    t = threading.Thread(target=work)
    t.start()
    t.join(60)
    assert not t.is_alive()
    got = profiler.phase_totals(since=opened)
    assert got["starved_test_busy"]["counts"] == {"starved_ns": 0}
    # the whole of a span that lies inside a starved interval
    whole = got["starved_test_starved"]
    assert whole["counts"]["starved_ns"] == whole["ns"] > 0
    assert 0 < seen["ended"] <= got["starved_test_ended"]["ns"]
    assert got["device_starved"] == {
        "spans": 1, "ns": seen["closed_ns"], "counts": {}}
    assert seen["closed_ns"] >= whole["ns"] + seen["ended"]
    # this thread has no clock still
    assert profiler._thread.starved is None


# ---------------------------------------------------------------------------
# (b) a toy paged engine: the sums nest, the rooflines' counts stand
# ---------------------------------------------------------------------------
LEAVES = ("serve_resolve", "serve_admit", "serve_prepare", "serve_decode",
          "serve_prefill")


def test_paged_engine_accounts_for_its_starved_time():
    """Command A+'s toy of ``tests/test_cohere2_moe.py`` (two classes of
    block, a window): P, Q that shares five blocks of it, P again, one
    after the other, so every count is the same in every run.  The
    counts the four rooflines read are the parent's (commit 77cc4be,
    the same three requests: its ``phase_totals``)."""
    from _cohere2_moe_common import PARAMS, SPEC_IN, STORE_KW
    rs = np.random.RandomState(2)
    P = [int(t) for t in rs.randint(0, 96, 43)]
    Q = P[:40] + [int(t) for t in rs.randint(0, 96, 9)]
    reg = ModelRegistry()
    reg.add_generative_model("co", dict(PARAMS), SPEC_IN, **STORE_KW)
    opened = profiler.phase_totals()
    t0 = time.perf_counter_ns()
    eng = GenerationEngine(reg)
    try:
        for prompt in (P, Q, P):
            eng.submit("co", prompt, max_tokens=8).result(300)
        stats = eng.stats()
    finally:
        eng.close()
    wall_ns = time.perf_counter_ns() - t0
    got = profiler.phase_totals(since=opened)
    starved = got["device_starved"]
    assert starved["spans"] > 0 and starved["ns"] > 0
    clock = eng._starved
    # (close() woke the thread from its wait: that interval stays open)
    assert clock.closed_ns == starved["ns"]
    inside = sum(got[n]["counts"]["starved_ns"] for n in LEAVES)
    ticks = got["serve_tick"]
    # the leaves tile part of the ticks, the ticks part of the run
    assert inside <= ticks["counts"]["starved_ns"] <= starved["ns"]
    assert ticks["counts"]["starved_ns"] <= ticks["ns"]
    assert starved["ns"] <= wall_ns
    # a fork lies inside its serve_prepare, the wait for traffic in none
    assert got["cow_fork"]["counts"]["starved_ns"] \
        <= got["serve_prepare"]["counts"]["starved_ns"]
    assert got["serve_idle"]["counts"]["starved_ns"] == 0
    # one preparation a dispatch, and nothing counted in it but time:
    # one request after the other, no slot adopts late or waits for a
    # sibling's block (PR 42's counts, there once another test of the
    # process has made them, read 0)
    prepare = got["serve_prepare"]
    assert prepare["spans"] == (got["serve_decode"]["spans"]
                                + got["serve_prefill"]["spans"])
    late = {"late_tokens", "late_blocks", "waited"}
    assert set(prepare["counts"]) - late == {"starved_ns"}
    assert not any(prepare["counts"].get(k) for k in late)
    assert stats["prefix_late_tokens"] == stats["prefill_rows_waited"] == 0
    assert stats["cow_forks"] == got["cow_fork"]["spans"] == 6
    # every dispatch was counted, every one fetched or followed by one
    # that was; a wait for traffic is none
    assert clock.through == clock.queued == (
        stats["decode_steps"] + stats["prefills"] + stats["cow_forks"])
    # an interval ends where a dispatch call is entered or a wait
    # opens, and each such call was timed to its return
    launch = got["device_launch"]
    assert 0 < launch["spans"] <= starved["spans"] <= (
        launch["spans"] + got["serve_idle"]["spans"])
    assert launch["ns"] > 0 and launch["counts"] == {}
    # (the totals are the process's: a count that another model's
    # dispatches made in an earlier test is there too, and reads 0)
    decode, prefill = ({k: v for k, v in got[name]["counts"].items()
                        if k != "starved_ns" and (v or k.startswith(
                            ("sample_", "deferred")))}
                       for name in ("serve_decode", "serve_prefill"))
    assert got["serve_decode"]["spans"] == 21
    assert decode == {"rows": 21, "kv_tokens": 1029, "q_tokens": 21,
                      "sample_draw": 0, "sample_topk": 0,
                      "kv_tokens_window": 336}
    assert got["serve_prefill"]["spans"] == 9
    assert prefill == {"width": 18, "deferred": 0, "rows": 9,
                       "kv_tokens": 303, "q_tokens": 53, "sample_draw": 0,
                       "sample_topk": 0, "kv_tokens_window": 136}


def test_a_one_pass_tick_is_one_launch_and_one_fetch():
    """A mix that keeps decode rows and prompt rows in the same ticks,
    through a store that takes the one-pass tick: every busy tick is
    ONE preparation, ONE dispatch call the clock counted and ONE fetch,
    whatever rows it had; a tick with both kinds of row closes a
    ``serve_decode`` AND a ``serve_prefill`` span around that one call,
    each with its own group's rows; and the leaves' starved time still
    nests inside the ticks'."""
    from _paged_common import (_burst_registry, _mixed_requests,
                               _submit_at_once, _watch_ticks)
    reg = _burst_registry("cohere2_moe", pool_blocks=0)
    assert reg.gen_store("m").one_pass
    reqs = _mixed_requests(23, 96)
    opened = profiler.phase_totals()
    eng = GenerationEngine(reg)
    ticks = _watch_ticks(eng)
    fetches, fetch = [], eng._fetch_decode

    def counted(arr):
        fetches.append(1)
        return fetch(arr)

    eng._fetch_decode = counted
    try:
        for f in _submit_at_once(eng, reqs):
            f.result(300)
        stats = eng.stats()
    finally:
        eng.close()
    got = profiler.phase_totals(since=opened)
    clock = eng._starved
    busy = [t for t in ticks if t["dec"] or t["pre"]]
    both = [t for t in busy if t["dec"] and t["pre"]]
    assert both and stats["tick_one_pass"] >= len(both)
    assert stats["tick_programs"] == len(busy) == len(fetches) \
        == got["serve_sample"]["spans"] == got["serve_prepare"]["spans"]
    assert clock.through == clock.queued \
        == len(busy) + stats["cow_forks"]
    decode, prefill = got["serve_decode"], got["serve_prefill"]
    assert decode["spans"] == stats["decode_steps"] \
        == sum(1 for t in busy if t["dec"])
    assert prefill["spans"] == stats["prefills"] \
        == sum(1 for t in busy if t["pre"])
    assert decode["spans"] + prefill["spans"] == len(busy) + len(both)
    assert decode["counts"]["rows"] == decode["counts"]["q_tokens"] \
        == sum(t["dec"] for t in busy)
    assert prefill["counts"]["rows"] == stats["prefill_chunks"]
    assert stats["generated_tokens"] == decode["counts"]["rows"]
    inside = sum(got[n]["counts"]["starved_ns"] for n in LEAVES)
    assert inside <= got["serve_tick"]["counts"]["starved_ns"] \
        <= got["device_starved"]["ns"]
    assert 0 < got["device_launch"]["spans"] <= clock.queued


def test_a_tick_ahead_leaves_the_device_nothing_to_wait_for(monkeypatch):
    """One request alone on a one-pass store whose fetch is SLOW (the
    program's time, on the host's clock): once the loop runs a tick
    ahead every fetch is of the OLDER of two dispatches, so the clock
    opens no interval between the first launch and the last fetch: two
    ``device_starved`` spans (before the first launch, after the last
    fetch) and one launch into an idle device, however many ticks.  Its
    twin, the same store without its model's step over row groups,
    starves once a tick.  Either way the six readers' parts sum to the
    host's account of the gap."""
    from _paged_common import _burst_registry, _without_groups
    rs = np.random.RandomState(7)
    prompt = [int(t) for t in rs.randint(0, 96, 11)]
    readers = {name: harness.load_module(
        BENCH, "layer_metrics/%s.py" % name) for name in READERS}
    for path in ("ahead", "twin"):
        if path == "twin":
            _without_groups(monkeypatch)
        reg = _burst_registry.__wrapped__("deepseek_v3")
        opened = profiler.phase_totals()
        eng = GenerationEngine(reg)
        fetch = eng._fetch_decode

        def slow(arr, fetch=fetch):
            time.sleep(0.005)
            return fetch(arr)

        eng._fetch_decode = slow
        try:
            eng.submit("m", prompt, max_tokens=12).result(300)
            stats = eng.stats()
        finally:
            eng.close()
        got = profiler.phase_totals(since=opened)
        clock = eng._starved
        ticks = stats["tick_programs"]
        # (the one fork: the first write behind a registered tail)
        assert ticks == 3 + 11 and stats["cow_forks"] == 1
        assert clock.queued == clock.through == ticks + 1
        starved, launch = got["device_starved"], got["device_launch"]
        if path == "ahead":
            assert stats["tick_ahead"] == ticks - 1
            assert (starved["spans"], launch["spans"]) == (2, 1)
        else:
            assert (starved["spans"], launch["spans"]) == (ticks + 1, ticks)
            assert got["serve_resolve"]["counts"]["starved_ns"] > 0
        run = {"cell": harness.Cell(CELLS[0], rehearse=True),
               "trace": {"window_s": 1.0, "devices": []},
               "host": {"window_s": 1.0, "traced_phases": got}}
        read = {name: r.read(run) for name, r in readers.items()}
        account_ms = 1e-6 * (starved["ns"] + launch["ns"]) \
            / got["serve_tick"]["spans"]
        parts = sum(read[n] for n in READERS if n.endswith("_ms"))
        assert parts + read["engine.starved_unspanned_pct"] / 100 \
            * account_ms == pytest.approx(account_ms, rel=1e-9)


# ---------------------------------------------------------------------------
# (c) two engines, two clocks
# ---------------------------------------------------------------------------
def test_two_engines_in_one_process_keep_two_clocks():
    from _paged_common import _add_model
    reg = ModelRegistry()
    _add_model(reg, paged=True, prefill_chunk=8)
    opened = profiler.phase_totals()
    busy, quiet = GenerationEngine(reg), GenerationEngine(reg)
    try:
        busy.submit("m", [1, 2, 3, 4, 5], max_tokens=6).result(180)
    finally:
        busy.close()
        quiet.close()
    assert busy._starved is not quiet._starved
    # the engine that served counted its dispatches and its gaps
    assert busy._starved.queued >= 1 + 5 and busy._starved.closed_ns > 0
    # the other one's thread only ever waited for traffic
    assert quiet._starved.queued == 0 and quiet._starved.closed_ns == 0
    assert _gained(opened)[1] == busy._starved.closed_ns


# ---------------------------------------------------------------------------
# (d) the six readers
# ---------------------------------------------------------------------------
BENCH = harness.load_json(harness.ROOT + "/BENCHMARK.json")
READERS = ("engine.starved_pct", "engine.starved_resolve_ms",
           "engine.starved_admit_ms", "engine.starved_prepare_ms",
           "engine.starved_dispatch_ms", "engine.starved_unspanned_pct")
CELLS = ["lfm2-24b-a2b.serve-agent-backlog",
         "command-a-plus.serve-ragmix-backlog"]


def _span(spans, ns, **counts):
    return {"spans": spans, "ns": ns, "counts": counts}


def _run(**without):
    """3 s traced, 50 ticks.  The host's account of the device's gap
    is 180 ms: 140 starved (30 + 10 + 40 + (3 + 1) of it inside the
    five leaves, 5 of serve_prepare's inside its forks, 56 outside
    them) and 40 inside the launches that ended the intervals."""
    phases = {
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
    for name in without.get("phases", ()):
        del phases[name]
    run = {"cell": harness.Cell(CELLS[0], rehearse=True),
           "trace": {"window_s": 3.0, "busy_s": 2.79, "devices": []},
           "host": {"window_s": 30.0, "traced_phases": phases}}
    if without.get("trace"):
        run["trace"] = None
    if without.get("traced"):
        del run["host"]["traced_phases"]
    return run


@pytest.mark.parametrize("name,value", zip(READERS, (
    100 * 0.140 / 3.0, 30 / 50, 10 / 50, 40 / 50, (40 + 3 + 1) / 50,
    100 * 56 / 180)))
def test_reader_on_hand_made_totals(name, value):
    reader = harness.load_module(BENCH, "layer_metrics/%s.py" % name)
    assert reader.read(_run()) == pytest.approx(value, rel=1e-12)
    # None, not 0 and not an error: a rehearsal (no trace), a driver
    # that hands no traced totals, a program from before the clock, a
    # window without a tick
    assert reader.read(_run(trace=True)) is None
    assert reader.read(_run(traced=True)) is None
    assert reader.read(_run(phases=["device_starved"])) is None
    assert reader.read(_run(phases=["serve_tick"])) is None
    # a leaf the window did not hold (no admission in three seconds,
    # no call entered with the device idle) is nothing starved there,
    # not a reason to say nothing
    assert reader.read(_run(phases=["serve_admit"])) is not None
    assert reader.read(_run(phases=["device_launch"])) is not None


def test_the_parts_sum_to_the_hosts_account_of_the_gap():
    """Four ``engine.starved_*_ms`` and the unspanned share of the
    account: ``device_starved`` + ``device_launch`` a tick."""
    read = {name: harness.load_module(
        BENCH, "layer_metrics/%s.py" % name).read(_run())
        for name in READERS}
    account_ms = (140 + 40) / 50
    parts = sum(read[n] for n in READERS if n.endswith("_ms"))
    assert parts + read["engine.starved_unspanned_pct"] / 100 * account_ms \
        == pytest.approx(account_ms, rel=1e-12)


def test_readers_are_listed_for_the_two_cells_that_hand_traced_totals():
    # by name: where an entry stands and which cells join later is the
    # benchmark's to grow
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert set(CELLS) <= set(m["workloads"]) and m["better"] == "lower"
        assert (m["source"], m["layer"], m["moves"]) == (
            "program_span", "Serving planes", "serve_tokens_per_s")
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
