"""reduce/xplane.py on a small recorded trace (data/small_trace.textproto,
worked by hand): busy union, per-op totals, collective exposure, idle
gaps named by the host span over each; and on one with the traced window
marked in it (data/window_trace.textproto): everything clipped to the
span, the two edge gaps, no reading from a run's file without the span."""
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
xplane = harness.load_module(BENCH, "reduce/xplane.py")
NAMES = harness.load_json(harness.find_file(BENCH,
                                            "reduce/trace_names.json"))
US = 1e-6


def _profile(name):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", name)) as f:
        return ProfileData.from_text_proto(f.read())


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_profile(_profile("small_trace.textproto"), NAMES)


def test_interval_arithmetic():
    assert xplane.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [[0, 2.5], [3, 4]]
    assert xplane.length([[0, 2.5], [3, 4]]) == 3.5
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 7]]) == 7
    assert xplane.subtract([[0, 2], [4, 6]], [[1, 5]]) == 2
    assert xplane.subtract([[0, 2]], []) == 2


def test_busy_is_the_union_not_the_sum(reduced):
    d0, d1 = reduced["devices"]
    assert (d0["id"], d1["id"]) == (0, 1)
    # 0..900 + 1500..1700 + 1800..2000 us; the events sum to 1500 us
    assert d0["busy_s"] == pytest.approx(1300 * US)
    assert sum(d0["ops"].values()) == pytest.approx(1500 * US)
    assert d1["busy_s"] == pytest.approx(1000 * US)
    assert reduced["busy_s"] == pytest.approx(1150 * US)
    # no host window given: first to last operation of the first device
    assert reduced["window_s"] == pytest.approx(2000 * US)


def test_op_totals_and_modules(reduced):
    d0 = reduced["devices"][0]
    assert d0["ops"]["fusion.1"] == pytest.approx(600 * US)
    assert d0["ops"]["all-reduce.3"] == pytest.approx(600 * US)
    assert d0["modules"]["jit_train_step(123)"][0] == 2
    assert reduced["device_ops"][0][1] == pytest.approx(600 * US)
    assert {name for name, _ in reduced["device_ops"]} == {
        "fusion.1", "all-reduce.3", "convolution.7"}


def test_collective_exposure(reduced):
    d0, d1 = reduced["devices"]
    assert d0["collective_s"] == pytest.approx(600 * US)
    # 500..600 hides under the convolution: 300 + 200 us exposed
    assert d0["collective_exposed_s"] == pytest.approx(500 * US)
    # on the second device the all-reduce runs wholly under a fusion
    assert d1["collective_exposed_s"] == pytest.approx(0.0)


def test_idle_gaps_take_the_innermost_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    # 900..1500 us: its middle (1200) lies in fit.callback inside fit
    assert gaps["fit.callback"] == pytest.approx(600 * US)
    # 1700..1800 us: middle 1750 is past both -> no benchmark span (the
    # long host event is not one of the benchmark's)
    assert gaps["no benchmark span"] == pytest.approx(100 * US)
    assert "not a benchmark span" not in gaps


def test_layer_metric_readers_on_the_trace(reduced):
    run = {"trace": dict(reduced, window_s=2000 * US)}
    step = harness.load_module(BENCH, "layer_metrics/step.device_ms.py")
    assert step.read(run) == pytest.approx(0.65)      # 1300 us / 2
    coll = harness.load_module(
        BENCH, "layer_metrics/device.collective_exposed_pct.py")
    assert coll.read(run) == pytest.approx(100 * 250 / 2000)
    assert step.read({"trace": None}) is None
    assert coll.read({"trace": {"devices": [], "window_s": 1}}) is None


def test_no_device_plane_reads_nothing():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }')
    out = xplane.reduce_profile(profile, NAMES, window_s=1.0)
    assert out["busy_s"] is None and out["devices"] == []


# ---------------------------------------------------------------------------
# the traced window is the span "bench.window", not the file
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cut():
    # the host stamps' difference, a few microseconds off the span's
    return xplane.reduce_profile(_profile("window_trace.textproto"), NAMES,
                                 window_s=2998 * US)


def test_the_window_is_the_span_and_busy_lies_inside_it(cut):
    assert NAMES["window_span"] == "bench.window"
    assert cut["window_s"] == pytest.approx(3000 * US)
    assert cut["window_host_s"] == pytest.approx(2998 * US)
    d0, d1 = cut["devices"]
    # device 0: 1200..2200 + 3000..3600 us; what ran before 1000 and
    # after 4000 us is not the window's
    assert d0["busy_s"] == pytest.approx(1600 * US)
    # device 1: 1000..1500 + 2000..2500 + 3800..4000 us, both edges cut
    # (700..1000 us before, 4000..4600 us after)
    assert d1["busy_s"] == pytest.approx(1200 * US)
    assert cut["busy_s"] == pytest.approx(1400 * US)
    for d in cut["devices"]:
        assert 0 < d["busy_s"] <= cut["window_s"]
    assert cut["busy_s"] <= cut["window_s"]


def test_op_totals_and_modules_are_the_clipped_ones(cut):
    d0, d1 = cut["devices"]
    assert d0["ops"] == {"fusion.1": pytest.approx(600 * US),
                         "all-reduce.3": pytest.approx(500 * US),
                         "convolution.7": pytest.approx(600 * US)}
    assert d1["ops"] == {"fusion.1": pytest.approx(400 * US),
                         "all-reduce.3": pytest.approx(600 * US),
                         "convolution.7": pytest.approx(500 * US)}
    # an execution counts where it STARTS inside the window; its
    # seconds are clipped whichever edge it crosses
    assert d0["modules"]["jit_step(1)"] == (2, pytest.approx(1600 * US))
    assert d1["modules"]["jit_step(1)"] == (2, pytest.approx(1200 * US))
    assert dict(cut["device_ops"]) == {
        "fusion.1": pytest.approx(600 * US),
        "convolution.7": pytest.approx(600 * US),
        "all-reduce.3": pytest.approx(500 * US)}


def test_collective_exposure_is_clipped(cut):
    d0, d1 = cut["devices"]
    assert d0["collective_s"] == pytest.approx(500 * US)
    assert d0["collective_exposed_s"] == pytest.approx(400 * US)
    # 1300..1500 and 3800..3900 us: the fusions hide the rest, and
    # 4000..4300 us is past the window
    assert d1["collective_s"] == pytest.approx(600 * US)
    assert d1["collective_exposed_s"] == pytest.approx(300 * US)


def test_the_edge_gaps_make_the_gaps_add_up(cut):
    gaps = dict(cut["idle_gaps"])
    assert gaps == {
        "engine.submit": pytest.approx(200 * US),      # 1000..1200 us
        "no benchmark span": pytest.approx(800 * US),  # 2200..3000 us
        "loadgen.sleep": pytest.approx(400 * US)}      # 3600..4000 us
    first = cut["devices"][0]
    assert sum(gaps.values()) == pytest.approx(
        cut["window_s"] - first["busy_s"])


def test_a_runs_file_without_the_span_reads_nothing():
    # a caller with a window of its own (window_s) and no span in the
    # file: no busy_s and the reason, never the whole file's sum
    out = xplane.reduce_profile(_profile("small_trace.textproto"), NAMES,
                                window_s=2000 * US)
    assert out["busy_s"] is None and out["devices"] == []
    assert "bench.window" in out["reason"]


def test_device_trace_marks_its_window(capsys):
    """``DeviceTrace`` on the CPU: the span is in the file and as long
    as the host stamps are apart (to a millisecond on the chip, where
    ``DeviceTrace.reduce`` says any disagreement; this shared CPU may
    take a thread off between two lines, so 20 ms here); with no device
    plane the reduction reads nothing and says why."""
    import time
    from jax.profiler import ProfileData
    trace = harness.DeviceTrace()
    trace.start()
    time.sleep(0.05)
    trace.stop()
    profile = ProfileData.from_file(xplane.find_xplane(trace.dir))
    spans = [ev.duration_ns * 1e-9 for plane in profile.planes
             for line in plane.lines for ev in line.events
             if ev.name == NAMES["window_span"]]
    assert len(spans) == 1
    assert spans[0] == pytest.approx(trace.t_stop - trace.t_start,
                                     abs=2e-2)
    out = trace.reduce(BENCH)
    assert out["busy_s"] is None and not os.path.exists(trace.dir)
    assert '"trace_unread": "no device plane in the trace"' in \
        capsys.readouterr().out
