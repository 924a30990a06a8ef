"""reduce/xplane.py on a small recorded trace (data/small_trace.textproto,
worked by hand): busy union, per-op totals, collective exposure, idle
gaps named by the host span over each."""
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
xplane = harness.load_module(BENCH, "reduce/xplane.py")
NAMES = harness.load_json(harness.find_file(BENCH,
                                            "reduce/trace_names.json"))
US = 1e-6


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "small_trace.textproto")) as f:
        profile = ProfileData.from_text_proto(f.read())
    return xplane.reduce_profile(profile, NAMES)


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
