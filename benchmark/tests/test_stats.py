"""Percentiles, due-time latency, miss accounting and the served
tokens' decisions on hand-made values; peaks.json refuses a device it
does not know."""
import pytest

from benchmark import harness

serve = harness.load_module(harness.load_json(
    harness.ROOT + "/BENCHMARK.json"), "drivers/serve.py")


def test_percentile_interpolates():
    assert harness.percentile([4, 1, 3, 2], 50) == 2.5
    assert harness.percentile([10], 95) == 10
    assert harness.percentile(list(range(101)), 95) == 95
    assert harness.percentile([0, 10], 95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def test_latency_is_timed_from_the_due_time():
    # due at 1.0, first token at 1.3 however late it was sent
    ttft, itl, missed = serve.latency_summary(
        [(1.0, [1.3, 1.35, 1.45]), (2.0, [2.1])], t_settled=9.0)
    assert missed == 0
    assert ttft == pytest.approx([300.0, 100.0])
    assert itl == pytest.approx([50.0, 100.0])


def test_a_miss_counts_as_the_worst():
    ttft, itl, missed = serve.latency_summary(
        [(1.0, [1.2]), (2.0, None), (3.0, [])], t_settled=4.0)
    assert missed == 2
    # the unfinished waited at least until the settle time ran out
    assert sorted(ttft) == pytest.approx([200.0, 2000.0, 2000.0])
    ttft, _, _ = serve.latency_summary(
        [(1.0, [6.0]), (3.5, None)], t_settled=4.0)
    assert sorted(ttft) == pytest.approx([5000.0, 5000.0])


def test_a_repeated_decision_counts_once():
    import numpy as np
    # a loop round one near-tie (7 served where the reference puts 5),
    # a second decision at the same pair of tokens in another request
    gap = [0.0, 0.25, 0.0, 0.5, 0.0, 0.25]
    best = [3, 5, 3, 5, 3, 5]
    served = [3, 7, 3, 7, 3, 7]
    assert sorted(serve.decision_gaps(gap, best, served)) == [
        (0.0, False), (0.5, True)]
    sample = [([1], served), ([1], [9, 9])]
    gaps = [(np.array(gap), np.array(best)),
            (np.array([0.0, 0.125]), np.array([9, 8]))]
    checks = {c["name"]: c for c in serve.compare(sample, gaps)}
    # four decisions, two of them flips (0.5 and 0.125), averaged as
    # FLIPS_MIN of them while they are fewer
    assert checks["flip_gap_mean"]["value"] == pytest.approx(
        0.625 / serve.FLIPS_MIN)
    assert checks["token_gap_max"]["value"] == 0.5
    assert not checks["flip_gap_mean"]["ok"]
    many = [(np.full(40, 0.02), np.arange(40))]
    checks = {c["name"]: c for c in serve.compare(
        [([1], list(range(1, 41)))], many)}
    assert checks["flip_gap_mean"]["value"] == pytest.approx(0.02)
    assert not serve.compare([], [])[0]["ok"]


def test_tokens_in_window_is_half_open():
    rows = [(0.0, [0.9, 1.0, 1.5, 2.0]), (0.0, None), (0.0, [1.99])]
    assert serve.tokens_in(rows, 1.0, 2.0) == 3


def test_check_limit_and_nan():
    assert harness.check("x", 0.5, 1.0)["ok"]
    assert not harness.check("x", 1.5, 1.0)["ok"]
    assert not harness.check("x", float("nan"), 1.0)["ok"]
    assert harness.check("exact", 0, 0)["ok"]


def test_peaks_known_and_unknown_kind():
    cell = harness.Cell("resnet50.fit-b128")
    peaks = cell.peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="peaks.json"):
        cell.peaks("TPU v7 imaginary")
    with pytest.raises(harness.BenchError, match="peaks.json"):
        cell.peaks("cpu")


def test_cells_find_their_metrics_by_name():
    fit = harness.Cell("resnet50.fit-dp4-b512")
    assert {m["name"] for m in fit.end_to_end} == {
        "train_samples_per_s", "setup_s"}
    assert "device.collective_exposed_pct" in {
        m["name"] for m in fit.per_layer}
    one = harness.Cell("resnet50.fit-b128")
    assert "device.collective_exposed_pct" not in {
        m["name"] for m in one.per_layer}
    serve = harness.Cell("lm2048.serve-chat-backlog")
    assert {m["name"] for m in serve.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    for cell in (fit, one, serve):
        for m in cell.per_layer:
            assert hasattr(harness.load_module(
                cell.bench, "layer_metrics/%s.py" % m["name"]), "read")
    with pytest.raises(harness.BenchError):
        harness.Cell("no.such-cell")
