"""Every cell of BENCHMARK.json through ``run.py --rehearse`` at toy
size (the four-chip cell on four virtual devices): the last line has
the contract's keys, names the CPU and carries no device metric.  The
control — the cell in the lower precision its configuration names —
comes out not correct (fit) or moves the number it has to fail on the
chip (serve); and off a chip the measuring command fails."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
RUN = [sys.executable, os.path.join(harness.ROOT, "benchmark", "run.py")]


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(RUN + list(args), cwd=harness.ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_result_line(cell, trace):
    proc, lines = _run("--workload", cell, "--seed", str(2**31 + 17),
                       "--trace", trace, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(last)
    assert last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == next(
        w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    # a plumbing run measures nothing: no metric, no time on any line
    assert last["metrics"] == {}
    assert "busy_s" not in last["device"] and "breakdown" not in last
    for ln in lines[:-1]:
        for key in json.loads(ln):
            assert not key.endswith(("_s", "_ms", "_per_s")), key
    checks = [json.loads(ln)["checks"] for ln in lines
              if "checks" in json.loads(ln)][0]
    assert all("limit" in c and "value" in c for c in checks)


def test_the_lower_precision_comes_out_not_correct():
    proc, lines = _run("--workload", "resnet50.fit-b128", "--seed", "23",
                       "--rehearse", "--control")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is False


def _flip_gap_mean(lines):
    checks = [json.loads(ln)["checks"] for ln in lines
              if "checks" in json.loads(ln)][0]
    return {c["name"]: c["value"] for c in checks}["flip_gap_mean"]


@pytest.mark.parametrize("seed", ["23", "26"])
def test_the_lower_precision_moves_the_served_decisions(seed):
    """The serve cells' limit is set on the chip at the cell's own size,
    where one-pass bf16 products put the flips of sound runs at 4e-3 ..
    7e-3 and the control's at 2e-2 and more (PERF.md).  At toy size on
    the CPU float32 IS the reference's arithmetic, so the sound run has
    no flip and reads 0, and the control, the same seed with int8
    weights, must not."""
    cell = "lm2048.serve-chat-backlog"
    sound = _run("--workload", cell, "--seed", seed, "--rehearse")[1]
    control = _run("--workload", cell, "--seed", seed, "--rehearse",
                   "--control")[1]
    assert _flip_gap_mean(sound) <= 1e-6
    assert _flip_gap_mean(control) >= 5e-3


def test_no_result_without_the_chip():
    proc, lines = _run("--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not lines
    assert "TPU" in proc.stderr


def test_open_loop_arrivals_through_the_serve_driver():
    """No cell offers load at a rate yet (PERF.md, Open questions); the
    driver's open-loop path is kept driven here: due-time latencies of
    every request due in the window, nothing missed, tokens correct."""
    import argparse
    import time
    cell = harness.Cell("lm2048.serve-chat-backlog", rehearse=True)
    cell.traffic = dict(cell.traffic,
                        arrival={"kind": "poisson", "rate": 10.0})
    harness.REHEARSAL = True
    try:
        out = cell.driver().run(
            cell, harness.devices_for(1, True),
            argparse.Namespace(seed=2**31 + 19, seconds=2.0, trace=0),
            time.perf_counter())
    finally:
        harness.REHEARSAL = False
    assert {"ttft_p95_ms", "itl_p95_ms", "setup_s"} <= set(
        out["end_to_end"])
    assert out["attempted"] > 5 and out["failed"] == 0
    assert len(out["host"]["late_ms"]) == out["attempted"]
    assert all(c["ok"] for c in out["checks"]), out["checks"]
