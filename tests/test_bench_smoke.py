"""bench.py smoke: the harness plumbing must hold on the CPU (exit 0,
well-formed JSON, no errored row — an errored row now fails the sweep),
and throughput must stay within tolerance of the banked CPU baseline so
a hot-loop regression shows up without a chip.
"""
import json
import os
import platform
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _load_baseline():
    # committed by tools/bank_cpu_baseline.py; its env dict IS the smoke
    # protocol — one source of truth for both banking and gating
    with open(os.path.join(ROOT, "BENCH_cpu_baseline.json")) as f:
        return json.load(f)


def _run_sweep(env):
    proc = subprocess.run([sys.executable, "bench.py"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, proc.stdout[-2000:]
    return json.loads(lines[-1])


def test_bench_smoke_rows():
    baseline = _load_baseline()
    env = dict(os.environ)
    env.update(baseline["env"])
    out = _run_sweep(env)
    for key in ("metric", "value", "unit", "vs_baseline", "rows"):
        assert key in out, key
    assert out["smoke"] is True
    metrics = {r["metric"]: r for r in out["rows"]}
    for m in ("train.resnet-50.trainer_direct",
              "train.resnet-50.module_fit"):
        assert m in metrics, sorted(metrics)
        assert metrics[m].get("unit") != "error", metrics[m]
        assert metrics[m]["value"] > 0
    # drain-bounded timing: fused fit and direct trainer run the same
    # tiny net; the ratio must be same-order, not the 20x dispatch-rate
    # artifact the async callback clock used to produce
    ratio = out["fit_vs_direct"]
    # steady-state parity is ~1.0 (the old 0.55 readings were the
    # metric-accumulator compile landing inside a warmup=1 window);
    # bounds stay loose only for 1-core host noise
    assert ratio is not None and 0.5 < ratio < 2.0, ratio
    assert "fit_vs_direct_note" in out

    # perf-regression gate vs the banked CPU baseline.  Absolute
    # images/sec only compares like-for-like on the same host class the
    # baseline was banked on — elsewhere the plumbing assertions above
    # still ran, so don't turn a hardware change into a red suite.
    host = {"machine": platform.machine(), "cpu_count": os.cpu_count()}
    if host != baseline["host"]:
        pytest.skip("perf gate skipped: host %s != banking host %s — "
                    "re-bank via tools/bank_cpu_baseline.py" %
                    (host, baseline["host"]))
    tol = baseline["tolerance"]

    def below_floor(rows):
        bad = []
        for name, ref in baseline["rows"].items():
            if not ref["gated"]:
                continue
            assert name in rows, (name, sorted(rows))
            if rows[name]["value"] < ref["median"] * tol:
                bad.append("%s at %.1f %s vs banked %.1f (floor %.1f)"
                           % (name, rows[name]["value"], ref["unit"],
                              ref["median"], ref["median"] * tol))
        return bad

    bad = below_floor(metrics)
    if bad:
        # a genuine hot-loop regression reproduces; transient host
        # contention (this is a 1-core box) does not — measure once more
        # before declaring the regression real
        retry = {r["metric"]: r for r in _run_sweep(env)["rows"]}
        bad = below_floor(retry)
    assert not bad, (
        "perf regression vs banked CPU baseline (reproduced on retry): "
        "%s. If this slowdown is expected, re-bank via "
        "tools/bank_cpu_baseline.py." % "; ".join(bad))
