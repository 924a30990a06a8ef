"""Bank a CPU smoke-sweep perf baseline into BENCH_cpu_baseline.json.

Without a chip at hand NO perf signal of any kind gates the hot loop: a
2-3x regression in the fused step would sail through a green suite.
This tool runs the
exact configuration ``tests/test_bench_smoke.py`` runs (same rows,
iters, warmup, platform) several times and banks the per-row MEDIAN, so
the smoke test can fail any future run whose throughput drops below
``tolerance`` of the banked number on comparable hardware.

Usage:  python tools/bank_cpu_baseline.py [--runs 3]
Re-run (and commit the result) after any deliberate perf-relevant
change to the hot path, or when moving to a different host class.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "BENCH_cpu_baseline.json")

# THE smoke protocol: banked into the baseline file, and read back from
# there by tests/test_bench_smoke.py — one source of truth, no drift.
SMOKE_ENV = {"JAX_PLATFORMS": "cpu", "BENCH_SMOKE": "1",
             # 4 iters: at 2, fixed epoch costs (epoch-end metric drain)
             # dominate the fit row and fit_vs_direct reads ~0.55 even
             # though steady state is ~1.0 (measured over 40 iters)
             # warmup 2: the device-metric accumulator jit-compiles at
             # batch 2; with warmup 1 that compile lands inside the
             # measured window and distorts the fit row
             "BENCH_ITERS": "4", "BENCH_WARMUP": "2",
             "BENCH_ROWS": "train.resnet-50,lstm,comm",
             # single-device protocol, pinned against ambient XLA_FLAGS
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
# images/sec rows are gated; bandwidth is recorded but not gated (host
# memory bandwidth varies too much across machine classes)
GATED_UNITS = ("images/sec", "samples/sec")


def run_sweep():
    env = dict(os.environ)
    env.update(SMOKE_ENV)
    proc = subprocess.run([sys.executable, "bench.py"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=560)
    if proc.returncode != 0:
        raise RuntimeError("bench.py failed: %s" % proc.stderr[-2000:])
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.6,
                    help="smoke test fails a gated row below "
                         "tolerance * baseline (0.6 = 40%% slack)")
    args = ap.parse_args(argv)

    samples = {}
    units = {}
    for i in range(args.runs):
        out = run_sweep()
        for row in out["rows"]:
            if row.get("unit") == "error":
                raise RuntimeError("error row in sweep: %s" % row)
            samples.setdefault(row["metric"], []).append(row["value"])
            units[row["metric"]] = row["unit"]
        print("# run %d/%d: %s" % (
            i + 1, args.runs,
            {m: round(v[-1], 1) for m, v in samples.items()}), flush=True)

    banked = {
        "comment": "CPU smoke-sweep perf baseline; see "
                   "tools/bank_cpu_baseline.py for protocol and "
                   "tests/test_bench_smoke.py for the gate",
        "env": SMOKE_ENV,
        "runs": args.runs,
        "tolerance": args.tolerance,
        "host": {"machine": platform.machine(),
                 "cpu_count": os.cpu_count()},
        "banked_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": {m: {"median": round(statistics.median(v), 2),
                     "samples": [round(x, 2) for x in v],
                     "unit": units[m],
                     "gated": units[m] in GATED_UNITS}
                 for m, v in samples.items()},
    }
    with open(OUT, "w") as f:
        json.dump(banked, f, indent=1)
        f.write("\n")
    print("banked -> %s" % OUT)


if __name__ == "__main__":
    main()
