#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each run; inputs and weights come from ``--seed``; the
last line of standard output is the result object.  Without the TPU
chips the cell asks for the exit code is 1 and no result is printed.
``--rehearse`` is the plumbing run: toy sizes on the CPU by name, counts
only, nothing measured.  ``--control`` runs the cell in the lower
precision its configuration names under ``control`` and has to come out
``correct: false`` (the builder's readings and the tests use it; the
driver never does).
"""
import os
import sys
import time

T0 = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    try:
        cell = harness.Cell(args.workload, rehearse=args.rehearse,
                            control=args.control)
        if args.rehearse:
            harness.REHEARSAL = True
            # must be set before jax is imported
            os.environ["JAX_PLATFORMS"] = "cpu"
            if cell.chips > 1:
                os.environ["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "") +
                    " --xla_force_host_platform_device_count=%d"
                    % cell.chips)
        if args.seconds is None:
            args.seconds = float(cell.traffic.get(
                "seconds", cell.bench["run_seconds"]))
        devices = harness.devices_for(cell.chips, args.rehearse)
        if not args.rehearse:
            harness.say(compile_cache=harness.use_compile_cache())
        out = cell.driver().run(cell, devices, args, T0)
        return harness.finish(cell, devices, out, bool(args.trace),
                              args.rehearse)
    except harness.BenchError as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
