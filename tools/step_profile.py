"""Per-step phase breakdown of a ``Module.fit`` loop.

Attributes each training step's wall time to the four fit-loop phases
recorded by the step-phase profiler seam (``mxnet_tpu/profiler.py``):

* ``data_wait``    — blocked on the data iterator (what the DeviceStager
  hides by staging batch t+1 during step t);
* ``h2d_stage``    — host->device upload on the stager thread (OVERLAPS
  compute; reported but excluded from the step percentage base);
* ``compute``      — step dispatch + execution (forward/backward/update);
* ``metric_fetch`` — metric accumulation incl. any host fetch;
* ``spmd_step``    — the one-SPMD-step-program dispatch
  (``parallel/spmd.py``), NESTED inside ``compute``: its share of
  compute shows how much of the step is the sharded program vs frontend
  packing/metric glue (absent when training runs the classic
  executor-group replication path).

This is the diagnostic for a utilization gap: a healthy saturated chip shows
``compute`` ~100% of the step; a fat ``data_wait`` means the input
pipeline starves the MXU (raise staging depth / decode threads), a fat
``metric_fetch`` means per-batch host syncs serialize dispatch.

Usage::

    python tools/step_profile.py                  # smoke fit, report
    python tools/step_profile.py --json           # machine-readable
    python tools/step_profile.py --trace t.json   # aggregate an existing
                                                  # Chrome trace's spans
    python tools/step_profile.py --delay-ms 20    # inject host latency

The smoke fit runs the profiler (Chrome trace) around a tiny synthetic
``Module.fit``, dumps the trace, and aggregates its cat="step_phase"
spans — exercising the same span path a real on-chip investigation uses
(``make step-profile`` keeps the format from rotting in CI).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def smoke_fit(trace_path, batches=8, batch_size=32, delay_ms=0.0):
    """Run a tiny synthetic fit under the Chrome-trace profiler and
    dump the trace to ``trace_path``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.test_utils import smoke_mlp

    sym = smoke_mlp(num_hidden=64)
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (batch_size * batches, 32)).astype("float32")
    y = rs.randint(0, 10, (batch_size * batches,)).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=batch_size)
    if delay_ms > 0:
        from mxnet_tpu.test_utils import DelayedIter
        it = DelayedIter(it, delay=delay_ms / 1e3)

    mod = mx.Module(sym, context=mx.current_context())
    profiler.profiler_set_config(filename=trace_path)
    profiler.profiler_set_state("run")
    try:
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                eval_metric="acc")
        mx.nd.waitall()
    finally:
        profiler.profiler_set_state("stop")
    profiler.dump_profile()
    return trace_path


def render_metrics(snap):
    """Human-readable registry snapshot (``--metrics``): the per-phase
    histograms (count + p50/p95/p99 ms) beside the counters/gauges —
    the AGGREGATE answer next to the phase table's per-step one, from
    the same phase() spans."""
    lines = ["-- metrics registry (mxnet_tpu/metrics.py snapshot) --"]
    hists = snap.get("histograms", {})
    if hists:
        lines.append("%-44s %8s %10s %10s %10s" % (
            "histogram", "count", "p50_ms", "p95_ms", "p99_ms"))
        for name, d in sorted(hists.items()):
            if not d["count"]:
                continue
            lines.append("%-44s %8d %10.3f %10.3f %10.3f" % (
                name, d["count"], (d["p50"] or 0) * 1e3,
                (d["p95"] or 0) * 1e3, (d["p99"] or 0) * 1e3))
    counters = {k: v for k, v in snap.get("counters", {}).items() if v}
    if counters:
        lines.append("counters: " + "  ".join(
            "%s=%d" % kv for kv in sorted(counters.items())))
    gauges = {k: v for k, v in snap.get("gauges", {}).items() if v == v}
    if gauges:
        lines.append("gauges:   " + "  ".join(
            "%s=%g" % kv for kv in sorted(gauges.items())))
    return "\n".join(lines)


def render(report):
    """Human-readable phase table from an aggregated report."""
    lines = []
    lines.append("steps: %d" % report["steps"])
    lines.append("%-14s %8s %9s %12s %7s" % (
        "phase", "spans", "total_ms", "per_step_ms", "pct"))
    for name, row in report["phases"].items():
        pct = "-" if row["pct"] is None else "%.1f%%" % row["pct"]
        lines.append("%-14s %8d %9.2f %12.3f %7s" % (
            name, row["spans"], row["total_ms"], row["per_step_ms"], pct))
    if report.get("overlapped"):
        lines.append("(%s excluded from pct: h2d_stage overlaps compute "
                     "on the stager thread, spmd_step nests inside "
                     "compute as the sharded-program dispatch)"
                     % ", ".join(report["overlapped"]))
    return "\n".join(lines)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="per-step fit phase breakdown from profiler spans")
    parser.add_argument("--trace", help="aggregate an existing Chrome "
                        "trace instead of running the smoke fit")
    parser.add_argument("--json", action="store_true",
                        help="emit the report as one JSON line")
    parser.add_argument("--batches", type=int, default=8)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--delay-ms", type=float, default=0.0,
                        help="inject per-batch host latency into the "
                        "smoke iterator (the faultinject-delay pattern)")
    parser.add_argument("--keep-trace", help="also copy the smoke trace "
                        "to this path")
    parser.add_argument("--metrics", action="store_true",
                        help="also print the metrics-registry snapshot "
                        "(phase histograms + counters) beside the phase "
                        "table — one tool answers both the 'trace' and "
                        "the 'aggregate' question")
    args = parser.parse_args(argv)

    from mxnet_tpu import profiler
    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()

    if args.trace:
        trace = args.trace
    else:
        trace = os.path.join(tempfile.mkdtemp(prefix="mxt_step_profile_"),
                             "step_profile_trace.json")
        t0 = time.time()
        smoke_fit(trace, batches=args.batches,
                  batch_size=args.batch_size, delay_ms=args.delay_ms)
        print("# smoke fit done in %.1fs -> %s" % (time.time() - t0, trace))
    report = profiler.aggregate_phase_trace(trace)
    if args.keep_trace and not args.trace:
        import shutil
        shutil.copy(trace, args.keep_trace)

    missing = [p for p in profiler.PHASES if p not in report["phases"]
               and p not in ("h2d_stage", "data_next", "comm_overlap")]
    if not args.trace and missing:
        # h2d_stage is legitimately absent when MXNET_IO_STAGE=0,
        # data_next only appears when the source is a record pipeline
        # (ThreadedBatchPipeline consumer seam, not NDArrayIter), and
        # comm_overlap only under the dist_mesh bucketed-reduce step;
        # the core fit phases must always be there — CI pins the format
        print("ERROR: phases missing from trace: %s" % missing)
        return 1
    if args.metrics:
        from mxnet_tpu import metrics as _metrics
        report["metrics"] = _metrics.snapshot()
    if args.json:
        print(json.dumps(report))
    else:
        print(render(report))
        if args.metrics:
            print(render_metrics(report["metrics"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
