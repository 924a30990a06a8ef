"""Driver ``serve-window``: ``drivers/serve-hybrid.py`` for a model whose
cache blocks come in classes, some behind a window, every request due
at 0.  That driver's ``run`` is imported whole, not copied: the ramp,
the window, the slices, the traced totals, the seeded weights and the
reference's pass are its.  What differs is DATA:

* the limits of ``correct`` are the configuration's (``limits`` in its
  file, beside the readings each was set from), so the next
  configuration of this kind brings a file and no driver;
* the engine's counters of the block classes are read with the others
  (``WINDOW_COUNTERS``; a program without them leaves them out);
* a run says what share of the admitted prompt tokens came from the
  prefix store in every ``SLICE_S`` seconds of its window
  (``prefix_hit_pct_by_slice``, from the ``serve_admit`` spans'
  counts), so that it shows whether the window lay past the first
  generation's prompts, which fill the store.
"""
from __future__ import annotations

import importlib.util
import threading
import time

from benchmark import harness

# deltas of GenerationEngine.stats() over the window, beside
# serve-hybrid's
WINDOW_COUNTERS = ("window_blocks_released", "prefix_hits_cut",
                   "prefix_evictions", "cache_bytes_live",
                   "cache_bytes_one_table")
SAMPLE_S = 0.5


def _hybrid(cell):
    """``drivers/serve-hybrid.py`` as a module of this cell's own: its
    ``LIMITS`` and ``COUNTERS`` are replaced below, and the copy other
    cells import stays what it is."""
    path = harness.find_file(cell.bench, "drivers/serve-hybrid.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_drivers_serve_hybrid_of_serve_window", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _admitted():
    """(prefix_hit_tokens, prompt_tokens) over the process's
    ``serve_admit`` spans, None from a program that does not count
    both."""
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    counts = profiler.phase_totals().get("serve_admit", {}).get("counts",
                                                                 {})
    if "prompt_tokens" not in counts:
        return None
    return counts.get("prefix_hit_tokens", 0), counts["prompt_tokens"]


def run(cell, devices, args, t0):
    hybrid = _hybrid(cell)
    limits = cell.config.get("limits")
    if not limits or set(limits) != set(hybrid.LIMITS):
        raise harness.BenchError(
            "driver serve-window takes %s from the configuration's "
            "'limits'" % sorted(hybrid.LIMITS))
    hybrid.LIMITS = {k: float(v) for k, v in limits.items()}
    hybrid.COUNTERS = hybrid.COUNTERS + WINDOW_COUNTERS
    readings, stop = [], threading.Event()

    def sample():
        while not stop.wait(SAMPLE_S):
            got = _admitted()
            if got is not None:
                readings.append((time.perf_counter(), got))

    sampler = threading.Thread(target=sample, name="bench-admitted")
    sampler.start()
    try:
        out = hybrid.run(cell, devices, args, t0)
    finally:
        stop.set()
        sampler.join()
    # the window opened setup_s after the process started
    t_open = t0 + out["end_to_end"]["setup_s"]
    seconds = float(args.seconds)
    marks = [t_open + min(k * hybrid.SLICE_S, seconds) for k in range(
        int(-(-seconds // hybrid.SLICE_S)) + 1)]
    at = [min(readings, key=lambda r: abs(r[0] - m))[1] for m in marks] \
        if readings else []
    harness.say(prefix_hit_pct_by_slice=[
        100.0 * (b[0] - a[0]) / (b[1] - a[1]) if b[1] > a[1] else None
        for a, b in zip(at, at[1:])])
    return out
