"""Host span and device planes line up: the one thing about the traced
window that only the chip can show.  Run BY HAND on the chip,

    python3 benchmark/tests/test_window_on_chip.py

(under pytest this directory's conftest names the CPU, where the test
skips).  A second thread keeps ONE device saturated, a chain of
dependent matrix products kept several deep in its queue, across
``DeviceTrace.start()`` ... 3 s ... ``stop()`` and on through
``stop_trace``; its last result is fetched only after ``stop()`` has
returned.  Cut at the span ``bench.window`` the device reads busy for
all but the gaps between operations, ``0.97 <= busy_s / window_s <= 1``.
The file holds device work from outside the span too (the feeder ran
before ``start()`` and runs on after ``stop()``): the whole file's busy
seconds, summed here and nowhere else, are the larger, and the script
prints them over the host stamps' difference beside the cut reading
(PERF.md section 6, PR 46, has both)."""
import collections
import json
import os
import re
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SECONDS, DEPTH, WIDTH, PRODUCTS = 3.0, 8, 4096, 8


def whole_file_busy_s(trace, bench):
    """The first device's busy seconds over the WHOLE file, the span
    ignored: the sum that was divided by the host stamps' difference
    before the reducer cut at the window."""
    from jax.profiler import ProfileData
    from benchmark import harness
    xplane = harness.load_module(bench, "reduce/xplane.py")
    names = harness.load_json(harness.find_file(
        bench, "reduce/trace_names.json"))
    planes = {p.name: p for p in
              ProfileData.from_file(xplane.find_xplane(trace.dir)).planes
              if re.match(names["device_plane"], p.name)}
    return xplane.length(xplane.merge(
        (start, end) for _, start, end in
        xplane._events(planes[min(planes)], names["op_lines"])))


def saturated_window():
    """The reduced trace of 3 s of a device that never idles, and the
    whole file's busy seconds."""
    import jax
    import jax.numpy as jnp
    from benchmark import harness
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w = jnp.full((WIDTH, WIDTH), 1.0 / WIDTH, jnp.bfloat16)

    @jax.jit
    def step(x):
        return jax.lax.fori_loop(0, PRODUCTS, lambda _, y: y @ w, x)

    x = step(jnp.ones((WIDTH, WIDTH), jnp.bfloat16))
    x.block_until_ready()                       # compiled, outside
    done, last = threading.Event(), []

    def feed(x):
        queued = collections.deque()
        while not done.is_set():
            x = step(x)
            queued.append(x)
            if len(queued) > DEPTH:
                queued.popleft().block_until_ready()
        last.append(x)

    feeder = threading.Thread(target=feed, args=(x,), name="saturate")
    feeder.start()
    time.sleep(0.5)
    trace = harness.DeviceTrace()
    trace.start()
    time.sleep(SECONDS)
    trace.stop()                # the feeder goes on through stop_trace
    done.set()
    feeder.join()
    assert float(last[0][0, 0]) == 1.0          # fetched after stop()
    whole_s = whole_file_busy_s(trace, bench)
    return trace.reduce(bench), whole_s


def test_saturated_device_reads_busy_inside_the_window():
    import jax
    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs the chip: run this file by hand there")
    got, whole_s = saturated_window()
    cut = got["busy_s"] / got["window_s"]
    print(json.dumps({"busy_s": got["busy_s"], "window_s": got["window_s"],
                      "busy_over_window": cut,
                      "whole_file_busy_s": whole_s,
                      "window_host_s": got["window_host_s"],
                      "uncut_over_host_window":
                          whole_s / got["window_host_s"],
                      "idle_gaps": got["idle_gaps"]}), flush=True)
    assert abs(got["window_s"] - got["window_host_s"]) < 1e-3
    assert 0.97 <= cut <= 1.0
    # the file holds device work outside the span, and the cut left it
    # out: with nothing outside, the two sums would be equal
    assert whole_s > got["busy_s"]


if __name__ == "__main__":
    test_saturated_device_reads_busy_inside_the_window()
