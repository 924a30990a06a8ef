"""Device time of one training step: busy time (union of device-op
intervals) on the first device over the step programs traced there.
Layer: step program (``parallel/spmd.py``, ``parallel/dp.py``)."""

# the jitted step as the profiler's "XLA Modules" line names it
STEP_MODULE = "jit_train_step"


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    first = trace["devices"][0]
    steps = sum(count for name, (count, _) in first["modules"].items()
                if name.startswith(STEP_MODULE))
    if not steps:
        return None
    return 1e3 * first["busy_s"] / steps
