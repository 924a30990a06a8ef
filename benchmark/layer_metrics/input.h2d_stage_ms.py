"""The stager's busy time a step: the ``h2d_stage`` spans of the
window collector the fit driver opens (``profiler.start_step_profile``,
host clock; the producer thread's spans land in it), over the window's
steps.  It overlaps the step (77 MB a step on one chip, 308 MB on
four), so beside ``step.device_ms`` it says how far the input is from
setting the pace.  Layer: input (``io/stager.py``)."""

PHASE = "h2d_stage"


def read(run):
    phases = run["host"].get("phase_ns")
    steps = run["counters"].get("steps")
    if not phases or PHASE not in phases or not steps:
        return None
    return 1e-6 * phases[PHASE] / steps
