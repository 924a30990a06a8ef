"""Share of the window's wall time that ``Module.fit`` spent blocked on
the iterator: the ``data_wait`` spans of the program's own
``profiler.start_step_profile`` collector (host clock), over the
window.  Layer: input (``io/``, ``io/stager.py``)."""

PHASE = "data_wait"


def read(run):
    phases = run["host"].get("phase_ns")
    if not phases or PHASE not in phases:
        return None
    return 100.0 * phases[PHASE] * 1e-9 / run["host"]["window_s"]
