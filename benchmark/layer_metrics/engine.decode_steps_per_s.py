"""Decode steps per second of window: the ``decode_steps`` delta of
``GenerationEngine.stats()``.  Layer: serving planes (the tick loop's
pace sets the gap between tokens)."""


def read(run):
    c = run["counters"]
    if "decode_steps" not in c:
        return None
    return c["decode_steps"] / run["host"]["window_s"]
