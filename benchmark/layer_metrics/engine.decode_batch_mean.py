"""Tokens produced per decode step: ``generated_tokens / decode_steps``,
deltas of ``GenerationEngine.stats()`` over the window.  Layer: serving
planes (continuous batching's occupancy)."""


def read(run):
    c = run["counters"]
    if not c.get("decode_steps"):
        return None
    return c["generated_tokens"] / c["decode_steps"]
