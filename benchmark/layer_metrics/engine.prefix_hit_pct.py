"""Share of the admitted prompts' tokens that came from the prefix
cache: ``prefix_hit_tokens / prompt_tokens_admitted``, deltas of
``GenerationEngine.stats()`` over the window.  For a model with a
per-sequence state every hit also restored that state from the last
adopted block's row (``state_restores`` counts them).  None from a
program without the second counter.  Layer: serving planes (the prefix
store, ``decode_engine.py``)."""


def read(run):
    c = run["counters"]
    if not c.get("prompt_tokens_admitted"):
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prompt_tokens_admitted"]
