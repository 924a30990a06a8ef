"""Compiled-program cost introspection: the memory and FLOP counts of
one program.

``compiled_cost`` lowers and compiles a jitted callable at concrete
arguments and reads XLA's own ``cost_analysis()`` /
``memory_analysis()``.  ``lower().compile()`` does NOT reuse the jit's
warmed executable — every query pays one fresh XLA compile — so it is a
one-shot diagnostic off the hot path.

Read by ``Executor.program_cost``: ``tests/test_remat_policy.py`` pins
the residual bytes a remat policy saves with it
(docs/architecture/pallas_kernels.md).  What a chip can do at its peak
is not here: that table is ``benchmark/peaks.json``.
"""
from __future__ import annotations

__all__ = ["compiled_cost"]


def compiled_cost(fn, *args, **kwargs):
    """Cost/memory analysis of a jitted callable at concrete args.

    Returns ``{"flops", "temp_bytes", "output_bytes", "argument_bytes"}``
    (entries None/absent where the backend declines) or None when the
    program cannot be lowered — callers treat the column as diagnostic,
    never load-bearing."""
    try:
        compiled = fn.lower(*args, **kwargs).compile()
    except Exception:
        return None
    out = {"flops": None}
    try:
        flops = compiled.cost_analysis().get("flops")
        if flops is not None and float(flops) > 0:
            out["flops"] = float(flops)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
        out["output_bytes"] = int(ma.output_size_in_bytes)
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
    except Exception:
        pass
    return out
