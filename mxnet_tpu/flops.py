"""Compiled-program cost introspection: model FLOPs and memory.

The MFU columns in ``bench.py`` were analytic (hand-counted network
FLOPs); this module reads them from the COMPILED program instead —
``jitted.lower(*args).compile()`` then ``cost_analysis()`` /
``memory_analysis()`` — so the numerator of every MFU claim is what XLA
actually scheduled, on any backend.  ``lower().compile()`` does NOT
reuse the jit's warmed executable — every cost query pays one fresh XLA
compile — so callers treat this as a one-shot diagnostic off the hot
path (bench rows ask once per row; the persistent
``JAX_COMPILATION_CACHE_DIR`` cache, when set, does absorb it).

Consumers: ``DataParallelTrainer.step_cost_analysis`` /
``Executor.program_cost`` (the per-plane accessors), ``bench.py``'s
fit/direct/transformer rows, and ``tools/step_profile.py``'s MFU-proxy
column.
"""
from __future__ import annotations

__all__ = ["compiled_cost", "peak_bf16_flops", "mfu_proxy",
           "PEAK_BF16_FLOPS"]

# Peak dense bf16 FLOP/s per JAX device, keyed by device_kind substring
# (bench.py's chip table reads this — single source for the MFU
# denominator).
PEAK_BF16_FLOPS = [("v6e", 918e12), ("v6", 918e12), ("v5p", 459e12),
                   ("v5litepod", 197e12), ("v5 lite", 197e12),
                   ("v5e", 197e12), ("v4", 275e12), ("v3", 61.4e12),
                   ("v2", 22.5e12)]


def peak_bf16_flops(device_kind):
    """Table peak bf16 FLOP/s for a PJRT device_kind.  The CPU platform
    gets None (its rows report the FLOP rate without an MFU claim); an
    accelerator that is not in the table is an error, not a default."""
    k = str(device_kind).lower().replace("_", " ")
    if k == "cpu":
        return None
    for key, val in PEAK_BF16_FLOPS:
        if key in k:
            return val
    raise ValueError("device_kind %r is not in the peak-FLOPs table; add "
                     "its peak bf16 FLOP/s to mxnet_tpu/flops.py"
                     % (device_kind,))


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


def mfu_proxy(flops_per_step, steps_per_sec, peak_flops, n_devices=1):
    """Measured-FLOPs MFU: compiled-program FLOPs per step over measured
    step rate, against table peak.  None when either side is unknown."""
    if not flops_per_step or not steps_per_sec or not peak_flops:
        return None
    return round(flops_per_step * steps_per_sec /
                 (peak_flops * max(1, n_devices)), 4)
