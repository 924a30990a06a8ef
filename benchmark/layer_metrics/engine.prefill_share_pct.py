"""Prefill's share of the paged step programs' device time: seconds of
the modules named ``jit_paged_prefill_chunk`` over seconds of every
``jit_paged_`` module (decode, prefill chunk, verify) on the first
device's "XLA Modules" line.  None where the store's programs are not
named (both were ``jit_fn`` before the ``tracing`` PR).  Layer: serving
planes (``program_store.py``, chunked prefill)."""

PAGED = "jit_paged_"
PREFILL = "jit_paged_prefill_chunk"


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    modules = trace["devices"][0]["modules"]
    paged = sum(secs for name, (_, secs) in modules.items()
                if name.startswith(PAGED))
    if not paged:
        return None
    return 100.0 * sum(secs for name, (_, secs) in modules.items()
                       if name.startswith(PREFILL)) / paged
