"""The prediction module's share of the first device's busy time: the
seconds of the module's own programs — ``jit_paged_draft_step`` behind
each verify, ``jit_paged_prefill_chunk_draft`` behind each prompt chunk
(``serving/program_store.py``), as the "XLA Modules" line names them —
over busy time.  One expert layer, ``W_eh`` and the head once more
beside the target's five layers: about a sixth of a step's weights.
None from a trace without such modules (a program that does not draft
for itself).  Layer: serving planes (``program_store.py``, the module's
programs)."""
MODULES = ("jit_paged_draft_step", "jit_paged_prefill_chunk_draft")


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    first = trace["devices"][0]
    spent = sum(secs for name, (_, secs) in first["modules"].items()
                if name.startswith(MODULES))
    if not first["busy_s"] or not spent:
        return None
    return 100.0 * spent / first["busy_s"]
