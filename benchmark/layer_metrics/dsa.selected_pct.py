"""What the selection keeps of what the indexer scores: ``keys_selected
/ index_pairs``, the counts of the ``serve_decode`` and ``serve_prefill``
spans summed over the TRACED seconds (``host["traced_phases"]``; the
engine's ``dsa_keys_selected`` / ``dsa_index_pairs`` count the same
over its lifetime).  100 while no query sees more than ``index_topk``
positions; at 20 k of context, 10.  Lower is sparser: attention reads
that share of what dense attention would.  None from a program or a
model without an indexer, or from a driver without the traced totals.
Layer: serving planes (the dispatch spans, ``decode_engine.py``)."""

PHASES = ("serve_decode", "serve_prefill")


def read(run):
    phases = run["host"].get("traced_phases") or {}
    pairs = kept = 0
    for phase in PHASES:
        counts = phases.get(phase, {}).get("counts", {})
        if "index_pairs" not in counts:
            continue
        pairs += counts["index_pairs"]
        kept += counts["keys_selected"]
    if not pairs:
        return None
    return 100.0 * kept / pairs
