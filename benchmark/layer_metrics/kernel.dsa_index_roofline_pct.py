"""The lightning indexer's calls' share of their roofline: the least
time the chip could take for what the TRACED dispatches required over
the calls' seconds in the trace.

Required, by ``costs/<config>.py``'s ``index_kernel_cost``: every
(query, key) pair a query sees scored (``index_pairs`` a span: 16,512
FLOP a pair) and each live sequence's index keys read once
(``kv_tokens``: 256 B a key), for the mean dispatch of each phase x its
spans x the layers, over the traced seconds alone
(``kernel.dsa_index_time_pct.least``).  A decode step is bound by the
bytes (one query a sequence: 64 FLOP/B), a chunk of 32 by the FLOPs.
Nothing for dead rows, the queries or the scores written, so the share
can only under-read; a reading over 100 means the count is wrong.  None
if any part is missing.  Layer: kernels (``pallas_ops/dsa.py``)."""


def read(run):
    base = run["cell"].module("layer_metrics", "kernel.dsa_index_time_pct")
    costs = run["cell"].module("costs")
    if not hasattr(costs, "index_kernel_cost"):
        return None
    cfg = run["config"]
    return base.roofline(
        run, base.KERNEL, ("rows", "index_pairs", "kv_tokens"),
        lambda m: costs.index_kernel_cost(
            cfg, m["rows"], m["index_pairs"], m["kv_tokens"]))
