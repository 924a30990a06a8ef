"""The selection's calls' share of their roofline: the least time the
chip could take for what the TRACED dispatches required over the calls'
seconds in the trace.

Required, by ``costs/<config>.py``'s ``select_cost``: every score the
indexer wrote read ONCE (``index_pairs`` a span x 4 B) and no product,
for the mean dispatch of each phase x its spans x the layers, over the
traced seconds alone (``kernel.dsa_index_time_pct.least``).  The kernel
reads the table's whole width a query, the ``-inf`` past a query's
frontier too, and passes over its tile 47 times in VMEM, so the share
reads how far counting is from one pass over the live scores; it can
only under-read, and a reading over 100 means the count is wrong.  None
if any part is missing.  Layer: kernels (``pallas_ops/dsa.py``)."""


def read(run):
    cell = run["cell"]
    base = cell.module("layer_metrics", "kernel.dsa_index_time_pct")
    sel = cell.module("layer_metrics", "kernel.dsa_select_time_pct")
    costs = cell.module("costs")
    if not hasattr(costs, "select_cost"):
        return None
    cfg = run["config"]
    return base.roofline(
        run, sel.KERNEL, ("index_pairs",),
        lambda m: costs.select_cost(cfg, m["index_pairs"]))
