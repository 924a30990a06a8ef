"""The sparse latent attention's calls' share of their roofline: the
least time the chip could take for what the TRACED dispatches required
over the calls' seconds in the trace.

Required, by ``costs/<config>.py``'s ``sparse_attn_kernel_cost``: the
SELECTED pairs alone (``keys_selected`` a span: 278,528 FLOP a pair)
and each live sequence's selected rows read once (1,152 B a row,
``rows`` x the mean a query keeps), for the mean dispatch of each phase
x its spans x the layers, over the traced seconds alone
(``kernel.dsa_index_time_pct.least``).  The least ANY form can do: the
form that gathers a copy of the rows a query reads them once a QUERY,
so a chunk's share under-reads by design; a reading over 100 means the
count is wrong.  None if any part is missing.  Layer: kernels
(``pallas_ops/dsa.py``)."""


def read(run):
    cell = run["cell"]
    base = cell.module("layer_metrics", "kernel.dsa_index_time_pct")
    attn = cell.module("layer_metrics", "kernel.dsa_attn_time_pct")
    costs = cell.module("costs")
    if not hasattr(costs, "sparse_attn_kernel_cost"):
        return None
    cfg = run["config"]
    return base.roofline(
        run, attn.KERNEL,
        ("rows", "keys_selected", "kv_tokens", "q_tokens"),
        lambda m: costs.sparse_attn_kernel_cost(
            cfg, m["rows"], m["keys_selected"], m["kv_tokens"],
            m["q_tokens"]))
