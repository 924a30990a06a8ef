"""Share of the first device's busy time spent in the sparse latent
attention's calls: summed duration of the events named
``%dsa_mla_attention…`` over busy time.  The gather that lays a
query's selected rows side by side for the call is XLA's and is NOT in
it (PERF.md section 5 has its share from the breakdown).  None from a
trace without such events.  Layer: kernels (``pallas_ops/dsa.py``)."""
import re

KERNEL = re.compile(r"^%dsa_mla_attention")


def read(run):
    base = run["cell"].module("layer_metrics", "kernel.dsa_index_time_pct")
    return base.share(run, KERNEL)
