"""Share of the first device's busy time spent in the SELECTION, the
exact top-``index_topk`` of every query's index scores as a threshold:
summed duration of the events named ``%dsa_select_threshold…`` (the
call's ``name=``) over busy time.  What turns a decode step's mask into
ascending positions afterwards (``compact_positions``) is XLA's fusions
and products and is NOT in it (PERF.md section 5 has its share from the
breakdown).  None from a trace without such events (a program before
the kernel, or one whose selection is a sort).  Layer: kernels
(``pallas_ops/dsa.py``)."""
import re

KERNEL = re.compile(r"^%dsa_select_threshold")


def read(run):
    base = run["cell"].module("layer_metrics", "kernel.dsa_index_time_pct")
    return base.share(run, KERNEL)
