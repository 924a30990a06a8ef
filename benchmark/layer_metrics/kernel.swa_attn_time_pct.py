"""Share of the first device's busy time spent in the WINDOW layers'
paged-attention calls: summed duration of the events named
``%window_paged_attention…`` (the call's ``name=``, which becomes its
HLO instruction's name; the full layers' calls of the same kernel body
are ``%paged_attention…`` and ``kernel.gqa_attn_time_pct`` reads them)
over busy time.  None from a trace without such events (a program
before the window).  Layer: kernels
(``pallas_ops/paged_attention.py``)."""
import re

KERNEL = re.compile(r"^%window_paged_attention")


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    first = trace["devices"][0]
    spent = sum(s for name, s in first["ops"].items()
                if KERNEL.match(name))
    if not first["busy_s"] or not spent:
        return None
    return 100.0 * spent / first["busy_s"]
