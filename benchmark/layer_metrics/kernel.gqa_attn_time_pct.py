"""Share of the first device's busy time spent in the grouped-query
paged-attention kernel: summed duration of the events named
``%paged_attention…`` (the kernel's ``name=``, which becomes its HLO
instruction's name) over busy time.  Layer: kernels
(``pallas_ops/paged_attention.py``)."""
import re

KERNEL = re.compile(r"^%paged_attention")


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
