"""Share of the first device's busy time spent in the latent-attention
kernel: summed duration of its events in the trace over busy time.  The
kernel's ``name=`` is its HLO instruction's name, which opens the
event's name.  Layer: kernels (``pallas_ops/mla_attention.py``)."""
import re

KERNEL = re.compile(r"^%mla_paged_attention")


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
