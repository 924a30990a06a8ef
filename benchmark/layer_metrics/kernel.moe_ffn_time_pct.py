"""Share of the first device's busy time spent in the expert layers'
grouped products (``jax.lax.ragged_dot``: a Mosaic kernel whose HLO
instructions, the product and its small metadata call, are named
``ragged-dot...``): summed duration of their events over busy time.
Layer: kernels (``ops/moe.py``)."""
import re

KERNEL = re.compile(r"^%ragged-dot")


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
