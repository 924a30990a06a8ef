"""Share of the first device's busy time spent in the paged-attention
kernel: summed duration of its events in the trace over busy time.
Layer: kernels (``pallas_ops/paged_attention.py``)."""
import re

# How the trace names the kernel's device operations (read by hand from
# the first traced run, PERF.md section 5): the event name is the whole
# HLO instruction, a Pallas kernel is a nameless tpu_custom_call, and
# the paged-attention kernel is the one whose first two operands are
# the s32 block tables and positions (the norm kernels take f32 rows).
KERNEL = re.compile(
    r"^%\S+ = f32\[[\d,]+\]\S* custom-call\(s32\[[\d,]+\]\S* %\S+, "
    r"s32\[[\d,]+\]\S* %\S+,.*custom_call_target=\"tpu_custom_call\"")


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    first = trace["devices"][0]
    spent = sum(s for name, s in first["ops"].items()
                if KERNEL.search(name))
    if not first["busy_s"] or not spent:
        return None
    return 100.0 * spent / first["busy_s"]
