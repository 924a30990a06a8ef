"""The grouped-query paged-attention kernel's share of its roofline:
the least time the chip could take for what the TRACED dispatches
required — the larger of required bytes over the HBM's rate and
required FLOPs over the bf16 peak — over the kernel's seconds in the
trace.

Required, by ``costs/<config>.py``'s ``gqa_kernel_cost``: for each step
program the mean dispatch of its phase (``rows``, ``kv_tokens`` and
``q_tokens`` a ``serve_decode``/``serve_prefill`` span) x its spans x
the attention layers, all of it over the traced seconds alone
(``host["traced_phases"]``, the driver's ``phase_totals()`` readings at
the trace's start and stop): nothing of the whole window is multiplied
by a count of three seconds of it.  A decode step is bound by bytes (8
FLOP/B), a chunk by FLOPs.  Live context counted once, nothing for dead
rows, nothing for the zero half of the kernel's padded query, so the
share can only under-read; a reading over 100 means the count is
wrong.  None if any part is missing (a program without the kernel or
the spans, a driver without the traced totals).  Layer: kernels
(``pallas_ops/paged_attention.py``)."""
import re

KERNEL = re.compile(r"^%paged_attention")
PHASES = ("serve_decode", "serve_prefill")


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    phases = run["host"].get("traced_phases")
    if not trace or not trace["devices"] or not peaks or not phases:
        return None
    cfg = run["config"]
    costs = run["cell"].module("costs")
    layers = list(cfg["spec"]["layer_types"]).count("full_attention")
    least = 0.0
    for phase in PHASES:
        spans = phases.get(phase)
        if not spans or not spans["spans"]:
            continue
        if not {"rows", "kv_tokens", "q_tokens"} <= set(spans["counts"]):
            return None
        mean = {k: spans["counts"][k] / spans["spans"]
                for k in ("rows", "kv_tokens", "q_tokens")}
        flops, nbytes = costs.gqa_kernel_cost(
            cfg, mean["rows"], mean["kv_tokens"], mean["q_tokens"])
        least += spans["spans"] * layers * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    spent = sum(s for name, s in trace["devices"][0]["ops"].items()
                if KERNEL.match(name))
    if not least or not spent:
        return None
    return 100.0 * least / spent
