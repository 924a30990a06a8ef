"""The window layers' paged-attention calls' share of their roofline:
the least time the chip could take for what the TRACED dispatches
required — the larger of required bytes over the HBM's rate and
required FLOPs over the bf16 peak — over the calls' seconds in the
trace.

Required, by ``costs/<config>.py``'s ``swa_kernel_cost``: for each step
program the mean dispatch of its phase (``rows``, ``kv_tokens_window``
— the sum over the live rows of ``min(frontier, window)`` — and
``q_tokens`` a ``serve_decode``/``serve_prefill`` span) x its spans x
the window layers, all of it over the traced seconds alone
(``host["traced_phases"]``, as ``kernel.gqa_attn_roofline_pct``).
Keys and values inside the window counted once, nothing for the rest
of the groups of blocks the kernel fetches around them, nothing for
dead rows, so the share can only under-read; a reading over 100 means
the count is wrong.  None if any part is missing (a program without the
named call or without ``kv_tokens_window`` on its spans, a driver
without the traced totals).  Layer: kernels
(``pallas_ops/paged_attention.py``)."""
import re

KERNEL = re.compile(r"^%window_paged_attention")
PHASES = ("serve_decode", "serve_prefill")
COUNTS = ("rows", "kv_tokens_window", "q_tokens")


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    phases = run["host"].get("traced_phases")
    if not trace or not trace["devices"] or not peaks or not phases:
        return None
    cfg = run["config"]
    costs = run["cell"].module("costs")
    layers = list(cfg["spec"]["layer_types"]).count("sliding_attention")
    least = 0.0
    for phase in PHASES:
        spans = phases.get(phase)
        if not spans or not spans["spans"]:
            continue
        if not set(COUNTS) <= set(spans["counts"]):
            return None
        mean = {k: spans["counts"][k] / spans["spans"] for k in COUNTS}
        flops, nbytes = costs.swa_kernel_cost(
            cfg, mean["rows"], mean["kv_tokens_window"], mean["q_tokens"])
        least += spans["spans"] * layers * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    spent = sum(s for name, s in trace["devices"][0]["ops"].items()
                if KERNEL.match(name))
    if not least or not spent:
        return None
    return 100.0 * least / spent
