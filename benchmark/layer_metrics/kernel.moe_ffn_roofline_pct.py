"""The expert layers' grouped products' share of their roofline: the
larger of (bytes of the held experts a step's routing touches) over the
HBM's rate and (FLOPs of its assignments) over the bf16 peak, over the
products' seconds in the trace.

Required, by ``costs/<config>.py``'s ``moe_kernel_cost``: the window's
means an expert layer a step (``moe_local_assignments`` and
``moe_experts_touched`` over ``moe_expert_steps``, deltas of
``GenerationEngine.stats()``) x the step programs' executions in the
trace x the expert layers.  At some 30 assignments a layer a decode
step reads 16 experts of 88 MB for 2.6 GFLOP: bound by bytes.  None if
a part is missing (a program without expert counters or without the
product).  Layer: kernels (``ops/moe.py``)."""
import re

KERNEL = re.compile(r"^%ragged-dot")
PROGRAMS = ("jit_paged_decode", "jit_paged_prefill_chunk")


def read(run):
    trace, peaks, c = run["trace"], run["peaks"], run["counters"]
    if not trace or not trace["devices"] or not peaks \
            or not c.get("moe_expert_steps"):
        return None
    first = trace["devices"][0]
    cfg = run["config"]
    spec = cfg["spec"]
    costs = run["cell"].module("costs")
    layers = int(spec["num_hidden_layers"]) \
        - int(spec["first_k_dense_replace"])
    ran = sum(count for name, (count, _) in first["modules"].items()
              if name.startswith(PROGRAMS))
    flops, nbytes = costs.moe_kernel_cost(
        cfg, c["moe_local_assignments"] / c["moe_expert_steps"],
        c["moe_experts_touched"] / c["moe_expert_steps"])
    least = ran * layers * max(flops / peaks["bf16_flops_per_s"],
                               nbytes / peaks["hbm_bytes_per_s"])
    spent = sum(s for name, s in first["ops"].items()
                if KERNEL.match(name))
    if not least or not spent:
        return None
    return 100.0 * least / spent
