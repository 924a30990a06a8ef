"""The expert layers' grouped products' share of their roofline in a
SELF-DRAFTING program: the larger of (bytes of the held experts a
step's routing touches) over the HBM's rate and (FLOPs of its
assignments) over the bf16 peak, over the products' seconds in the
trace.

``kernel.moe_ffn_roofline_pct`` counts the executions of
``jit_paged_decode`` / ``jit_paged_prefill_chunk`` and the target's
expert layers; this one counts this store's programs as the "XLA
Modules" line names them: the target's (``jit_paged_self_verify``,
``jit_paged_prefill_chunk_self``) x its expert layers, and the
prediction module's (``jit_paged_draft_step``,
``jit_paged_prefill_chunk_draft``) x its ONE.  Required, by
``costs/<config>.py``'s ``moe_kernel_cost``: the window's means an
expert layer a step (``moe_local_assignments`` and
``moe_experts_touched`` over ``moe_expert_steps``: the module's layer
counts among them).  At 4 tokens an expert a decode step reads 16
experts of 94 MB for some 12 GFLOP: bound by bytes.  None if a part is
missing.  Layer: kernels (``ops/moe.py``)."""
import re

KERNEL = re.compile(r"^%ragged-dot")
TARGET = ("jit_paged_self_verify", "jit_paged_prefill_chunk_self")
MODULE = ("jit_paged_draft_step", "jit_paged_prefill_chunk_draft")


def read(run):
    trace, peaks, c = run["trace"], run["peaks"], run["counters"]
    if not trace or not trace["devices"] or not peaks \
            or not c.get("moe_expert_steps"):
        return None
    first = trace["devices"][0]
    cfg = run["config"]
    spec = cfg["spec"]
    costs = run["cell"].module("costs")

    def ran(names):
        return sum(count for name, (count, _) in first["modules"].items()
                   if name.startswith(names))

    steps = ran(TARGET) * (int(spec["num_hidden_layers"])
                           - int(spec["first_k_dense_replace"])) \
        + ran(MODULE)
    flops, nbytes = costs.moe_kernel_cost(
        cfg, c["moe_local_assignments"] / c["moe_expert_steps"],
        c["moe_experts_touched"] / c["moe_expert_steps"])
    least = steps * max(flops / peaks["bf16_flops_per_s"],
                        nbytes / peaks["hbm_bytes_per_s"])
    spent = sum(s for name, s in first["ops"].items()
                if KERNEL.match(name))
    if not least or not spent:
        return None
    return 100.0 * least / spent
