"""The latent-attention kernel's share of its roofline in a
SELF-DRAFTING program: the least time the chip could take for what the
traced dispatches required — the larger of required bytes over the
HBM's rate and required FLOPs over the bf16 peak — over the kernel's
seconds in the trace.

``kernel.mla_attn_roofline_pct`` counts the modules ``jit_paged_decode``
and ``jit_paged_prefill_chunk`` and ``num_hidden_layers`` layers, so it
would read nothing of a store whose programs are the verify, the chunk
and the module's two, and miss the module's layer.  This one takes, by
``costs/<config>.py``'s ``mla_kernel_cost``, for each phase's spans
OVER THE TRACED SECONDS (``host["traced_phases"]``): the mean dispatch
(``rows``, ``kv_tokens``, ``q_tokens`` a ``serve_decode`` /
``serve_prefill`` span; a verify brings TWO query rows a sequence) x
the target's layers, and the module's layer at one query row a
sequence in a step (a row a token emitted: at least one) and the
chunk's own rows behind a chunk.  The latent rows are required once a
sequence a layer whatever the queries, so two queries a row are 484
FLOP a byte where one is 242, the chip's ridge: a verify is bound by
FLOPs.  Nothing for dead rows or the stored row's
padding: the share can only under-read, and a reading over 100 means
the count is wrong.  None if a part is missing.  Layer: kernels
(``pallas_ops/mla_attention.py``)."""
import re

KERNEL = re.compile(r"^%mla_paged_attention")
COUNTS = ("rows", "kv_tokens", "q_tokens")


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    phases = run["host"].get("traced_phases")
    if not trace or not trace["devices"] or not peaks or not phases:
        return None
    cfg = run["config"]
    costs = run["cell"].module("costs")
    if not hasattr(costs, "draft_layers"):
        return None
    layers = int(cfg["spec"]["num_hidden_layers"])
    drafts = costs.draft_layers(cfg)

    def seconds(rows, kv_tokens, q_tokens):
        flops, nbytes = costs.mla_kernel_cost(cfg, rows, kv_tokens,
                                              q_tokens)
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    least = 0.0
    for phase in ("serve_decode", "serve_prefill"):
        spans = phases.get(phase)
        if not spans or not spans["spans"]:
            continue
        if not set(COUNTS) <= set(spans["counts"]):
            return None
        rows, kv, q = (spans["counts"][k] / spans["spans"]
                       for k in COUNTS)
        module_q = rows if phase == "serve_decode" else q
        least += spans["spans"] * (
            layers * seconds(rows, kv, q)
            + drafts * seconds(rows, kv, module_q))
    spent = sum(s for name, s in trace["devices"][0]["ops"].items()
                if KERNEL.match(name))
    if not least or not spent:
        return None
    return 100.0 * least / spent
