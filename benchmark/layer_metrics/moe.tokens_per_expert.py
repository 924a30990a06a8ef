"""Tokens a held expert computes in one expert layer of one step:
``moe_local_assignments / (held experts x moe_expert_steps)``, deltas of
``GenerationEngine.stats()`` over the window, decode steps and prompt
chunks alike.  A deployment that shares a layer over 16 chips gives an
expert 16 times this at the same batch a chip.  Layer: serving planes
(what the tick's batch leaves each expert)."""


def read(run):
    c = run["counters"]
    if not c.get("moe_expert_steps"):
        return None
    held = int(run["config"]["spec"]["n_routed_experts"])
    return c["moe_local_assignments"] / (held * c["moe_expert_steps"])
