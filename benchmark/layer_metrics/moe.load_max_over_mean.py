"""How uneven the routing leaves the held experts: the fullest expert's
tokens over the mean expert's, ``moe_expert_load_max x held /
moe_local_assignments`` (the maximum summed over steps and layers, so
this is the assignment-weighted mean of each layer's ratio).  1 is
even; the grouped product's time follows the sum, an expert-parallel
deployment's the maximum.  Layer: serving planes."""


def read(run):
    c = run["counters"]
    if not c.get("moe_local_assignments"):
        return None
    held = int(run["config"]["spec"]["n_routed_experts"])
    return c["moe_expert_load_max"] * held / c["moe_local_assignments"]
