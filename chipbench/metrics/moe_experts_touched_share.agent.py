"""Experts a decode step multiplied through over the 64 of a layer, a decode step and expert layer (the program's counters)."""


def read(run):
    c = run.get("counters", {})
    n = c.get("moe_expert_tokens_max.count")     # (step, layer) pairs routed
    if not n or "moe_experts_touched" not in c:
        return None
    return 100.0 * c["moe_experts_touched"] / (n * run["cfg"]["num_experts"])
