"""Tokens of the fullest expert over the mean of all 64, a decode step and expert layer (the program's histogram and counters)."""


def read(run):
    c = run.get("counters", {})
    n = c.get("moe_expert_tokens_max.count")
    local = c.get("moe_assignments_local_decode")
    if not n or not local:
        return None
    mean = local / (n * run["cfg"]["num_experts"])
    return c["moe_expert_tokens_max.sum"] / n / mean
