"""Assignments to experts held here over all the assignments the routers made in the window, chunks and decode steps (the program's counters)."""


def read(run):
    c = run.get("counters", {})
    made = c.get("moe_assignments_decode", 0) \
        + c.get("moe_assignments_prefill", 0)
    if not made:
        return None
    return 100.0 * (c.get("moe_assignments_local_decode", 0)
                    + c.get("moe_assignments_local_prefill", 0)) / made
