"""The touched experts' weights (read once a step and layer) and the local assignments' 3 x 6144 x 2048 x 2 operations of the window's decode steps at the chip's peaks over the decode executable's device time under experts_routed."""
from chipbench import costs, families, phases


def read(run):
    a = phases.of_run(run)
    c = run.get("counters", {})
    if not a or not run.get("peaks") or not c.get("moe_experts_touched"):
        return None
    seconds = sum(v for k, v in a["by_scope"].get(phases.DECODE, {}).items()
                  if "experts_routed" in k.split("/"))
    if not seconds:
        return None
    flops, nbytes = families.of(run["cfg"]).routed_experts_cost(
        run["cfg"], c.get("moe_assignments_local_decode", 0),
        c["moe_experts_touched"])
    least, bound = costs.roofline_seconds(flops, nbytes, run["peaks"])
    run.setdefault("notes", {})["experts_routed"] = {
        "bound": bound, "seconds": seconds, "least_s": least}
    return 100.0 * least / seconds
