"""Share of its roofline that the prefill chunk's selective scan reached: the least time of one state-space layer's scan over a chunk, times layers and chunks, over the chunk executable's device time under ssm/ssm_scan."""
from chipbench import costs, families, phases


def read(run):
    a = phases.of_run(run)
    row = a and a["by_executable"].get(phases.PREFILL_CHUNK)
    if not row or not run.get("peaks"):
        return None
    seconds = sum(v for k, v in a["by_scope"].get(phases.PREFILL_CHUNK,
                                                  {}).items()
                  if "ssm_scan" in k)
    if not seconds:
        return None
    cfg = run["cfg"]
    family = families.of(cfg)
    counts = family.layer_counts(cfg)
    calls = row["calls"] * (counts["ssm"] + counts["ssm_mem"])
    least, bound = costs.roofline_seconds(
        *family.ssm_scan_cost(cfg, cfg["runner"]["server"]["prefill_chunk"]),
        run["peaks"])
    run.setdefault("notes", {})["ssm_scan"] = {
        "bound": bound, "calls": calls, "seconds": seconds,
        "least_s_per_call": least}
    return 100.0 * calls * least / seconds
