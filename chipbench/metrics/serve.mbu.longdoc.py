"""Bytes the window's decode steps must read (the weights outside the routed experts once a step, each touched expert's weights, the index key of every row scored, the latent row of every row selected) at the peak bandwidth over the decode executable's device time: the share of the whole step."""
from chipbench import families, phases


def read(run):
    a = phases.of_run(run)
    row = a and a["by_executable"].get(phases.DECODE)
    c = run.get("counters", {})
    if not row or not row["seconds"] or not run.get("peaks") \
            or not run.get("decode_steps") \
            or "dsa_rows_scored_decode" not in c:
        return None
    nbytes = families.of(run["cfg"]).decode_step_bytes(
        run["cfg"], run["decode_steps"], c.get("moe_experts_touched", 0),
        c["dsa_rows_scored_decode"], c["dsa_rows_selected_decode"])
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["decode_step"] = {
        "bound": "memory", "seconds": row["seconds"], "least_s": least}
    return 100.0 * least / row["seconds"]
