"""Bytes the window's decode steps must move (every weight once a step, every running sequence's state, window rows and shared K/V) at the peak bandwidth over the decode executable's device time: the share of the whole step."""
from chipbench import families, phases


def read(run):
    a = phases.of_run(run)
    row = a and a["by_executable"].get(phases.DECODE)
    if not row or not row["seconds"] or not run.get("peaks") \
            or not run.get("decode_steps"):
        return None
    nbytes = families.of(run["cfg"]).decode_step_bytes(
        run["cfg"], run["decode_steps"], run["occupancy_sum"],
        run["decode_context_tokens"],
        run["counters"].get("window_rows_read", 0))
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["decode_step"] = {
        "bound": "memory", "seconds": row["seconds"], "least_s": least}
    return 100.0 * least / row["seconds"]
