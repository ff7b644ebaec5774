"""State read and written, window rows and the shared K/V read once a reading layer, at the peak bandwidth over the decode executable's device time under ssm_step, window_attention, full_attention and cross_attention."""
from chipbench import families, phases

SCOPES = ("ssm_step", "window_attention", "full_attention", "cross_attention")


def read(run):
    a = phases.of_run(run)
    if not a or not run.get("peaks") or not run.get("decode_steps"):
        return None
    seconds = sum(v for k, v in a["by_scope"].get(phases.DECODE, {}).items()
                  if any(s in k for s in SCOPES))
    if not seconds:
        return None
    nbytes = families.of(run["cfg"]).decode_state_bytes(
        run["cfg"], run["occupancy_sum"], run["decode_context_tokens"],
        run["counters"].get("window_rows_read", 0))
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["decode_state"] = {
        "bound": "memory", "seconds": seconds, "least_s": least}
    return 100.0 * least / seconds
