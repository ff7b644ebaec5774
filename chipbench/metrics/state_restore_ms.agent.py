"""Host time under serving.state_restore (a hit's snapshot looked up and marked used) and serving.state_snapshot (snapshots taken for a prefill call's boundaries) for each gateway step of the window."""
from chipbench import phases

SPANS = ("serving.state_restore", "serving.state_snapshot")


def read(run):
    a = phases.of_run(run)
    if not a or not run.get("step_ms") \
            or not any(a["span_counts"].get(s) for s in SPANS):
        return None
    return 1e3 * sum(a["span_counts"].get(s, 0) * a["span_mean_s"].get(s, 0.0)
                     for s in SPANS) / len(run["step_ms"])
