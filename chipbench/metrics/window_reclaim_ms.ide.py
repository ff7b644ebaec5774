"""Host time under serving.release_window (window pages handed back and taken, a chunk and a decode step) for each gateway step of the window."""
from chipbench import phases


def read(run):
    a = phases.of_run(run)
    n = a and a["span_counts"].get("serving.release_window")
    if not n or not run.get("step_ms"):
        return None
    return 1e3 * n * a["span_mean_s"]["serving.release_window"] \
        / len(run["step_ms"])
