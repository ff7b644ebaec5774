"""The arithmetic the per-layer readers under ``chipbench/metrics/`` share.

A reader gets ``run``: what the runner counted in the window (steps, times,
tokens, counter differences), the cell's configuration and traffic, the
table's peaks, and under ``trace`` what ``xplane.reduce`` made of the
profiler's trace. A reader that finds nothing to read returns None and the
metric is left out of the line; it never returns 0 for a share of a peak.
"""
from __future__ import annotations

from . import costs


def mean_step_ms(run):
    ms = run.get("step_ms")
    return float(sum(ms) / len(ms)) if ms is not None and len(ms) else None


def idle_share(run):
    tr = run.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu(run):
    rate = run.get("measured", {}).get("train_tokens_per_s")
    if not rate or not run.get("peaks"):
        return None
    per_token = costs.train_flops_per_token(run["cfg"], run["seq"])
    return 100.0 * rate * per_token / (run["chips"]
                                       * run["peaks"]["bf16_flops"])


def kernel_roofline(run, kernels, cost_fn):
    """Calls x the least time one call could take, over the device time of
    those calls. ``kernels`` that split one call between them (the flash
    backward's dq and dkv) count one call per pair."""
    tr = run.get("trace")
    if not tr or not run.get("peaks"):
        return None
    found = [tr["kernels"].get(k) for k in kernels]
    if not all(found):
        return None
    seconds = sum(f["seconds"] for f in found)
    calls = min(f["calls"] for f in found)
    flops, nbytes = cost_fn(run["cfg"], run["batch"], run["seq"])
    least, bound = costs.roofline_seconds(flops, nbytes, run["peaks"])
    run.setdefault("notes", {})["+".join(kernels)] = {
        "bound": bound, "calls": calls, "seconds": seconds,
        "least_s_per_call": least}
    return 100.0 * calls * least / seconds if seconds else None


def mbu(run):
    """Bytes the decode steps of the window had to read (weights once a
    step, K and V of the running sequences) over the window's time."""
    if not run.get("decode_steps") or not run.get("peaks"):
        return None
    cfg = run["cfg"]
    nbytes = run["decode_steps"] * costs.matmul_params(cfg) * 2 \
        + run["decode_context_tokens"] * costs.kv_bytes_per_token(cfg)
    return 100.0 * nbytes / run["elapsed_s"] \
        / run["peaks"]["hbm_bytes_per_s"]


def occupancy(run):
    if not run.get("decode_steps"):
        return None
    return run["occupancy_sum"] / run["decode_steps"]


def queue_wait_ms(run):
    if not run.get("queue_wait_count"):
        return None
    return 1e3 * run["queue_wait_sum"] / run["queue_wait_count"]


def prefix_hit_share(run):
    total = run.get("hit_tokens", 0) + run.get("miss_tokens", 0)
    return 100.0 * run["hit_tokens"] / total if total else None


def generator_late_ms(run):
    late = run.get("late_ms")
    return float(sum(late) / len(late)) if late else None


def tpot_p95_ms(run):
    return run.get("tpot_p95_ms")
