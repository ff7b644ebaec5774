"""The harness checks its own last line before it prints it.

``problems`` holds the line to every requirement the driver reads it by: the
five keys, every metric of the cell for this trace mode present with its unit
and a finite value, ``device`` complete, and in a traced run
``0 < busy_s <= window_s``. An empty list means the line may be printed.
"""
from __future__ import annotations

import math
import numbers


def cell_metrics(bench: dict, workload: str, trace: bool) -> dict:
    """{name: unit} of the metrics a run of this cell must report."""
    e2e = {m["name"]: m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])}
    if not trace:
        return {n: m["unit"] for n, m in e2e.items()}
    return {m["name"]: m["unit"] for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in e2e}


def _finite(x) -> bool:
    return isinstance(x, numbers.Real) and not isinstance(x, bool) \
        and math.isfinite(x)


def problems(line: dict, bench: dict, workload: str, trace: bool,
             chips: int, optional=()) -> list:
    out = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            out.append(f"key {key!r} is missing")
    if out:
        return out
    if not isinstance(line["correct"], bool):
        out.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        if not (isinstance(line[key], int) and line[key] >= 0):
            out.append(f"{key} is not a count")
    if isinstance(line["attempted"], int) and line["attempted"] < 1:
        out.append("nothing was attempted")
    want = cell_metrics(bench, workload, trace)
    got = line["metrics"]
    for name, unit in want.items():
        if name not in got:
            if name not in optional:
                out.append(f"metric {name!r} is missing")
            continue
        m = got[name]
        if not isinstance(m, dict) or m.get("unit") != unit:
            out.append(f"metric {name!r}: unit is not {unit!r}")
        elif not _finite(m.get("value")):
            out.append(f"metric {name!r}: value {m.get('value')!r} is not "
                       f"a finite number")
        elif (name.endswith("_roofline") or "mfu" in name) \
                and not 0 < m["value"] <= 105:
            out.append(f"metric {name!r}: {m['value']} is not a share of "
                       f"a peak")
    for name in got:
        if name not in want:
            out.append(f"metric {name!r} is not one of this cell's")
    dev = line["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if dev.get(key) in (None, ""):
            out.append(f"device.{key} is missing")
    if dev.get("count") != chips:
        out.append(f"device.count {dev.get('count')} is not the cell's "
                   f"{chips}")
    peak = dev.get("memory_peak_bytes")
    if peak is not None and not (_finite(peak) and peak > 0):
        out.append(f"device.memory_peak_bytes {peak!r} is not a byte count")
    if trace:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not (_finite(busy) and _finite(window)):
            out.append("device.busy_s and device.window_s must be numbers "
                       "in a traced run")
        elif not 0 < busy <= window:
            out.append(f"device: 0 < busy_s <= window_s does not hold "
                       f"({busy} and {window})")
        bd = line.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key)
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _finite(r[1]))
                        for r in rows):
                    out.append(f"breakdown.{key} is not at most 10 "
                               f"[name, seconds] pairs")
    return out
