"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads an ``.xplane.pb`` with nothing but JAX into plain data:
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]}``. ``reduce`` works on that, so a trace cut down and
kept as JSON under ``chipbench/data/`` checks it (tests/test_xplane.py).

Busy time is the UNION of the intervals in which an operation ran on a
device, clipped to the traced window, on the busiest device. It is never a
sum over lines or cores: nested and overlapping events count once.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

WINDOW_SPAN = "chipbench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+")
_OP_LINE = "XLA Ops"
# where the CPU backend's operations show up; read only when no device plane
# exists, which is the case in a --rehearse run
_CPU_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient")


def newest_xplane(root: str):
    paths = sorted(glob.glob(os.path.join(root, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
    return paths[-1] if paths else None


def load(path: str) -> dict:
    if path.endswith(".json") or path.endswith(".json.gz"):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return json.load(f)
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _device_timelines(trace: dict) -> dict:
    """{device name: [events]} — the operation line of each device plane."""
    out = {}
    for plane in trace["planes"]:
        if not _DEVICE.match(plane["name"]):
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == _OP_LINE] \
            or plane["lines"]
        out[plane["name"]] = [e for ln in lines for e in ln["events"]
                              if e[2] > 0]
    if out:
        return out
    events = [e for plane in trace["planes"] for ln in plane["lines"]
              if ln["name"].startswith(_CPU_LINES)
              for e in ln["events"]
              if e[2] > 0 and not e[0].startswith(("end: ", "Threadpool"))]
    return {"/host:CPU": events} if events else {}


def host_spans(trace: dict, names) -> list:
    """[(name, start_ns, end_ns)] of the harness's own spans."""
    names = set(names)
    return sorted((e[0], e[1], e[1] + e[2])
                  for plane in trace["planes"]
                  if not _DEVICE.match(plane["name"])
                  for ln in plane["lines"] for e in ln["events"]
                  if e[0] in names)


def union(intervals) -> list:
    """Merge [(start, end)] into disjoint, sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def self_times(events) -> dict:
    """Seconds by operation name, a parent (a ``while`` around its body)
    counted without what its children cover."""
    out, stack = {}, []          # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def canon(name: str) -> str:
    """The operation's name without its instance: the trace gives either
    ``fusion.123`` or the whole instruction, ``%flash_fwd.14 = (...)
    custom-call(...)``; both become ``fusion`` and ``flash_fwd``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name) or name


def _top10(seconds_by_name: dict) -> list:
    return [[k, v] for k, v in sorted(seconds_by_name.items(),
                                      key=lambda kv: -kv[1])[:10]]


_SHAPE = re.compile(r"([a-z]+\d*\[[\d,]*\])")


def label(name: str) -> str:
    """``canon`` plus the first output shape where the trace gives the whole
    instruction, so that one ``fusion`` can be told from another."""
    head, _, rest = name.partition(" = ")
    m = _SHAPE.search(rest)
    return f"{canon(head)}_{m.group(1)}" if m else canon(head)


def reduce(trace: dict, span_names=()) -> dict:
    """busy_s, window_s (busiest device), per-kernel seconds and call counts,
    the top device operations and the idle gaps named by the covering span."""
    timelines = _device_timelines(trace)
    if not timelines:
        raise ValueError("the trace holds no device operation")
    spans = host_spans(trace, set(span_names) | {WINDOW_SPAN})
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    lo = min(e[1] for ev in timelines.values() for e in ev)
    hi = max(e[1] + e[2] for ev in timelines.values() for e in ev)
    if window and window[0][0] < hi and window[0][1] > lo:
        lo, hi = window[0]                 # same clock: clip to our window
    best, busy_all = None, []
    for dev, events in timelines.items():
        clipped = list(_clip(events, lo, hi))
        busy = union([(a, b) for _, a, b in clipped])
        busy_ns = sum(b - a for a, b in busy)
        busy_all.append(busy_ns)
        if best is None or busy_ns > best[0]:
            best = (busy_ns, dev, clipped, busy)
    busy_ns, dev, clipped, busy = best
    kern = {}
    for n, a, b in clipped:
        row = kern.setdefault(canon(n), {"seconds": 0.0, "calls": 0})
        row["seconds"] += (b - a) / 1e9
        row["calls"] += 1
    ops = {}
    for name, sec in self_times(clipped).items():
        ops[label(name)] = ops.get(label(name), 0.0) + sec
    gaps, edge = {}, lo
    others = [s for s in spans if s[0] != WINDOW_SPAN]
    for a, b in busy + [[hi, hi]]:
        if a > edge:
            mid = (edge + a) / 2
            cover = [n for n, s, e in others if s <= mid < e]
            name = cover[-1] if cover else "outside_spans"
            gaps[name] = gaps.get(name, 0.0) + (a - edge) / 1e9
        edge = max(edge, b)
    return {"device": dev, "busy_s": busy_ns / 1e9,
            "busy_mean_s": sum(busy_all) / len(busy_all) / 1e9,
            "window_s": (hi - lo) / 1e9, "kernels": kern,
            "device_ops": _top10(ops), "idle_gaps": _top10(gaps)}
