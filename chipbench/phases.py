"""From a profiler trace to device time by executable and model scope, and to
the idle device time under each span of the program.

``chipbench.xplane`` reduces a trace to busy time, kernels and the gaps under
the harness's three spans. This reads the same trace for what the program
itself names since PR 25: the module line (``jit_serving_paged_decode``), the
model scope of every device operation (``paged_attention/kv_gather``) and the
phase spans inside ``Gateway.step`` and ``TrainStep`` (PERF.md section 3).

The trace gives an operation no scope: its events carry the instruction's
text and a duration. The scope comes from the ``op_name`` metadata of the
optimized HLO, which ``observability.opprof`` keeps by instruction name at
the warm transition (``OpProfile.op_paths``); ``of_run`` writes that map
beside the trace as ``op_scopes.json``, and a kept cut holds it under
``op_scopes``. An instruction without metadata of its own (a layout copy the
compiler put in) takes the scope of its first operand, marked ``<-operand``.

Idle time is the complement of ``xplane``'s busy union inside the window.
Every instant of it goes to the INNERMOST span open at that instant (the
latest start): a gap that crosses span boundaries is cut at them, so the
one gap of a decode step splits into fetch, pick, poll, dispatch, launch.
The spans tile it exactly: their sum and ``outside_spans`` are window - busy.

In a run the readers under ``chipbench/metrics/`` call ``of_run``: it finds
the trace this process has just written, analyses it once and prints the
tables as earlier lines. By hand: ``python -m chipbench.phases <trace>``
(an ``.xplane.pb``, or a cut kept as ``.json`` / ``.json.gz``).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

from . import families
from . import xplane as X
from .harness import ROOT, SPANS as HARNESS_SPANS, say

PROGRAM_SPANS = (
    "gateway.dispatch", "gateway.replica_step", "gateway.poll",
    "serving.admit", "serving.prefill_chunk", "serving.grow",
    "serving.sync_tables", "serving.launch", "serving.fetch", "serving.pick",
    "trainstep.assemble", "trainstep.launch", "trainstep.writeback")
DECODE = "serving_paged_decode"
PREFILL_CHUNK = "serving_paged_prefill_chunk"
SCOPES = ("embed", "attn_norm", "qkv_rope", "paged_attention", "kv_scatter",
          "kv_gather", "scores", "o_proj", "mlp_norm", "mlp", "head")
_MODULE_LINE = "XLA Modules"
_SIDECAR = "op_scopes.json"
_INHERITED = " <-operand"


def newest_trace(root: str = ROOT):
    """The newest trace of any cell under this checkout: the one a run's
    process has just written."""
    paths = filter(None, map(X.newest_xplane, glob.glob(
        os.path.join(root, ".chipbench", "trace", "*"))))
    return max(paths, key=os.path.getmtime, default=None)


def scope_of(op_name: str, scopes=SCOPES) -> str:
    """``paged_attention/kv_gather`` from an instruction's ``op_name``: the
    model's scopes in the order they were entered, "" where there is none."""
    return "/".join(p for p in op_name.split("/") if p in scopes)


def program_op_scopes(scopes=SCOPES) -> dict:
    """{module: {instruction: scope}} of the executables this process
    compiled, from what opprof kept of their optimized HLO; {} where the
    program keeps no such map (before PR 25)."""
    from paddle_tpu.observability import opprof
    out = {}
    for label, profs in opprof.get_captures().items():
        paths = getattr(profs[-1], "op_paths", None) if profs else None
        if paths:       # the module's name as jit._named makes it
            out[re.sub(r"\W", "_", label)] = {
                k: s for k, v in paths.items() if (s := scope_of(v, scopes))}
    return out


def load(path: str) -> dict:
    """``xplane.load``'s plain data, with the ``op_scopes`` written beside
    an ``.xplane.pb`` (a kept cut holds its own)."""
    trace = X.load(path)
    side = os.path.join(os.path.dirname(path), _SIDECAR)
    if "op_scopes" not in trace and os.path.exists(side):
        with open(side) as f:
            trace["op_scopes"] = json.load(f)
    return trace


def instruction(event_name: str):
    """(name, first operand's name or None) from the instruction text the
    trace names an operation by: ``%copy.681 = bf16[..] copy(bf16[..]
    %fusion.617)`` gives ``("copy.681", "fusion.617")``."""
    head, _, rest = event_name.partition(" = ")
    m = re.search(r"%([\w.\-]+)", rest)
    return head.lstrip("%"), (m.group(1) if m else None)


def _scope_lookup(op_scopes: dict, events) -> dict:
    """{(module, event name): scope} for the (module, event name) pairs seen
    in a trace. An instruction with no scope of its own takes its first
    operand's, through as many such instructions as lie between (the
    compiler chains them); two modules may use one name for two things."""
    out = {}
    for module, scopes in op_scopes.items():
        instr = {n: instruction(n) for m, n in events if m == module}
        first = dict(instr.values())
        inherited = {}                # unscoped instruction -> scope

        def inherit(name):
            chain = []
            while name is not None and name not in scopes \
                    and name not in inherited and len(chain) < 256:
                chain.append(name)
                name = first.get(name)
            scope = scopes.get(name) or inherited.get(name, "")
            inherited.update(dict.fromkeys(chain, scope))
            return scope

        for n, (name, _) in instr.items():
            if name in scopes:
                out[module, n] = scopes[name]
            elif inherit(name):
                out[module, n] = inherited[name] + _INHERITED
    return out


def module_name(event_name: str) -> str:
    """``serving_paged_decode`` from the module line's
    ``jit_serving_paged_decode(1234567890)``."""
    name = event_name.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def _window(trace, timelines):
    lo = min(e[1] for ev in timelines.values() for e in ev)
    hi = max(e[1] + e[2] for ev in timelines.values() for e in ev)
    win = [(s, e) for n, s, e in X.host_spans(trace, {X.WINDOW_SPAN})]
    if win and win[0][0] < hi and win[0][1] > lo:
        return win[0]
    return lo, hi


def _device_plane(trace, lo, hi):
    """The busiest device, as ``xplane.reduce`` chooses it: (busy ns,
    plane, its clipped operations, the busy union)."""
    best = None
    for plane in trace["planes"]:
        if not X._DEVICE.match(plane["name"]):
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == X._OP_LINE] \
            or plane["lines"]
        ops = [o for ln in lines
               for o in X._clip((e for e in ln["events"] if e[2] > 0),
                                lo, hi)]
        busy = X.union([(a, b) for _, a, b in ops])
        total = sum(b - a for a, b in busy)
        if best is None or total > best[0]:
            best = (total, plane, ops, busy)
    return best


class _Cover:
    """Disjoint, sorted intervals, and how much of them lies in [lo, hi)."""

    def __init__(self, intervals):
        self.starts = [a for a, _ in intervals]
        self.ends = [b for _, b in intervals]
        self.upto = [0.0]                 # total length before interval i
        for a, b in intervals:
            self.upto.append(self.upto[-1] + (b - a))

    def _before(self, t) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.upto[i - 1] + min(t, self.ends[i - 1]) \
            - self.starts[i - 1]

    def inside(self, lo, hi) -> float:
        return self._before(hi) - self._before(lo) if hi > lo else 0.0


def _self_by_scope(ops, modules, op_scopes) -> dict:
    """{module: {scope: seconds}}: each operation's time less what the
    operations nested in it cover (a ``while`` around its body), put down
    to the operation's scope and to the execution it ran in."""
    starts = [m[1] for m in modules]

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return modules[i][0] if i >= 0 and t < modules[i][2] else "no_module"

    ops = sorted(((module_at(a), name, a, b) for name, a, b in ops),
                 key=lambda o: (o[2], -o[3]))
    scope_of_event = _scope_lookup(op_scopes, {o[:2] for o in ops})
    out, stack = {}, []              # stack of [end, own_ns, scope, module]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, own, scope, mod = stack.pop()
            row = out.setdefault(mod, {})
            row[scope] = row.get(scope, 0.0) + max(own, 0.0) / 1e9

    for mod, name, a, b in ops:
        close(a)
        if stack:
            stack[-1][1] -= min(b, stack[-1][0]) - a
        stack.append([b, b - a, scope_of_event.get((mod, name), "no_scope"),
                      mod])
    close(float("inf"))
    return out


def innermost_segments(spans, lo, hi) -> list:
    """[(start, end, stack)] tiling [lo, hi): ``stack`` is the names of the
    spans open there, outermost first, ordered by start; () under none."""
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in spans]
    spans = sorted((s for s in spans if s[2] > s[1]),
                   key=lambda s: (s[1], -s[2]))
    points = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)})
    out, open_, i = [], [], 0
    for t0, t1 in zip(points, points[1:]):
        while i < len(spans) and spans[i][1] <= t0:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s[2] > t0]
        out.append((t0, t1, tuple(s[0] for s in open_)))
    return out


def analyse(trace: dict, spans=PROGRAM_SPANS) -> dict:
    """``spans``: the program's spans that idle time is put down to and
    whose durations are kept (the base tuple, with a family's own)."""
    timelines = X._device_timelines(trace)
    if not timelines:
        raise ValueError("the trace holds no device operation")
    lo, hi = _window(trace, timelines)
    found = _device_plane(trace, lo, hi)
    if found is None:                # a --rehearse run off the chip: the CPU
        ops = [o for ev in timelines.values()         # backend's lines
               for o in X._clip(ev, lo, hi)]
        busy = X.union([(a, b) for _, a, b in ops])
        plane = {"name": "/host:CPU", "lines": []}
    else:
        _, plane, ops, busy = found
    busy_ns = sum(b - a for a, b in busy)
    busy_in = _Cover(busy).inside

    # executions of each executable, whole or clipped, from the module line
    modules = sorted(
        ((module_name(n), a, b, (b - a) >= d - 1)
         for ln in plane["lines"] if ln["name"] == _MODULE_LINE
         for n, s, d in ln["events"] if d > 0
         for a, b in [(max(s, lo), min(s + d, hi))] if b > a),
        key=lambda m: m[1])
    by_exe = {}
    for name, a, b, whole in modules:
        row = by_exe.setdefault(name, {"seconds": 0.0, "calls": 0,
                                       "whole_calls": 0, "whole_s": 0.0})
        inside = busy_in(a, b) / 1e9
        row["seconds"] += inside
        row["calls"] += 1
        if whole:
            row["whole_calls"] += 1
            row["whole_s"] += inside
    named = sum(r["seconds"] for r in by_exe.values())
    if busy_ns / 1e9 - named > 1e-9:
        by_exe["no_module"] = {"seconds": busy_ns / 1e9 - named, "calls": 0,
                               "whole_calls": 0, "whole_s": 0.0}

    # idle time by the innermost span
    opened = X.host_spans(trace, set(spans) | set(HARNESS_SPANS))
    gaps, under_admit = {}, 0.0
    for t0, t1, stack in innermost_segments(opened, lo, hi):
        sec = (t1 - t0 - busy_in(t0, t1)) / 1e9
        if sec <= 0:
            continue
        key = stack[-1] if stack else "outside_spans"
        gaps[key] = gaps.get(key, 0.0) + sec
        if "serving.admit" in stack and key != "serving.fetch":
            under_admit += sec
    durations = {}
    for n, s, e in opened:
        if n in spans and s >= lo and e <= hi:
            durations.setdefault(n, []).append((e - s) / 1e9)
    return {
        "device": plane["name"], "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9, "idle_s": (hi - lo - busy_ns) / 1e9,
        "by_executable": by_exe,
        "by_scope": _self_by_scope(ops, modules,
                                   trace.get("op_scopes") or {}),
        "gaps": gaps, "gap_under_admit_s": under_admit,
        "span_counts": {n: len(v) for n, v in durations.items()},
        "span_mean_s": {n: sum(v) / len(v) for n, v in durations.items()}}


def _sorted_rows(seconds: dict, last=None) -> list:
    rows = sorted(((k, v) for k, v in seconds.items() if k != last),
                  key=lambda kv: -kv[1])
    return rows + ([(last, seconds[last])] if last in seconds else [])


def tables(a: dict) -> dict:
    """The analysis as the lines a run prints: name -> plain data."""
    out = {"device_by_executable": {
        k: {"seconds": round(r["seconds"], 6), "calls": r["calls"],
            "mean_ms_whole_calls": round(
                1e3 * r["whole_s"] / r["whole_calls"], 4)
            if r["whole_calls"] else None}
        for k, r in sorted(a["by_executable"].items(),
                           key=lambda kv: -kv[1]["seconds"])}}
    out["device_by_executable"]["busy_s"] = round(a["busy_s"], 6)
    out["idle_gaps_by_program_span"] = [
        [k, round(v, 6)] for k, v in _sorted_rows(a["gaps"],
                                                   "outside_spans")]
    for exe, scopes in a["by_scope"].items():
        out[f"device_by_scope.{exe}"] = [
            [k, round(v, 6)] for k, v in _sorted_rows(scopes, "no_scope")]
    out["program_span_counts"] = a["span_counts"]
    return out


_ANALYSES = {}


def of_run(run: dict):
    """The analysis of the trace this run has just written, made once a
    process and printed as earlier lines; None where there is no trace."""
    path = newest_trace()
    if path is None or not run.get("trace"):
        return None
    if path not in _ANALYSES:
        family = families.of(run["cfg"])
        op_scopes = program_op_scopes(SCOPES + tuple(family.SCOPES))
        if op_scopes and path.endswith(".pb"):
            with open(os.path.join(os.path.dirname(path), _SIDECAR),
                      "w") as f:
                json.dump(op_scopes, f)
        a = _ANALYSES[path] = analyse(
            load(path), PROGRAM_SPANS + tuple(family.SPANS))
        for key, value in tables(a).items():
            say(key, value)
    return _ANALYSES[path]


# -- what the readers under chipbench/metrics/ return ------------------------

def device_ms(run, executable):
    """Mean device time of one whole execution of ``executable`` inside the
    window: the busy union inside its module events."""
    a = of_run(run)
    row = a and a["by_executable"].get(executable)
    if not row or not row["whole_calls"]:
        return None
    return 1e3 * row["whole_s"] / row["whole_calls"]


def gap_fetch_ms(run):
    """Idle device time under ``serving.fetch`` and ``serving.pick`` for
    each ``serving.launch``: what sampling on the device can win."""
    a = of_run(run)
    n = a and a["span_counts"].get("serving.launch")
    if not n:
        return None
    return 1e3 * (a["gaps"].get("serving.fetch", 0.0)
                  + a["gaps"].get("serving.pick", 0.0)) / n


def gap_host_loop_ms(run):
    """All other idle device time of the window for each
    ``serving.launch``: what a leaner host loop can win."""
    a = of_run(run)
    n = a and a["span_counts"].get("serving.launch")
    if not n:
        return None
    return 1e3 * a["idle_s"] / n - gap_fetch_ms(run)


def admit_ms(run):
    a = of_run(run)
    mean = a and a["span_mean_s"].get("serving.admit")
    return 1e3 * mean if mean else None


def gap_admit_ms(run):
    """Idle device time inside ``serving.admit``, the first token's fetch
    apart, for each admission."""
    a = of_run(run)
    n = a and a["span_counts"].get("serving.admit")
    return 1e3 * a["gap_under_admit_s"] / n if n else None


def paged_attn_roofline(run):
    """The K and V bytes the window's decode steps had to read, at the peak
    bandwidth, over the device time of the decode executable under
    ``paged_attention`` (a later kernel of that name counts by its name)."""
    from . import costs
    a = of_run(run)
    if not a or not run.get("peaks") or not run.get("decode_context_tokens"):
        return None
    seconds = sum(v for k, v in a["by_scope"].get(DECODE, {}).items()
                  if "paged_attention" in k) \
        or sum(v["seconds"] for k, v in run["trace"]["kernels"].items()
               if k.startswith("paged_attention"))
    if not seconds:
        return None
    least = run["decode_context_tokens"] * costs.kv_bytes_per_token(
        run["cfg"]) / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["paged_attention"] = {
        "bound": "memory", "seconds": seconds, "least_s": least}
    return 100.0 * least / seconds


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit("usage: python -m chipbench.phases <trace>")
    a = analyse(load(argv[0]))
    print(json.dumps({"device": a["device"], "window_s": a["window_s"],
                      "busy_s": a["busy_s"], "idle_s": a["idle_s"]}))
    for key, value in tables(a).items():
        print(f"{key}: {json.dumps(value)}")


if __name__ == "__main__":
    main()
