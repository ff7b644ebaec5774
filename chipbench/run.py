#!/usr/bin/env python3
"""chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on. The
cell's configuration, traffic mix, limits and per-layer readers are files
found by name under chipbench/; a later cell is new files and new entries.
Prints earlier lines (fingerprints, step times, set-up by phase, what was
compared) and, last, the one result line, checked before it is printed.

--rehearse runs the same path at the tiny sizes under chipbench/rehearse/
on whatever JAX finds; its numbers are written nowhere. --control 1 also
reads the lower-precision control beside the comparison (for setting limits).
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RUNNERS = {"train": "chipbench.train", "serve-closed": "chipbench.serve",
           "serve-open": "chipbench.serve"}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"chipbench: no workload {args.workload!r} in "
                         f"BENCHMARK.json")

    import importlib
    from chipbench import harness as H
    from chipbench import traffic as T
    from chipbench.lastline import cell_metrics

    devices, peaks = H.require_chip(cell["chips"], args.rehearse)
    cfg = H.load_config(cell["config"], args.rehearse)
    traffic = T.load(cell["traffic"], args.rehearse)
    cell_file = H.load_json("cells", f"{cell['name']}.json")
    H.say("cell", {"workload": cell["name"], "config": cell["config"],
                   "traffic": cell["traffic"], "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "rehearse": args.rehearse})
    H.say("compile_cache_dir", H.enable_compile_cache())
    tracer = H.Tracer(bool(args.trace), cell["name"])

    def finish(correct, attempted, failed, measured, numbers, peak, run):
        run.update(cfg=cfg, traffic=traffic, peaks=peaks,
                   chips=cell["chips"], measured=measured)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        line = {"correct": bool(correct), "attempted": int(attempted),
                "failed": int(failed)}
        wanted = cell_metrics(bench, cell["name"], bool(args.trace))
        if args.trace:
            run["trace"], path = tracer.reduce()
            H.say("trace_file", os.path.relpath(path, ROOT))
            values = {name: H.read_metric(name, run) for name in wanted}
            device["busy_s"] = run["trace"]["busy_mean_s"]
            device["window_s"] = run["trace"]["window_s"]
            line["breakdown"] = {
                "device_ops": run["trace"]["device_ops"],
                "idle_gaps": run["trace"]["idle_gaps"]}
            H.say("trace_notes", run.get("notes", {}))
        else:
            values = {name: measured.get(name) for name in wanted}
        line["metrics"] = {n: {"value": v, "unit": wanted[n]}
                           for n, v in values.items() if v is not None}
        line["device"] = device
        # off the chip a rehearsal has no peaks and no Pallas kernels: what
        # reads them finds nothing, and only there may that be left out
        missing = [n for n, v in values.items() if v is None] \
            if args.rehearse and devices[0].platform != "tpu" else ()
        H.finish(line, numbers, bench, cell["name"], bool(args.trace),
                 cell["chips"], optional=missing)

    ctx = {"args": args, "bench": bench, "cell": cell, "cfg": cfg,
           "traffic": traffic, "cell_file": cell_file, "devices": devices,
           "peaks": peaks, "tracer": tracer, "t0": _T0, "finish": finish}
    importlib.import_module(RUNNERS[traffic["kind"]]).run(ctx)


if __name__ == "__main__":
    main()
