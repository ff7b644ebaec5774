#!/usr/bin/env python3
"""Find the knee of an open-loop cell, once, when the cell is defined.

chipbench/sweep.py --workload <name> --rates 0.5,0.75,1.0 --seconds 30

One server, the cell's own traffic at each rate in turn (new documents each
time), then a drain. A rate is sustained when no request waits unserved at the
close of the window and the wait for the first token does not grow from the
first half to the second. The traffic's file then fixes
four fifths of the highest sustained rate; PERF.md records the rows.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import harness as H, serve, traffic as T
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == args.workload)
    H.require_chip(cell["chips"], args.rehearse)
    H.enable_compile_cache()
    cfg = H.load_config(cell["config"], args.rehearse)
    base = T.load(cell["traffic"], args.rehearse)
    serve.set_flags(cfg)
    model, batcher, gateway = serve.build(cfg, args.seed)
    serve.warm_up(gateway, cfg, args.seed)
    tracer = H.Tracer(False, cell["name"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(base, rate_per_s=rate)
        drv = serve.Driver(gateway, tracer)
        stream = T.offers(traffic, cfg["vocab_size"], args.seed + 1000 * i)
        t_open = H.clock()
        late = serve.offer_open(drv, stream, args.seconds, t_open)
        backlog = sum(not r.times for r in drv.live)
        in_flight = len(drv.live)
        t_drain = H.clock()
        while drv.live and H.clock() - t_drain < 120:
            drv.step()
        done = [r for r in drv.finished if not r.failed]
        ttft = sorted(((r.offer.due, (r.times[0] - t_open - r.offer.due))
                       for r in done))
        first = [t for d, t in ttft if d < args.seconds / 2]
        second = [t for d, t in ttft if d >= args.seconds / 2]
        gaps = [g for r in done for g in np.diff(r.times)]
        H.say("rate", {
            "rate_per_s": rate, "offered": len(late), "finished": len(done),
            "unfinished_after_drain": len(drv.live),
            "backlog_at_close": backlog,
            "in_flight_at_close": in_flight,
            "ttft_mean_first_half_ms": 1e3 * float(np.mean(first)),
            "ttft_mean_second_half_ms": 1e3 * float(np.mean(second)),
            "tpot_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
            "tpot_median_ms": 1e3 * float(np.median(gaps)),
            "drain_s": H.clock() - t_drain,
            "late_mean_ms": float(np.mean(late))})
    batcher.close()


if __name__ == "__main__":
    main()
