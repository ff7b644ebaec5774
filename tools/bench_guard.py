#!/usr/bin/env python
"""Bench-trajectory regression gate over the committed ``BENCH_*.json``.

The driver appends one ``BENCH_rNN.json`` per round (a wrapper
``{n, cmd, rc, tail, parsed}`` whose ``parsed`` field holds the bench
line bench.py printed). This tool reads the ordered history, separates
real-TPU points from CPU-proxy points (``detail.tpu`` — the two run on
different hardware and must never be compared against each other), and
fails loudly when the NEWEST point of a series regresses below a
tolerance band fit to its own recent history:

    lower_bound = (1 - tolerance) * median(previous k points)

Median over a trailing window (not the single previous point) so one
noisy round neither hides a real regression nor trips a false one; a
linear trend fit is reported for context but never gates (trend is a
narrative, the band is the contract). Records with ``rc != 0`` or an
unparsable line (e.g. a timed-out round) are skipped with a note — a
round that produced no number is not a regression.

CI wiring: ``python tools/bench_guard.py --check`` exits 0 (pass, or
nothing to check) / 1 (regression), printing the verdict per series.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional

DEFAULT_TOLERANCE = 0.10
DEFAULT_WINDOW = 4


def discover(dirpath: str, prefix: str = "BENCH_r") -> List[dict]:
    """Ordered bench records: ``{prefix}*.json`` sorted by round number.
    Each returned dict is the PARSED bench line plus ``_round``/``_file``
    bookkeeping; unusable rounds appear with ``_skip`` set (reason).
    The default prefix is the train lane; the gateway lane lives in
    ``BENCH_GATEWAY_r*.json`` (bench_gateway.py writes it), the
    multichip lane in ``MULTICHIP_r*.json`` (bench_multichip.py), the
    KV-tier churn lane in ``BENCH_PREFIX_r*.json``
    (bench_prefix_churn.py), the self-heal traffic lane in
    ``BENCH_TRAFFIC_r*.json`` (bench_selfheal.py), the durable-session
    resume lane in ``BENCH_SESSION_r*.json`` (bench_session.py), the
    serving-quantization lane in ``BENCH_QUANT_r*.json``
    (bench_quant.py), and the op-profile lane in ``OPPROF_r*.json``
    (opprof cost artifacts,
    synthesized into inverse drift series directly in ``run_check``) —
    all pulled in by ``run_check`` with their own prefixes. The globs are
    disjoint, and pre-lane MULTICHIP artifacts (raw dry-run wrappers
    without a parsed bench line) skip cleanly."""
    out: List[dict] = []
    rx = re.compile(re.escape(prefix) + r"(\d+)\.json$")
    for path in sorted(glob.glob(os.path.join(dirpath,
                                              prefix + "*.json"))):
        m = rx.search(os.path.basename(path))
        if not m:
            continue
        rnd = int(m.group(1))
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError) as e:
            out.append({"_round": rnd, "_file": path,
                        "_skip": f"unreadable: {e}"})
            continue
        # driver wrapper {n, cmd, rc, parsed} or a bare bench line (test
        # fixtures / manual runs)
        if "parsed" in raw or "rc" in raw:
            rc = raw.get("rc", 0)
            parsed = raw.get("parsed")
            if rc != 0 or not isinstance(parsed, dict):
                out.append({"_round": rnd, "_file": path,
                            "_skip": f"rc={rc}, parsed="
                                     f"{'ok' if parsed else parsed}"})
                continue
            rec = dict(parsed)
        elif "value" in raw:
            rec = dict(raw)
        else:
            out.append({"_round": rnd, "_file": path,
                        "_skip": "no parsed bench line"})
            continue
        if not isinstance(rec.get("value"), (int, float)):
            out.append({"_round": rnd, "_file": path,
                        "_skip": "non-numeric value"})
            continue
        rec["_round"] = rnd
        rec["_file"] = path
        out.append(rec)
    return out


def split_series(records: List[dict]) -> dict:
    """Group usable points by (metric, hardware): CPU-proxy and TPU
    points form separate series."""
    series: dict = {}
    for r in records:
        if "_skip" in r:
            continue
        hw = "tpu" if r.get("detail", {}).get("tpu") else "cpu"
        metric = r.get("metric", "unknown")
        lane = r.get("_lane")
        key = (f"{lane}:{metric}" if lane else metric, hw)
        series.setdefault(key, []).append(r)
    return series


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _trend(points: List[float]) -> Optional[float]:
    """Least-squares slope per round (info only)."""
    n = len(points)
    if n < 2:
        return None
    xbar = (n - 1) / 2.0
    ybar = sum(points) / n
    num = sum((i - xbar) * (y - ybar) for i, y in enumerate(points))
    den = sum((i - xbar) ** 2 for i in range(n))
    return num / den if den else None


def check_series(points: List[dict], tolerance: float,
                 window: int) -> dict:
    """Gate the NEWEST point against median(previous ``window``)."""
    values = [float(p["value"]) for p in points]
    result = {
        "n_points": len(values),
        "values": values,
        "rounds": [p["_round"] for p in points],
        "latest": values[-1] if values else None,
        "trend_per_round": _trend(values),
        "status": "pass",
    }
    if len(values) < 2:
        result["status"] = "insufficient_history"
        return result
    prior = values[:-1][-window:]
    baseline = _median(prior)
    bound = (1.0 - tolerance) * baseline
    result.update(baseline=baseline, lower_bound=bound)
    if values[-1] < bound:
        result["status"] = "regression"
        result["drop_frac"] = 1.0 - values[-1] / baseline
    return result


def run_check(dirpath: str, tolerance: float = DEFAULT_TOLERANCE,
              window: int = DEFAULT_WINDOW) -> dict:
    records = discover(dirpath)
    gw_records = discover(dirpath, prefix="BENCH_GATEWAY_r")
    for r in gw_records:
        r["_lane"] = "gateway"
    mc_records = discover(dirpath, prefix="MULTICHIP_r")
    for r in mc_records:
        r["_lane"] = "multichip"
    # synthesize the goodput series from the gateway lane's embedded
    # ledger (detail.goodput_frac_cache_on, written by bench_gateway
    # since round 15): goodput regressions gate exactly like
    # throughput. Older artifacts without the field simply contribute
    # no point (insufficient_history until two rounds carry it).
    goodput_records = []
    for r in gw_records:
        if "_skip" in r:
            continue
        gp = (r.get("detail") or {}).get("goodput_frac_cache_on")
        if isinstance(gp, (int, float)):
            goodput_records.append({
                "metric": "gateway_goodput_frac", "value": float(gp),
                "unit": "frac",
                "detail": {"tpu": (r.get("detail") or {}).get("tpu")},
                "_round": r["_round"], "_file": r["_file"],
                "_lane": "gateway"})
    px_records = discover(dirpath, prefix="BENCH_PREFIX_r")
    for r in px_records:
        r["_lane"] = "prefix"
    # the churn bench's headline value is the TIERED durable hit rate;
    # promotion latency gates as an INVERSE series (promotions/s from
    # detail.promotion_latency_p99_ms) because the band is a lower
    # bound — a latency blowup shows up as the rate collapsing.
    promo_records = []
    for r in px_records:
        if "_skip" in r:
            continue
        p99 = (r.get("detail") or {}).get("promotion_latency_p99_ms")
        if isinstance(p99, (int, float)) and p99 > 0:
            promo_records.append({
                "metric": "prefix_promotion_p99_rate",
                "value": 1000.0 / float(p99), "unit": "promotions/s",
                "detail": {"tpu": (r.get("detail") or {}).get("tpu")},
                "_round": r["_round"], "_file": r["_file"],
                "_lane": "prefix"})
    tr_records = discover(dirpath, prefix="BENCH_TRAFFIC_r")
    for r in tr_records:
        r["_lane"] = "traffic"
    # the self-heal bench's headline value is remediation-on
    # goodput_frac; recovery time gates as an INVERSE series
    # (recoveries per 100 steps from detail.recovery_steps_on) for the
    # same reason as promotion latency — the band is a lower bound, so
    # slower recovery shows up as the rate collapsing.
    recov_records = []
    for r in tr_records:
        if "_skip" in r:
            continue
        rs = (r.get("detail") or {}).get("recovery_steps_on")
        if isinstance(rs, (int, float)) and rs >= 0:
            recov_records.append({
                "metric": "traffic_recovery_rate",
                "value": 100.0 / max(float(rs), 1.0),
                "unit": "recoveries/100steps",
                "detail": {"tpu": (r.get("detail") or {}).get("tpu")},
                "_round": r["_round"], "_file": r["_file"],
                "_lane": "traffic"})
    se_records = discover(dirpath, prefix="BENCH_SESSION_r")
    for r in se_records:
        r["_lane"] = "session"
    # the session bench's headline value is resume goodput (resumed
    # tokens/s through the pipelined promotion stream); time-to-resume
    # gates as an INVERSE series (resumes/s from
    # detail.time_to_resume_ms) because the band is a lower bound — a
    # resume-latency blowup shows up as the rate collapsing.
    ttr_records = []
    for r in se_records:
        if "_skip" in r:
            continue
        ttr = (r.get("detail") or {}).get("time_to_resume_ms")
        if isinstance(ttr, (int, float)) and ttr > 0:
            ttr_records.append({
                "metric": "session_resume_rate",
                "value": 1000.0 / float(ttr), "unit": "resumes/s",
                "detail": {"tpu": (r.get("detail") or {}).get("tpu")},
                "_round": r["_round"], "_file": r["_file"],
                "_lane": "session"})
    qt_records = discover(dirpath, prefix="BENCH_QUANT_r")
    for r in qt_records:
        r["_lane"] = "quant"
    # the quant bench's headline value is int8-weights decode tokens/s;
    # the greedy token-match rate vs the fp arm gates as a SECOND series
    # (detail.token_match_rate) so a quantizer quality regression fails
    # as loudly as a speed one. The band is a lower bound, which is the
    # right direction for a match rate. Driver dry-run wrappers (rc != 0
    # or no parsed line) are already ``_skip`` records from discover and
    # contribute no point.
    match_records = []
    for r in qt_records:
        if "_skip" in r:
            continue
        tm = (r.get("detail") or {}).get("token_match_rate")
        if isinstance(tm, (int, float)):
            match_records.append({
                "metric": "quant_token_match_rate", "value": float(tm),
                "unit": "frac",
                "detail": {"tpu": (r.get("detail") or {}).get("tpu")},
                "_round": r["_round"], "_file": r["_file"],
                "_lane": "quant"})
    # op-level profile lane: OPPROF_r*.json (opprof.write_artifact —
    # bench.py emits one per run). These are cost artifacts, not bench
    # lines, so the series are synthesized here. The band is a LOWER
    # bound, so both drift signals gate as inverse series: the top
    # op-class cost share as HEADROOM (1 - share: a fusion regression
    # concentrating cost into one class collapses the headroom) and
    # the recompile count as 1/(1+n) (a recompile storm collapses the
    # health). Driver dry-run wrappers ({n, cmd, rc} without a
    # `captures` map) skip cleanly like pre-lane MULTICHIP rounds.
    opp_records = []
    opp_rx = re.compile(r"OPPROF_r(\d+)\.json$")
    for path in sorted(glob.glob(os.path.join(dirpath,
                                              "OPPROF_r*.json"))):
        m = opp_rx.search(os.path.basename(path))
        if not m:
            continue
        rnd = int(m.group(1))
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict) or "captures" not in doc:
            continue  # dry-run wrapper, not an opprof artifact
        h = doc.get("headline") or {}
        det = {"tpu": bool(doc.get("tpu"))}
        share = h.get("top_share")
        if isinstance(share, (int, float)):
            opp_records.append({
                "metric": "opprof_top_share_headroom",
                "value": max(0.0, 1.0 - float(share)), "unit": "frac",
                "detail": det, "_round": rnd, "_file": path,
                "_lane": "opprof"})
        nrec = h.get("n_recompiles")
        if isinstance(nrec, (int, float)):
            opp_records.append({
                "metric": "opprof_recompile_health",
                "value": 1.0 / (1.0 + float(nrec)), "unit": "frac",
                "detail": det, "_round": rnd, "_file": path,
                "_lane": "opprof"})
    records = (records + gw_records + mc_records + goodput_records
               + px_records + promo_records + tr_records
               + recov_records + se_records + ttr_records
               + qt_records + match_records + opp_records)
    report = {
        "dir": dirpath,
        "tolerance": tolerance,
        "window": window,
        "skipped": [{"round": r["_round"],
                     "lane": r.get("_lane", "train"),
                     "reason": r["_skip"]}
                    for r in records if "_skip" in r],
        "series": {},
        "status": "pass",
    }
    series = split_series(records)
    if not series:
        report["status"] = "no_history"
        return report
    for (metric, hw), pts in sorted(series.items()):
        res = check_series(pts, tolerance, window)
        report["series"][f"{metric}/{hw}"] = res
        if res["status"] == "regression":
            report["status"] = "regression"
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bench-trajectory regression gate")
    ap.add_argument("--check", action="store_true",
                    help="gate: exit 1 on regression (default prints "
                         "the report without gating)")
    ap.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="allowed drop below the trailing median "
                         "(default 0.10)")
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                    help="trailing points in the median baseline "
                         "(default 4)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON")
    args = ap.parse_args(argv)

    report = run_check(args.dir, tolerance=args.tolerance,
                       window=args.window)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        for s in report["skipped"]:
            print(f"  skip r{s['round']:02d}: {s['reason']}")
        for key, res in report["series"].items():
            line = (f"{key}: {res['n_points']} point(s), "
                    f"latest={res['latest']}")
            if "baseline" in res:
                line += (f", baseline(median{args.window})="
                         f"{res['baseline']:.2f}, "
                         f"bound={res['lower_bound']:.2f}")
            if res["trend_per_round"] is not None:
                line += f", trend={res['trend_per_round']:+.2f}/round"
            print(f"  {line} -> {res['status'].upper()}")
        print(f"bench_guard: {report['status'].upper()} "
              f"(tolerance {args.tolerance:.0%}, dir {report['dir']})")
    if args.check and report["status"] == "regression":
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
