"""Fleet telemetry plane + flight recorder (ISSUE 9, tier-1 ``fleet``).

Covers, bottom-up:

  * the binary ring journal — wraparound, reopen (epoch bump + seq
    continuity, geometry adopted from the file), corrupt-slot skip;
  * registry-wide default labels — no ``rank`` label in a single-process
    world (byte-identical output), env-stamped when the launcher env is
    present, explicit overrides;
  * dropped-span surfacing — ``TraceRecorder.dropped``, chrome-trace
    metadata, and the one-time warning;
  * shard aggregation over synthetic rank shards — counter sum,
    histogram bucket merge with re-estimated quantiles, per-rank gauges,
    skew gauges, straggler / desync / missing-rank findings;
  * the end-to-end 3-process chaos drill: ``kill_rank`` takes rank 2
    down mid-``all_reduce``; survivors' shards aggregate, the typed
    findings name the collective and the rank, and ``tools/blackbox.py
    postmortem`` replays the victim's ring (< 60s wall clock).
"""
import json
import logging
import os
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLEET_WORKER = os.path.join(REPO, "tests", "helpers",
                            "mp_fleet_worker.py")


# -- flight recorder: ring journal -------------------------------------------

def test_ring_wraparound_keeps_last_n(tmp_path):
    from paddle_tpu.observability.flight import FlightRecorder, read_ring
    path = str(tmp_path / "r.ring")
    rec = FlightRecorder(path, slots=8, slot_size=128, rank=3)
    for i in range(20):
        rec.record("tick", i=i)
    rec.close()
    events = read_ring(path)
    assert [e["i"] for e in events] == list(range(12, 20))
    assert [e["_seq"] for e in events] == list(range(12, 20))
    assert all(e["_rank"] == 3 for e in events)


def test_ring_reopen_bumps_epoch_and_continues_seq(tmp_path):
    from paddle_tpu.observability.flight import FlightRecorder, read_ring
    path = str(tmp_path / "r.ring")
    rec = FlightRecorder(path, slots=8, slot_size=128, rank=0)
    for i in range(3):
        rec.record("before", i=i)
    assert rec.epoch == 0
    rec.close()
    # reopen with DIFFERENT ctor geometry: the file's shape wins
    rec2 = FlightRecorder(path, slots=64, slot_size=512, rank=0)
    assert rec2.nslots == 8 and rec2.slot_size == 128
    assert rec2.epoch == 1
    assert rec2.seq == 3          # cursor recovered by max-seq scan
    rec2.record("after", i=99)
    rec2.close()
    events = read_ring(path)
    assert [e["kind"] for e in events] == ["before"] * 3 + ["after"]
    assert [e["_epoch"] for e in events] == [0, 0, 0, 1]
    assert events[-1]["_seq"] == 3


def test_ring_corrupt_slot_skipped_not_fatal(tmp_path):
    from paddle_tpu.observability.flight import FlightRecorder, read_ring
    path = str(tmp_path / "r.ring")
    rec = FlightRecorder(path, slots=8, slot_size=128, rank=0)
    for i in range(4):
        rec.record("tick", i=i)
    rec.close()
    with open(path, "r+b") as f:      # scribble over slot 1 (seq 1)
        f.seek(64 + 1 * 128)
        f.write(b"\xff" * 64)
    events = read_ring(path)
    assert [e["i"] for e in events] == [0, 2, 3]


def test_ring_oversized_payload_truncates(tmp_path):
    from paddle_tpu.observability.flight import FlightRecorder, read_ring
    path = str(tmp_path / "r.ring")
    rec = FlightRecorder(path, slots=4, slot_size=64, rank=0)
    rec.record("big", blob="x" * 500)
    rec.close()
    (ev,) = read_ring(path)
    assert ev["kind"] == "big" and ev.get("truncated") is True


# -- metrics: registry-wide default labels -----------------------------------

@pytest.fixture
def fresh_env(monkeypatch):
    from paddle_tpu.observability.fleet import reset_spool
    from paddle_tpu.observability.flight import reset_flight
    monkeypatch.delenv("PADDLE_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    reset_spool()
    reset_flight()
    yield monkeypatch
    reset_spool()
    reset_flight()


def test_default_labels_absent_single_process(fresh_env):
    from paddle_tpu.observability.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("fleettest.c").inc(2)
    reg.gauge("fleettest.g").set(1.5)
    for s in reg.snapshot(include_native=False):
        assert "rank" not in s["labels"], s


def test_default_labels_stamp_rank_from_env(fresh_env):
    from paddle_tpu.observability.metrics import MetricsRegistry
    fresh_env.setenv("PADDLE_TRAINERS_NUM", "4")
    fresh_env.setenv("PADDLE_TRAINER_ID", "2")
    reg = MetricsRegistry()
    reg.counter("fleettest.c").inc(1)
    reg.histogram("fleettest.h").observe(0.1)
    snap = reg.snapshot(include_native=False)
    assert snap and all(s["labels"]["rank"] == "2" for s in snap)
    # explicit series labels survive the merge
    reg.counter("fleettest.lc", labelnames=("op",)).labels(op="x").inc()
    snap = reg.snapshot(include_native=False)
    lc = next(s for s in snap if s["name"] == "fleettest.lc")
    assert lc["labels"] == {"rank": "2", "op": "x"}


def test_default_labels_explicit_override(fresh_env):
    from paddle_tpu.observability.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.set_default_labels(rank="7", host="hX")
    reg.counter("fleettest.c").inc()
    (s,) = reg.snapshot(include_native=False)
    assert s["labels"] == {"rank": "7", "host": "hX"}
    reg.clear_default_labels()
    (s,) = reg.snapshot(include_native=False)
    assert s["labels"] == {}


# -- trace recorder: dropped-span surfacing ----------------------------------

def test_dropped_spans_property_metadata_and_one_time_warning(
        fresh_env, caplog):
    from paddle_tpu.observability.trace_context import (TraceRecorder,
                                                        TraceSpan)
    rec = TraceRecorder(capacity=2)
    spans = [TraceSpan(f"{i:016x}", "s") for i in range(4)]
    with caplog.at_level(logging.WARNING,
                         logger="paddle_tpu.observability.trace_context"):
        for sp in spans:
            rec.record(sp)
    assert rec.dropped == 2
    assert rec.capacity == 2
    warnings = [r for r in caplog.records
                if "trace recorder full" in r.getMessage()]
    assert len(warnings) == 1            # one-time, not per drop
    doc = rec.to_chrome()
    assert doc["metadata"] == {"dropped_spans": 2, "capacity": 2}
    rec.clear()
    assert rec.dropped == 0
    assert rec.to_chrome()["metadata"]["dropped_spans"] == 0


# -- fleet aggregation over synthetic shards ---------------------------------

def _write_shard(dirpath, rank, records, world=3):
    path = os.path.join(dirpath, f"rank{rank:05d}.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "rank": rank,
                            "world_size": world, "host": "h",
                            "pid": 100 + rank, "t": 0.0}) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")
    return path


def _hist_series(counts, count, total, mn, mx):
    return {"name": "fleettest.lat", "type": "histogram", "labels": {},
            "buckets": [1.0, 2.0], "bucket_counts": counts,
            "count": count, "sum": total, "min": mn, "max": mx,
            "quantiles": {}}


def test_fleet_series_counter_sum_histogram_merge_gauge_per_rank(
        tmp_path):
    from paddle_tpu.observability.fleet import FleetAggregator
    d = str(tmp_path)
    _write_shard(d, 0, [{"kind": "metrics", "t": 1.0, "series": [
        {"name": "fleettest.c", "type": "counter",
         "labels": {"rank": "0", "op": "x"}, "value": 2},
        {"name": "fleettest.g", "type": "gauge", "labels": {"rank": "0"},
         "value": 1.5, "peak": 2.0},
        _hist_series([1, 2, 0], 3, 4.0, 0.5, 1.8)]}])
    _write_shard(d, 1, [{"kind": "metrics", "t": 1.1, "series": [
        {"name": "fleettest.c", "type": "counter",
         "labels": {"rank": "1", "op": "x"}, "value": 3},
        {"name": "fleettest.g", "type": "gauge", "labels": {"rank": "1"},
         "value": 7.0, "peak": 7.0},
        _hist_series([0, 1, 3], 4, 9.0, 0.9, 5.0)]}])
    agg = FleetAggregator(d)
    assert agg.ranks() == [0, 1]
    series = {(s["name"], tuple(sorted(s["labels"].items()))): s
              for s in agg.fleet_series()}
    c = series[("fleettest.c", (("op", "x"),))]
    assert c["value"] == 5 and c["ranks"] == [0, 1]
    assert "rank" not in c["labels"]
    h = series[("fleettest.lat", ())]
    assert h["bucket_counts"] == [1, 3, 3]
    assert h["count"] == 7 and h["sum"] == pytest.approx(13.0)
    assert h["min"] == 0.5 and h["max"] == 5.0
    # merged cumulative buckets: p50 target 3.5 -> bound 2.0; p99 spills
    # past the finite buckets -> merged max
    assert h["quantiles"]["p50"] == 2.0
    assert h["quantiles"]["p99"] == 5.0
    g0 = series[("fleettest.g", (("rank", "0"),))]
    g1 = series[("fleettest.g", (("rank", "1"),))]
    assert g0["value"] == 1.5 and g1["value"] == 7.0
    rr = series[("fleet.ranks_reporting", ())]
    assert rr["value"] == 2.0


def test_findings_straggler_desync_missing_rank(tmp_path):
    from paddle_tpu.observability.fleet import FleetAggregator
    d = str(tmp_path)

    def coll(phase, op, seq, t):
        return {"kind": "collective", "phase": phase, "op": op,
                "seq": seq, "t": t}

    base = 100.0
    # seq 1: clean. seq 2: rank 1 arrives 0.5s late (straggler).
    # seq 3: rank 2 entered a DIFFERENT op (desync). seq 4: rank 2
    # enters and never exits, then goes silent while 0/1 keep writing.
    for rank, skew2 in ((0, 0.0), (1, 0.5), (2, 0.01)):
        recs = [coll("enter", "all_reduce", 1, base),
                coll("exit", "all_reduce", 1, base + 0.01),
                coll("enter", "all_reduce", 2, base + 1 + skew2),
                coll("exit", "all_reduce", 2, base + 1.6),
                coll("enter",
                     "broadcast" if rank == 2 else "all_reduce",
                     3, base + 2),
                coll("exit",
                     "broadcast" if rank == 2 else "all_reduce",
                     3, base + 2.1),
                coll("enter", "all_reduce", 4, base + 3)]
        if rank != 2:
            recs.append({"kind": "event", "name": "watchdog_abort",
                         "t": base + 8.0})
        _write_shard(d, rank, recs)
    agg = FleetAggregator(d)
    by_kind = {}
    for f in agg.findings():
        by_kind.setdefault(f.kind, []).append(f)
    (strag,) = by_kind["straggler"]
    assert strag.op == "all_reduce" and strag.seq == 2
    assert strag.rank == 1 and strag.skew_s == pytest.approx(0.5, 0.05)
    (desync,) = by_kind["desync"]
    assert desync.seq == 3 and desync.rank == 2
    assert desync.op == "broadcast"
    assert desync.detail["op_by_rank"]["2"] == "broadcast"
    (missing,) = by_kind["missing_rank"]
    assert missing.rank == 2 and missing.op == "all_reduce"
    assert missing.seq == 4
    assert missing.detail["silent_for_s"] == pytest.approx(5.0, 0.1)
    # survivors blocked in the same seq-4 enter are NOT missing
    assert all(f.rank == 2 for f in by_kind["missing_rank"])
    # skew gauges ride the fleet series
    skews = [s for s in agg.fleet_series()
             if s["name"] == "collective.skew_seconds"]
    assert {(s["labels"]["op"], s["labels"]["quantile"])
            for s in skews} >= {("all_reduce", "p50"),
                                ("all_reduce", "p99")}


def test_spool_roundtrip_and_torn_tail_tolerated(tmp_path, fresh_env):
    from paddle_tpu.observability import fleet
    fresh_env.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
    fresh_env.setenv("PADDLE_TRAINERS_NUM", "2")
    fresh_env.setenv("PADDLE_TRAINER_ID", "1")
    fleet.reset_spool()
    fleet.spool_event("hello", x=1)
    fleet.spool_metrics()
    tok = fleet.on_collective_enter("all_reduce")
    assert tok is not None
    fleet.on_collective_exit(tok, "all_reduce")
    sp = fleet.get_spool()
    assert sp is not None and sp.path.endswith("rank00001.jsonl")
    with open(sp.path, "a") as f:      # simulate a crash mid-line
        f.write('{"kind": "event", "na')
    agg = fleet.FleetAggregator(str(tmp_path))
    shard = agg.shards[1]
    assert shard.meta["world_size"] == 2
    assert [e["name"] for e in shard.events] == ["hello"]
    assert len(shard.snapshots) == 1
    assert [c["phase"] for c in shard.collectives] == ["enter", "exit"]
    assert agg.collective_timeline()[0]["op_by_rank"] == {1: "all_reduce"}


# -- the 3-process kill drill -------------------------------------------------

def _launch_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_COLLECTIVE_WATCHDOG"] = "1"
    env.pop("XLA_FLAGS", None)   # conftest's 8-device forcing: 1/proc
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return env


def test_three_rank_kill_drill_fleet_forensics(tmp_path, monkeypatch):
    """Acceptance drill: chaos kills rank 2 mid-all_reduce in a
    3-process world; the survivors' shards merge into a fleet view, the
    straggler + missing-rank findings name the op and ranks, and the
    blackbox postmortem replays the victim's ring."""
    t0 = time.monotonic()
    tele = tmp_path / "telemetry"
    tele.mkdir()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "3", "--log_dir", str(tmp_path / "logs"),
         FLEET_WORKER, str(tele)],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=_launch_env())
    logs = ""
    log_root = tmp_path / "logs"
    if log_root.exists():
        for f in sorted(log_root.iterdir()):
            logs += f"\n--- {f.name} ---\n" + f.read_text()
    assert proc.returncode == 0, (
        f"launch rc={proc.returncode}\nstdout:{proc.stdout[-2000:]}\n"
        f"stderr:{proc.stderr[-2000:]}\nlogs:{logs[-4000:]}")
    for r in range(3):
        assert f"MPFLEET_START rank={r}/3" in logs, logs[-4000:]
    assert "MPFLEET_VICTIM_ALIVE rank=2" in logs, logs[-4000:]
    # the kill fired: nobody completed all 8 steps
    assert "MPFLEET_OK" not in logs, logs[-4000:]

    # 1) merged fleet view holds every rank's series (victim included —
    #    its shard is complete up to the kill)
    from paddle_tpu.observability.fleet import FleetAggregator
    agg = FleetAggregator(str(tele))
    assert agg.ranks() == [0, 1, 2], agg.ranks()
    names = {s["name"] for s in agg.fleet_series()}
    assert "collective_calls_total" in names
    assert "fleet.ranks_reporting" in names
    calls = next(s for s in agg.fleet_series()
                 if s["name"] == "collective_calls_total"
                 and s["labels"].get("op") == "all_reduce")
    assert sorted(calls["ranks"]) == [0, 1, 2]
    # spans from every rank landed on the shared clock
    span_ranks = {s["rank"] for s in agg.spans()}
    assert span_ranks == {0, 1, 2}, span_ranks

    # 2) typed findings name the collective and the rank. Threshold 2s:
    #    the victim is silent for ~4s (the watchdog timeout) before the
    #    survivors' last writes; the survivors themselves differ only by
    #    watchdog poll jitter (<1s) and must NOT be flagged.
    monkeypatch.setenv("PADDLE_FLEET_SILENCE_THRESHOLD", "2.0")
    findings = agg.findings()
    by_kind = {}
    for f in findings:
        by_kind.setdefault(f.kind, []).append(f)
    assert "missing_rank" in by_kind, [str(f) for f in findings]
    (missing,) = by_kind["missing_rank"]
    assert missing.rank == 2 and missing.op == "all_reduce"
    stragglers = by_kind.get("straggler", [])
    assert any(f.rank == 1 and f.op == "all_reduce"
               for f in stragglers), [str(f) for f in findings]

    # 3) the victim's ring journal survived the os._exit and replays in
    #    order, ending on the chaos injection
    from paddle_tpu.observability.flight import build_postmortem
    pm = build_postmortem(str(tele))
    assert set(pm["ranks"]) == {"0", "1", "2"}
    victim = pm["ranks"]["2"]
    assert victim["last_event"]["kind"] == "chaos"
    assert victim["last_event"]["point"] == "collective.enter"
    assert victim["last_event"]["fault"] == "kill_rank"
    assert victim["suspect_death"] is not None
    assert victim["open_collectives"], victim
    from paddle_tpu.observability.flight import read_ring
    ring = read_ring(os.path.join(str(tele), "flight-rank00002.ring"))
    seqs = [e["_seq"] for e in ring]
    assert seqs == sorted(seqs)
    kinds = [e["kind"] for e in ring]
    assert "collective_enter" in kinds and "span_open" in kinds
    assert kinds[-1] == "chaos"
    # enter of the fatal collective precedes the chaos event
    assert kinds.index("chaos") > len(kinds) - 3

    # both CLIs render the same story (launched concurrently — each
    # pays a full interpreter+package import, the dominant cost here)
    bb_p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "blackbox.py"),
         "postmortem", "--dir", str(tele)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_launch_env())
    td_p = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools",
                                      "telemetry_dump.py"),
         "--fleet", str(tele)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_launch_env())
    bb_out, bb_err = bb_p.communicate(timeout=60)
    td_out, td_err = td_p.communicate(timeout=60)
    assert bb_p.returncode == 0, bb_out + bb_err
    assert "SUSPECT DEATH" in bb_out
    assert "rank 2:" in bb_out
    assert "chaos" in bb_out
    assert td_p.returncode == 0, td_out + td_err
    assert "collective_calls_total" in td_out
    assert '"kind": "missing_rank"' in td_out

    elapsed = time.monotonic() - t0
    assert elapsed < 60.0, f"fleet drill took {elapsed:.1f}s (budget 60)"
