"""Cost attribution plane: per-request waterfalls, the goodput/waste
ledger, and streaming anomaly findings
(paddle_tpu.observability.{waterfall,ledger,anomaly}).

The acceptance bars:
  * a gateway request reconstructs into a COMPLETE waterfall whose
    per-segment self times tile the root span exactly — the invariant
    the ledger's chip-second balance rides on (charged == summed span
    time within 1%);
  * a torn fleet spool (crashed rank, half-written tail line, missing
    root span) degrades to PARTIAL waterfalls flagged ``incomplete`` —
    never an exception;
  * on the shared-prefix workload the ledger reproduces the round-13
    story from traces alone: prefill critical-path time shrinks
    consistent with the measured prefix hit rate, and goodput_frac
    strictly improves cache-on vs cache-off (pad waste priced out);
  * the failover drill's duplicated re-prefill is priced as
    ``waste.requeue_recompute`` and the streaming detector names the
    SURVIVOR replica in a ``tpot_spike`` finding (the remediator's
    input signal).

Everything is single-threaded and deterministic modulo wall-clock
noise; timing assertions use wide ratio bounds.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.gateway import Gateway
from paddle_tpu.inference.serving import (ContinuousBatcher,
                                          PagedContinuousBatcher)
from paddle_tpu.observability import (AnomalyDetector, GatewayProbe,
                                      build_waterfalls,
                                      critical_path_summary, get_recorder,
                                      ledger_from_waterfalls,
                                      render_waterfall,
                                      waterfalls_from_fleet)
from paddle_tpu.observability.export import snapshot_series
from paddle_tpu.resilience import arm_scenario, disarm

from drill_clock import DrillClock

pytestmark = pytest.mark.attr


@pytest.fixture(autouse=True)
def _disarm():
    disarm()
    yield
    disarm()


@pytest.fixture(scope="module")
def lm():
    from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=128,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, size=n).astype(np.int64) for n in sizes]


def _batcher(lm, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("s_max", 64)
    return ContinuousBatcher(lm, compile=False, **kw)


def _trace_mark():
    """Recorder watermark: trace ids recorded BEFORE the workload."""
    return set(get_recorder().trace_ids())


def _waterfalls_since(pre_ids, gids):
    """Waterfalls for exactly these gateway requests: traces newer than
    the watermark, matched back by the root span's gid tag."""
    spans = [s for s in get_recorder().spans()
             if s.trace_id not in pre_ids]
    return [w for w in build_waterfalls(spans) if w.gid in set(gids)]


# -- waterfall reconstruction -------------------------------------------------

def test_waterfall_reconstructs_complete_request(lm):
    prompts = _prompts(3, (5, 9, 7))
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    pre = _trace_mark()
    gids = [gw.submit(p, 6, tenant="wf") for p in prompts]
    gw.run_until_done()
    wfs = _waterfalls_since(pre, gids)
    assert len(wfs) == len(gids)
    for wf in wfs:
        assert not wf.incomplete
        assert wf.tenant == "wf" and wf.gid in gids
        # the serving phases a complete request must traverse
        assert {"queue", "admit", "prefill", "decode"} <= set(wf.phases)
        path_names = [h["name"] for h in wf.critical_path]
        assert {"queue", "prefill", "decode"} <= set(path_names)
        # THE invariant: segment self times tile the root span exactly
        assert sum(s.self_s for s in wf.segments) == \
            pytest.approx(wf.total_s, rel=1e-9)
        assert wf.ttft_s > 0.0 and wf.tpot_s is not None
        assert wf.replicas and wf.replicas[0] in ("r0", "r1")
        # the renderer holds together on real data
        text = render_waterfall(wf)
        assert "critical path:" in text and "prefill" in text


def test_ledger_balances_chip_seconds_and_publishes(lm):
    prompts = _prompts(4, (6, 8, 5, 9))
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    pre = _trace_mark()
    gids = [gw.submit(p, 5, tenant=t, session_id=t)
            for p, t in zip(prompts, ("acme", "acme", "zeta", "zeta"))]
    gw.run_until_done()
    wfs = _waterfalls_since(pre, gids)
    led = ledger_from_waterfalls(wfs)
    s = led.summary()
    # charged chip-seconds == summed span time within 1% (here: exact,
    # every trace is complete so self times tile each root span)
    wall = sum(w.total_s for w in wfs)
    assert abs(s["charged_seconds"] - wall) <= 0.01 * wall
    assert 0.0 < s["chip_seconds"] <= s["charged_seconds"]
    assert 0.0 < s["goodput_frac"] <= 1.0
    assert set(s["by_tenant"]) == {"acme", "zeta"}
    assert {"admit", "prefill", "decode"} <= set(s["by_phase"])
    led.publish()
    series = snapshot_series()
    names = {x["name"] for x in series}
    assert {"ledger.goodput_frac", "ledger.waste_seconds",
            "ledger.chip_seconds"} <= names
    cats = {x["labels"]["category"] for x in series
            if x["name"] == "ledger.waste_seconds"}
    assert {"bucket_pad", "requeue_recompute", "evicted_prefix_recompute",
            "speculation_rejected", "recompile"} <= cats
    tenants = {x["labels"]["tenant"] for x in series
               if x["name"] == "ledger.chip_seconds"}
    assert {"acme", "zeta"} <= tenants


def test_torn_fleet_spool_yields_partial_waterfalls(tmp_path):
    """A crashed rank's spool — root span never closed (absent), decode
    span missing, half-written tail line — must degrade to a partial
    waterfall flagged ``incomplete``, never raise."""
    def span(sid, parent, name, t0, t1, **tags):
        return {"kind": "span", "t": t0, "t_end": t1, "trace_id": "T1",
                "span_id": sid, "parent_id": parent, "name": name,
                "start_ns": int(t0 * 1e9), "end_ns": int(t1 * 1e9),
                "duration_s": t1 - t0, "tags": tags}

    lines = [json.dumps({"kind": "meta", "rank": 0, "host": "h0"})]
    # root "gateway.request" was still open at crash time -> no record;
    # the queue/admit/prefill spans reference the missing parent
    lines += [json.dumps(span("q1", "root1", "queue", 10.0, 10.2)),
              json.dumps(span("a1", "root1", "admit", 10.2, 10.9,
                              replica="r0")),
              json.dumps(span("p1", "a1", "prefill", 10.3, 10.7,
                              prompt_tokens=32, prefix_hit=0))]
    torn = json.dumps(span("d1", "a1", "decode", 10.7, 11.0))[:37]
    with open(tmp_path / "rank00000.jsonl", "w") as fh:
        fh.write("\n".join(lines) + "\n" + torn)

    wfs = waterfalls_from_fleet(str(tmp_path))
    assert len(wfs) == 1
    wf = wfs[0]
    assert wf.incomplete                      # missing root + torn tail
    assert {"queue", "admit", "prefill"} <= set(wf.phases)
    assert "decode" not in wf.phases          # the torn line dropped
    assert wf.total_s == pytest.approx(0.9, rel=1e-6)  # torn decode gone
    # downstream consumers stay well-defined on partial data
    led = ledger_from_waterfalls(wfs)
    assert led.summary()["incomplete"] == 1
    assert led.chip_s > 0.0
    assert "[INCOMPLETE]" in render_waterfall(wf)


# -- the round-13 story, reproduced from traces alone -------------------------

def test_shared_prefix_goodput_and_prefill_shrink_cache_on_vs_off(lm):
    """Two identically-driven paged gateways, radix prefix cache on vs
    off. From the traces alone the ledger must show (a) the prefilled
    rows shrinking by the measured hit rate and (b) goodput_frac
    strictly improving — cache-on admissions land on exact pow2 rungs
    (zero pad) while cache-off pays bucket_pad."""
    rng = np.random.RandomState(7)
    sys_prompts = [rng.randint(0, 128, (80,)).astype(np.int64)  # 10 blocks
                   for _ in range(2)]
    tails = [rng.randint(0, 128, (8 if i % 2 else 16,)).astype(np.int64)
             for i in range(8)]
    warm_tails = [rng.randint(0, 128, (n,)).astype(np.int64)
                  for n in (8, 8, 16)]

    stats = {}
    for label, cached in (("off", False), ("on", True)):
        gw = Gateway(policy="affinity")
        # ONE replica: affinity load-spill to a cold peer would silently
        # dilute the hit rate; n_pages sized so the measured window
        # never evicts — every measured hit is the full 80-row prefix
        gw.add_replica("r0", PagedContinuousBatcher(
            lm, max_batch=4, s_max=112, block_size=8, n_pages=256,
            compile=False, prefix_cache=cached, prompt_buckets="pow2"))
        # warm: per system prompt, one cold full prefill (seeds the
        # radix tree) then one suffix admission at EACH measured tail
        # rung — every prefill shape the measured window uses compiles
        # here, outside the clock
        for si, sysp in enumerate(sys_prompts):
            for wt in warm_tails:
                gw.submit(np.concatenate([sysp, wt]), 4,
                          tenant="warm", session_id=f"s{si}")
        gw.run_until_done()
        pre = set(get_recorder().trace_ids())
        gids = [gw.submit(np.concatenate([sys_prompts[i % 2], t]), 6,
                          tenant="r13", session_id=f"s{i % 2}")
                for i, t in enumerate(tails)]
        gw.run_until_done()
        spans = [s for s in get_recorder().spans()
                 if s.trace_id not in pre]
        wfs = [w for w in build_waterfalls(spans) if w.tenant == "r13"]
        assert len(wfs) == len(gids) and not any(w.incomplete for w in wfs)
        stats[label] = {
            "led": ledger_from_waterfalls(wfs),
            "cp": critical_path_summary(wfs),
            "hit": sum(w.prefix_hit_tokens for w in wfs),
            "prompt": sum(w.prompt_tokens for w in wfs),
        }

    hit_rate = stats["on"]["hit"] / stats["on"]["prompt"]
    # 80 cached rows of each 88/96-row prompt — the round-13 headline
    # hit rate (0.87), reproduced from the prefill spans' tags alone
    assert hit_rate == pytest.approx(640 / 736)
    assert stats["off"]["hit"] == 0
    # (a) prefill shrinks with the hit rate, in rows and not on the clock
    # (two XLA:CPU wall times at this model scale are mostly dispatch
    # overhead and compared badly): cache-on prefills exactly the rows
    # the cache did not hold, cache-off every row of the same prompts;
    # the critical path still carries a prefill phase on both sides
    pf_on = stats["on"]["prompt"] - stats["on"]["hit"]
    pf_off = stats["off"]["prompt"] - stats["off"]["hit"]
    assert pf_off == stats["on"]["prompt"] == 736
    assert pf_on == 96 == round(pf_off * (1.0 - hit_rate))
    assert stats["on"]["cp"]["prefill"] > 0.0
    assert stats["off"]["cp"]["prefill"] > 0.0
    # (b) goodput strictly improves: cache-on suffixes land on exact
    # rungs (8/16 -> zero pad) while cache-off pads 88/96 -> 112
    led_on, led_off = stats["on"]["led"], stats["off"]["led"]
    assert led_off.waste["bucket_pad"] > 0.0
    assert led_on.waste["bucket_pad"] == 0.0
    assert led_on.goodput_frac > led_off.goodput_frac


# -- failover: waste pricing + anomaly naming the survivor --------------------

def test_failover_prices_requeue_waste_and_anomaly_names_survivor(
        lm, monkeypatch):
    """The replica-death drill, read back through the attribution plane:
    total charged chip-seconds balance the span record within 1%, the
    survivor's duplicated re-prefill is priced as
    ``waste.requeue_recompute``, and the ONLINE detector (GatewayProbe)
    emits a tpot_spike finding naming the survivor — whose step time
    jumps when it absorbs the dead replica's re-prefills (on
    tests/drill_clock.py's clock, where a step costs the rows it
    computes; the spans keep the wall clock)."""
    prompts = _prompts(6, (5, 9, 7, 11))
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    DrillClock().install(monkeypatch, gw)
    probe = GatewayProbe(gw, AnomalyDetector(threshold=4.0,
                                             min_samples=6))
    pre = _trace_mark()
    gids = [gw.submit(p, 10) for p in prompts]
    arm_scenario("seed=0; serving.step:transient_error:after=6,count=3")
    for _ in range(1000):
        if not gw._has_work():
            break
        gw.step()
    probe.close()
    alive = [r for r in gw.pool.replicas() if r.alive]
    assert len(alive) == 1
    survivor = alive[0].name
    wfs = _waterfalls_since(pre, gids)
    led = ledger_from_waterfalls(wfs)
    # chip-second balance holds through the failover: every interrupted
    # span was closed (interrupted=1), so self times still tile roots
    wall = sum(w.total_s for w in wfs)
    assert abs(led.charged_s - wall) <= 0.01 * wall
    assert led.waste["requeue_recompute"] > 0.0
    assert sum(w.requeue_overhead_s for w in wfs) > 0.0
    spikes = [f for f in probe.findings if f.kind == "tpot_spike"
              and f.detail["key"] == survivor]
    assert spikes, (survivor,
                    [f.to_dict() for f in probe.findings])
    # findings are fleet-typed: the remediator consumes one format
    d = spikes[0].to_dict()
    assert d["kind"] == "tpot_spike" and d["detail"]["score"] >= 4.0


# -- detector unit behavior ---------------------------------------------------

def test_anomaly_detector_streaming_unit():
    det = AnomalyDetector(threshold=6.0, min_samples=8, window=64)
    # warmup: even a 100x value must NOT fire before min_samples
    assert det.observe("tpot", "r0", 100.0) is None
    for _ in range(7):
        assert det.observe("tpot", "r0", 1.0) is None
    # in-family samples never fire; the early outlier is median-immune
    assert det.observe("tpot", "r0", 1.04) is None
    f = det.observe("tpot", "r0", 5.0)
    assert f is not None and f.kind == "tpot_spike"
    assert f.detail["key"] == "r0" and f.detail["score"] >= 6.0
    assert f.skew_s == pytest.approx(4.0, abs=0.1)
    # series are independent: a fresh key restarts its warmup
    assert det.observe("tpot", "r1", 5.0) is None
    assert det.baseline("tpot", "r0")["median"] == pytest.approx(1.0,
                                                                 abs=0.1)
    assert [x.seq for x in det.findings] == [1]


# -- TP member attribution (satellite) ----------------------------------------

class _FakeShardGroup:
    """Duck-typed distributed.mesh.ShardGroup: 2 healthy members."""
    name = "tp0"
    degree = 2
    members = ["tp0/tensor0", "tp0/tensor1"]
    failed_members: list = []

    def heartbeat(self):
        return None

    def describe(self):
        return {"name": self.name, "members": list(self.members)}


def test_tp_member_labels_in_metrics_and_span_baggage(lm):
    b0 = _batcher(lm)
    b0.shard_group = _FakeShardGroup()
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", b0)
    pre = _trace_mark()
    gids = [gw.submit(p, 4) for p in _prompts(9, (5, 7))]
    gw.run_until_done()
    # per-member step-time attribution: one observation per HEALTHY
    # member per step, labelled {replica, member}
    pairs = {(x["labels"]["replica"], x["labels"]["member"])
             for x in snapshot_series()
             if x["name"] == "replica.step_seconds"}
    assert {("r0", "tp0/tensor0"), ("r0", "tp0/tensor1")} <= pairs
    # span baggage: admits carry the group + member list so waterfalls
    # show WHICH shards a request rode on
    wfs = _waterfalls_since(pre, gids)
    admits = [s for w in wfs for s in w.segments if s.name == "admit"]
    assert admits
    for seg in admits:
        assert seg.tags["tp_group"] == "tp0"
        assert seg.tags["tp_members"] == "tp0/tensor0,tp0/tensor1"
        assert seg.tags["replica"] == "r0"


def test_plain_replica_member_label_falls_back_to_replica_name(lm):
    gw = Gateway(policy="least_loaded")
    gw.add_replica("solo", _batcher(lm))
    gw.submit(_prompts(11, (6,))[0], 3)
    gw.run_until_done()
    pairs = {(x["labels"]["replica"], x["labels"]["member"])
             for x in snapshot_series()
             if x["name"] == "replica.step_seconds"}
    assert ("solo", "solo") in pairs
