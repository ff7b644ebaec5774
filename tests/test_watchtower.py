"""Perf watchtower: request tracing, roofline attribution, SLO burn
alerts, and the bench-trajectory gate.

The acceptance bars:
  * one gateway request's trace decomposes into >= 4 nested spans
    (queue -> admit -> prefill -> decode/stream) sharing ONE trace_id,
    exportable as Chrome trace JSON;
  * a chaos-killed replica's requeued request keeps the ORIGINAL
    trace_id and every post-failover span carries ``requeued=1``;
  * ``roofline.mfu_gap`` = ceiling - observed after jit train steps;
  * multi-window burn-rate alerts fire on a sustained SLO breach and
    stay quiet on a blip (fast window only);
  * ``tools/bench_guard.py --check`` passes the committed history and
    fails a synthetic 20% tokens/s regression.

Everything runs on the CPU proxy in well under the 10s obs budget.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.gateway import Gateway
from paddle_tpu.inference.serving import ContinuousBatcher
from paddle_tpu.observability import (SLO, BurnWindow, SLOMonitor,
                                      TraceContext, get_recorder,
                                      new_trace)
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.observability import roofline_attr
from paddle_tpu.resilience import arm_scenario, disarm

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


def _batcher(lm, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("s_max", 64)
    return ContinuousBatcher(lm, compile=False, **kw)


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, size=n).astype(np.int64) for n in sizes]


# -- trace context unit pieces ------------------------------------------------

def test_trace_context_ids_baggage_and_traceparent_roundtrip():
    ctx = new_trace("request", gid=7)
    assert ctx.root is not None and ctx.root.open
    sp = ctx.begin("phase_a", hint="x")
    assert sp.trace_id == ctx.trace_id
    assert sp.parent_id == ctx.root.span_id
    ctx.baggage["requeued"] = 1
    late = ctx.begin("phase_b")
    assert late.tags["requeued"] == 1        # baggage merges at begin
    assert "requeued" not in sp.tags         # ...not retroactively
    sp.end()
    assert not sp.open and sp.duration_s >= 0
    sp.end(extra=1)                          # idempotent: tags merge only
    assert sp.tags["extra"] == 1
    late.end()
    ctx.finish(ok=1)

    hdr = ctx.traceparent()
    back = TraceContext.from_traceparent(hdr, ctx.baggage_header())
    assert back.trace_id == ctx.trace_id
    assert back.baggage["requeued"] == "1"
    with pytest.raises(ValueError):
        TraceContext.from_traceparent("garbage")


def test_chrome_export_structure():
    rec = get_recorder()
    ctx = new_trace("request")
    ctx.begin("inner").end()
    ctx.finish()
    doc = rec.to_chrome(ctx.trace_id)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in events} == {"request", "inner"}
    for e in events:
        assert e["args"]["trace_id"] == ctx.trace_id
        assert e["ts"] >= 0 and e["dur"] >= 0
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(ctx.trace_id in m["args"]["name"] for m in metas)


# -- gateway trace decomposition ----------------------------------------------

def test_gateway_request_trace_decomposes_ttft(lm, tmp_path):
    gw = Gateway()
    gw.add_replica("r0", _batcher(lm))
    prompt = _prompts(1, (6,))[0]
    sess = gw.stream(prompt, 6)
    toks = list(sess)
    assert len(toks) == 6
    rec = get_recorder()
    tid = rec.trace_ids()[-1]
    spans = rec.spans(tid)
    names = {s.name for s in spans}
    # the acceptance bar: >= 4 nested spans, one trace_id
    assert {"queue", "admit", "prefill", "decode", "stream"} <= names
    assert all(s.trace_id == tid for s in spans)
    by_name = {s.name: s for s in spans}
    root = by_name["gateway.request"]
    assert by_name["queue"].parent_id == root.span_id
    assert by_name["prefill"].parent_id == by_name["admit"].span_id
    assert by_name["decode"].tags["tokens"] == 6
    # exports round-trip
    p = rec.export_chrome(str(tmp_path / "trace.json"), tid)
    doc = json.load(open(p))
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) >= 5
    jl = rec.export_jsonl(str(tmp_path / "trace.jsonl"), tid)
    lines = [json.loads(l) for l in open(jl)]
    assert {l["name"] for l in lines} == names


def test_trace_survives_chaos_failover_with_requeued_tag(lm):
    """A replica dies mid-decode; the resumed request keeps its original
    trace_id, records a ``requeue`` marker, and every span begun after
    the failover carries ``requeued=1``."""
    prompts = _prompts(6, (5, 9, 7, 11))
    gw = Gateway(policy="least_loaded")
    gw.add_replica("r0", _batcher(lm))
    gw.add_replica("r1", _batcher(lm))
    gids = [gw.submit(p, 10) for p in prompts]
    traces = {g: gw._requests[g].trace.trace_id for g in gids}
    arm_scenario("seed=0; serving.step:transient_error:after=6,count=3")
    for _ in range(1000):
        if not gw._has_work():
            break
        gw.step()
    assert gw.stats()["requeued"] > 0
    assert gw.stats()["completions"] == 4
    rec = get_recorder()
    hit = 0
    for g, tid in traces.items():
        spans = rec.spans(tid)
        assert spans and all(s.trace_id == tid for s in spans)
        if not any(s.name == "requeue" for s in spans):
            continue
        hit += 1
        post = [s for s in spans
                if s.name in ("queue", "admit", "prefill", "decode")
                and s.tags.get("requeued") == 1]
        # the failed attempt's interrupted spans closed; the resumed
        # attempt re-ran the whole pipeline under the requeued tag
        assert {"queue", "admit", "prefill", "decode"} \
            <= {s.name for s in post}
        assert any(s.tags.get("interrupted") == 1 for s in spans)
    assert hit > 0, "no requeued request left a trace"


# -- roofline attribution -----------------------------------------------------

def test_roofline_mfu_gap_after_jit_train_steps(monkeypatch):
    from paddle_tpu import hapi, nn, optimizer
    # MFU is published on a TPU only; stand in for one so that the join
    # against the roofline runs here (the net reaches no Pallas kernel)
    monkeypatch.setattr("paddle_tpu.ops.pallas.on_tpu", lambda: True)
    import sys
    # (utils/__init__ rebinds the name `flops` to the function)
    monkeypatch.setattr(sys.modules["paddle_tpu.utils.flops"],
                        "peak_device_flops", lambda device=None: 1e12)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    m = hapi.Model(net)
    m.prepare(optimizer=optimizer.SGD(learning_rate=0.01,
                                      parameters=net.parameters()),
              loss=nn.CrossEntropyLoss(), jit=True)
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (8, 1)).astype(np.int64)
    for _ in range(3):
        m.train_batch([x], [y])
    reg = get_registry()
    observed = reg.get("roofline.observed_mfu").value
    ceiling = reg.get("roofline.mfu_ceiling").value
    gap = reg.get("roofline.mfu_gap").value
    assert observed == reg.get("train_mfu").value
    assert gap == pytest.approx(ceiling - observed, abs=1e-9)
    assert 0.0 < ceiling <= 1.0
    # attribution fractions are a partition of the observed step
    attr = reg.get("roofline.gap_attribution")
    fracs = {ch.labels["phase"]: ch.value for ch in attr.children()}
    assert set(fracs) == {"compute", "memory", "overhead"}
    assert all(0.0 <= v <= 1.0 for v in fracs.values())
    # warm jit steps also feed the steady-state histogram
    assert reg.get("train.fused_step_seconds").count >= 1


def test_roofline_attribution_arithmetic(tmp_path, monkeypatch):
    model = {"configs": [
        {"config": "toy", "params": 1000, "batch": 1, "seq": 100,
         "t_compute_ms": 40.0, "t_memory_ms": 60.0, "bound": "memory",
         "tokens_per_s_bound": 1000.0, "measured_mfu_ceiling": 0.6},
    ]}
    p = tmp_path / "ROOFLINE.json"
    p.write_text(json.dumps(model))
    monkeypatch.setenv("PADDLE_ROOFLINE", str(p))
    roofline_attr.clear_cache()
    try:
        # 100 tokens (scale 1): compute 40ms, memory 60ms -> ideal 60ms;
        # observed 120ms: compute 1/3, exposed memory (60-40)/120 = 1/6,
        # overhead (120-60)/120 = 1/2
        out = roofline_attr.observe_train_step(0.120, observed_mfu=0.2,
                                               tokens=100)
        assert out["mfu_gap"] == pytest.approx(0.4)
        assert out["bound"] == "memory"
        assert out["compute_frac"] == pytest.approx(1 / 3)
        assert out["memory_frac"] == pytest.approx(1 / 6)
        assert out["overhead_frac"] == pytest.approx(1 / 2)
        # serving join: 500 tok/s observed vs 1000 bound -> 0.5
        roofline_attr.observe_serving_step(0.1, tokens=50)
        reg = get_registry()
        assert reg.get("roofline.serving.bound_frac").value \
            == pytest.approx(0.5)
    finally:
        roofline_attr.clear_cache()


def test_roofline_missing_file_is_silent(monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_ROOFLINE",
                       str(tmp_path / "nope.json"))
    roofline_attr.clear_cache()
    try:
        assert roofline_attr.observe_train_step(0.1, 0.5) is None
        roofline_attr.observe_serving_step(0.1, 10)   # no raise
    finally:
        roofline_attr.clear_cache()


# -- SLO burn-rate alerts -----------------------------------------------------

def _slo_rig():
    """Fresh registry histogram + monitor on a fake clock."""
    reg = get_registry()
    name = f"watchtower.test_latency_{os.getpid()}_{id(object())}"
    h = reg.histogram(name, "x")
    clock = [0.0]
    slo = SLO("test", name, threshold_s=0.5, objective=0.9)
    win = BurnWindow(fast_s=10.0, slow_s=60.0, burn_threshold=5.0,
                     severity="page")
    mon = SLOMonitor([slo], windows=[win], registry=reg,
                     clock=lambda: clock[0])
    return h, mon, clock


def test_slo_burn_alert_fires_on_sustained_breach_only():
    h, mon, clock = _slo_rig()
    mon.poll()
    # healthy baseline INSIDE the slow window, older than the fast one
    for _ in range(100):
        h.observe(0.01)
    clock[0] = 25.0
    assert mon.poll() == []
    clock[0] = 34.0
    mon.poll()
    # a BLIP: 100% bad inside the fast window — the slow window is still
    # diluted by the baseline, so no page
    for _ in range(20):
        h.observe(5.0)
    clock[0] = 40.0
    assert mon.poll() == []
    # sustained breach: keep burning until the slow window catches up
    fired = []
    for t in range(1, 30):
        clock[0] = 40.0 + t * 5.0
        for _ in range(20):
            h.observe(5.0)
        fired = mon.poll()
        if fired:
            break
    assert fired and fired[0].slo == "test"
    assert fired[0].severity == "page"
    assert fired[0].burn_fast >= 5.0 and fired[0].burn_slow >= 5.0
    # edge-triggered: still burning -> no duplicate alert
    clock[0] += 5.0
    for _ in range(10):
        h.observe(5.0)
    assert mon.poll() == []
    assert len(mon.alerts) == 1
    summary = mon.summary()
    assert summary["slos"][0]["firing"] == ["page"]
    assert len(summary["alerts"]) == 1


def test_slo_monitor_recovers_and_rearms():
    h, mon, clock = _slo_rig()
    for _ in range(10):
        h.observe(5.0)          # 100% bad from the start
    mon.poll()
    clock[0] = 60.0
    for _ in range(10):
        h.observe(5.0)
    assert len(mon.poll()) == 1          # burning in both windows
    # long healthy stretch clears the windows -> condition re-arms
    for t in range(1, 15):
        clock[0] = 60.0 + t * 10.0
        for _ in range(200):
            h.observe(0.01)
        mon.poll()
    assert mon.summary()["slos"][0]["firing"] == []
    clock[0] += 10.0
    for _ in range(400):
        h.observe(5.0)
    clock[0] += 60.0
    for _ in range(400):
        h.observe(5.0)
    assert len(mon.poll()) == 1          # re-fired after re-arming


def test_default_gateway_slos_read_real_histograms(lm):
    from paddle_tpu.observability import default_gateway_slos
    gw = Gateway()
    gw.add_replica("r0", _batcher(lm))
    mon = SLOMonitor(default_gateway_slos(ttft_s=2.5, tpot_s=2.5))
    mon.poll()
    gids = [gw.submit(p, 4) for p in _prompts(2, (5, 6))]
    gw.run_until_done()
    mon.poll()
    s = mon.summary()
    ttft = next(x for x in s["slos"] if x["name"] == "gateway_ttft")
    assert ttft["total"] >= 2        # the histogram really was read
    assert gids


# -- bench trajectory gate ----------------------------------------------------

def _guard(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_guard.py")]
        + args, capture_output=True, text=True)


def test_bench_guard_passes_committed_history():
    r = _guard(["--check", "--json"])
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["status"] in ("pass", "no_history")
    if report["series"]:
        # the wedged r01 round is skipped, not a failure
        assert any(s["reason"].startswith("rc=")
                   for s in report["skipped"]) or not report["skipped"]


def test_bench_guard_fails_synthetic_regression(tmp_path):
    hist = [21823.39, 22649.3, 22886.63, 23086.26]
    for i, v in enumerate(hist, start=2):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps(
            {"n": i, "rc": 0, "parsed": {
                "metric": "llama_train_tokens_per_sec_per_chip",
                "value": v, "unit": "tokens/s",
                "detail": {"tpu": False}}}))
    ok = _guard(["--check", "--dir", str(tmp_path)])
    assert ok.returncode == 0, ok.stdout + ok.stderr
    # a 20% tokens/s drop must gate
    (tmp_path / "BENCH_r06.json").write_text(json.dumps(
        {"n": 6, "rc": 0, "parsed": {
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": 0.8 * hist[-1], "unit": "tokens/s",
            "detail": {"tpu": False}}}))
    bad = _guard(["--check", "--dir", str(tmp_path), "--json"])
    assert bad.returncode == 1
    report = json.loads(bad.stdout)
    key = "llama_train_tokens_per_sec_per_chip/cpu"
    assert report["series"][key]["status"] == "regression"
    assert report["series"][key]["drop_frac"] == pytest.approx(0.2,
                                                               abs=0.02)
    # TPU and CPU points never gate each other
    (tmp_path / "BENCH_r07.json").write_text(json.dumps(
        {"n": 7, "rc": 0, "parsed": {
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": 29025.0, "unit": "tokens/s",
            "detail": {"tpu": True}}}))
    mixed = _guard(["--json", "--dir", str(tmp_path)])
    rep = json.loads(mixed.stdout)
    tpu_key = "llama_train_tokens_per_sec_per_chip/tpu"
    assert rep["series"][tpu_key]["status"] == "insufficient_history"


def test_bench_guard_multichip_lane_disjoint(tmp_path):
    """MULTICHIP_r*.json is its own lane: pre-lane dry-run wrappers
    (rounds without a parsed bench line) skip cleanly, the series gates
    independently, and train-lane history is never consulted."""
    (tmp_path / "MULTICHIP_r05.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
         "tail": "dryrun_multichip(8): OK"}))
    hist = [350.0, 362.0, 371.0, 380.0]
    for i, v in enumerate(hist, start=6):
        (tmp_path / f"MULTICHIP_r{i:02d}.json").write_text(json.dumps(
            {"metric": "multichip_sharded_train_tokens_per_sec",
             "value": v, "unit": "tokens/s",
             "detail": {"tpu": False}}))
    ok = _guard(["--check", "--dir", str(tmp_path), "--json"])
    assert ok.returncode == 0, ok.stdout + ok.stderr
    report = json.loads(ok.stdout)
    key = "multichip:multichip_sharded_train_tokens_per_sec/cpu"
    assert report["series"][key]["status"] == "pass"
    assert list(report["series"]) == [key]   # no train/gateway bleed
    assert any(s["lane"] == "multichip" and s["round"] == 5
               for s in report["skipped"])
    # a 20% sharded-rate drop gates this lane like any other
    (tmp_path / "MULTICHIP_r10.json").write_text(json.dumps(
        {"metric": "multichip_sharded_train_tokens_per_sec",
         "value": 0.8 * hist[-1], "unit": "tokens/s",
         "detail": {"tpu": False}}))
    bad = _guard(["--check", "--dir", str(tmp_path), "--json"])
    assert bad.returncode == 1
    assert json.loads(bad.stdout)["series"][key]["status"] == "regression"


def test_telemetry_dump_chrome_and_slo_flags():
    """Flag plumbing only (--no-workload keeps it fast)."""
    tool = os.path.join(REPO, "tools", "telemetry_dump.py")
    r = subprocess.run(
        [sys.executable, tool, "--format", "chrome", "--no-workload"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "traceEvents" in json.loads(r.stdout)
    r = subprocess.run(
        [sys.executable, tool, "--format", "jsonl", "--no-workload",
         "--slo"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "# slo summary" in r.stdout
    # incompatible combos error out loudly
    r = subprocess.run(
        [sys.executable, tool, "--format", "chrome", "--snapshot", "x"],
        capture_output=True, text=True)
    assert r.returncode != 0
